"""Collectives: per training step, the time on the first chip in which a
collective op (collective-permute, all-gather, reduce-scatter,
all-reduce, or the start/done halves of an async one) ran and no other op
did.  Should move ``train_tokens_per_s``."""

from bench.harness import trace as TR


def read(ctx):
    tr = ctx["trace"]
    dev = ctx["devices"][0]
    steps = ctx.get("traced_steps", 0)
    if not steps or not any(TR.is_collective(e.name)
                            for e in tr.ops.get(dev, [])):
        return None
    return TR.exposed_collective_ns(tr, dev) / steps / 1e6

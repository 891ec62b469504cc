"""Train step: model FLOP/s utilization.  Forward + backward FLOPs per
token from the shapes (no recomputation counted) times the tokens per
second of the traced window, over the chips' bf16 peak.  Should move
``train_tokens_per_s``."""

from bench.harness import flops as F


def read(ctx):
    if not ctx.get("traced_steps") or ctx.get("traced_s", 0) <= 0:
        return None
    model = F.Dense.of(ctx["config"])
    tok_s = ctx["traced_steps"] * ctx["tokens_per_step"] / ctx["traced_s"]
    fl = model.train_flops_per_token(ctx["seq_len"]) * tok_s
    return 100.0 * fl / (len(ctx["devices"]) * ctx["peak"]["flops_bf16"])

"""Training path: the BSP data-parallel step as ``launch/train.run`` builds
it (``trainer.make_bsp_train_step``: explicit reduce-scatter, ZeRO-1
AdamW, all-gather), fed as ``TrainLoop`` feeds it.

Set-up (counted in ``setup_s``) builds one object, the compiled step with
its state: parameters drawn from the seed on the devices in one jitted
call, the ZeRO-1 moments, the synthetic token stream (``SyntheticLM``
under the seed, through its prefetcher).  It drives that object through
its first three steps with ``TrainLoop`` itself, reading what the
correctness check compares, and hands the same object to the window.

Window: one step per batch, exactly ``TrainLoop``'s body (next prefetched
batch, placed with the batch shardings, the step, a block on the new
parameters, the loss read back), until ``seconds`` have passed; the rate
is over every step and all the time up to the end of the last one.  The
window drives that body itself: a ``TrainLoop`` run keeps the state it
started from alive to its end, a third copy of the parameters that one
chip cannot hold at this size.

Correctness, once the window has closed and the program's state is
freed: the plain float32 reference trains the same three global batches
from the same draw of weights, with the configuration's AdamW.  Compared,
each by the worst case: each step's loss; each leaf's first gradient
norm, as the optimizer got it (read from the first moment after one
step: m = (1 - beta1) g); each leaf's change after three steps.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench.harness import common as H
from bench.harness import flops as F
from bench.harness import model as M
from bench.harness import reference as R
from bench.harness import trace as TR
from bench.harness import weights as W

CHECK_STEPS = 3
TRACE_S = 4.0            # seconds of the window the profiler records


def leaf_ranges(engine, n_leaves: int, world: int
                ) -> List[List[List[Tuple[int, int]]]]:
    """Where each leaf lies in each rank's shard of the ZeRO-1 moment
    vector: ``out[r][leaf]`` lists [start, end) ranges of rank r's local
    vector.  Rank r holds, bucket after bucket, the slice of the packed
    bucket at its bit-reversed position."""
    bits = max(0, world.bit_length() - 1)

    def rev(r):
        return int(format(r, f"0{bits}b")[::-1], 2) if bits else 0

    out = [[[] for _ in range(n_leaves)] for _ in range(world)]
    for r in range(world):
        q = rev(r)
        for b, off in zip(engine.buckets, engine.shard_offsets()):
            s = engine.shard_len(b)
            lo, hi = q * s, (q + 1) * s          # this rank's packed slice
            pos = 0
            for i in b.leaf_ids:
                n = engine.leaf_specs[i].size
                a0, a1 = max(pos, lo), min(pos + n, hi)
                if a1 > a0:
                    out[r][i].append((off + a0 - lo, off + a1 - lo))
                pos += n
    return out


def leaf_sq_sums(ranges, mesh):
    """A jitted function of the global moment vector (sharded over the
    data axis): each leaf's sum of squares.  Each rank sums static slices
    of its own shard, so nothing the size of the vector is made."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def on_rank(r):
        def f(v):
            return jnp.stack([sum((jnp.sum(jnp.square(v[a:b]))
                                   for a, b in rs), jnp.float32(0))
                              for rs in ranges[r]])
        return f

    def local(v):
        r = jax.lax.axis_index("data")
        sums = jax.lax.switch(r, [on_rank(k) for k in range(len(ranges))], v)
        return jax.lax.psum(sums, "data")

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(), check_vma=False))


def run(cell, *, devices, seed: int, seconds: float, trace: bool,
        process_start: float, workdir, fault=None) -> H.Run:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import superstep
    from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.runtime import trainer
    from repro.runtime.loop import LoopConfig, TrainLoop

    c, mix = cell.config, cell.traffic
    n = len(devices)
    if mix["chips"] != n:
        raise ValueError(f"the mix is for {mix['chips']} chips, the cell has {n}")
    cfg = M.arch_config(c)
    acfg = M.adamw_config(c)
    bsp = M.bsp_config(c)
    compiles = H.Compiles()
    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)
    step_fn, init_state = trainer.make_bsp_train_step(cfg, mesh, acfg, bsp)
    if fault is not None:
        step_fn = fault(step_fn)
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    names = list(W.names(shapes))
    params = W.make_params(shapes, seed, NamedSharding(mesh, P()))
    state = init_state(params)
    del params
    gb, seq = mix["batch_per_chip"] * n, mix["seq_len"]
    data = SyntheticLM(cfg, DataConfig(global_batch=gb, seq_len=seq,
                                       seed=seed))
    bshard = {k: NamedSharding(mesh, P("data", None))
              for k in ("tokens", "labels")}

    def loop(start, stop, st):
        lp = TrainLoop(step_fn=step_fn, state=st, data=data,
                       cfg=LoopConfig(total_steps=stop, log_every=0,
                                      checkpoint_every=1 << 30),
                       batch_shardings=bshard, start_step=start)
        out = lp.run()
        return lp.state, [h["loss"] for h in out["history"]]

    # -- the first steps, through TrainLoop, with the readings -------------
    # (one TrainLoop run per step: a run holds the state it started from)
    state, losses = loop(0, 1, state)
    engine = superstep.engine_for(shapes, bsp, (n,),
                                  force_dtype=jnp.float32, zero1=True)
    sq = np.asarray(leaf_sq_sums(leaf_ranges(engine, len(names), n), mesh)(
        state[1]))
    g_prog = {nm: float(np.sqrt(sq[i])) / (1 - acfg.beta1)
              for i, nm in enumerate(names)}
    for k in range(1, CHECK_STEPS):
        state, more = loop(k, k + 1, state)
        losses += more
    # each leaf's change: its first value drawn again, one leaf at a time
    parts = W.seed_parts(seed)
    sds = W.names(shapes)
    d_prog = {}
    for nm, p in zip(names, jax.tree.leaves(state[0])):
        change = jax.jit(lambda p, s, nm=nm: jnp.linalg.norm(
            (p.astype(jnp.float32)
             - W.draw_leaf(s, nm, sds[nm]).astype(jnp.float32)).reshape(-1)))
        d_prog[nm] = float(change(p, parts))

    # -- the window --------------------------------------------------------
    def window(t_end, box, step0, steps_log):
        """Steps until ``t_end``; the state lives only in ``box``, so the
        parameters a step replaces are freed before the next step."""
        pf = Prefetcher(data, start_step=step0)
        step = step0
        try:
            while time.monotonic() < t_end:
                with H.span(trace, "bench.feed"):
                    _, host = pf.next()
                    batch = {k: jax.device_put(v, bshard[k])
                             for k, v in host.items()}
                with H.span(trace, "bench.train_step"):
                    *parts, met = step_fn(*box[0], batch)
                    box[0] = tuple(parts)
                    jax.block_until_ready(parts[0])
                with H.span(trace, "bench.loss_read"):
                    steps_log.append(float(np.asarray(met["loss"])))
                step += 1
        finally:
            pf.close()
        return step

    setup_s = time.monotonic() - process_start
    H.log(f"setup {setup_s:.1f} s; {compiles.count} compiles; losses "
          f"{losses}")
    before = compiles.count
    win_losses: List[float] = []
    t0 = time.monotonic()
    tdir = None
    step = CHECK_STEPS
    box = [state]
    del state
    if trace:
        with H.profiled(True, workdir, f"{cell.name}-{seed}") as tdir:
            # the traced rate leaves out the profiler's start and stop
            t_tr = time.monotonic()
            step = window(t_tr + min(seconds, TRACE_S), box, step,
                          win_losses)
            traced_s = time.monotonic() - t_tr
        traced_steps = len(win_losses)
    step = window(t0 + seconds, box, step, win_losses)
    elapsed = time.monotonic() - t0
    window_compiles = compiles.count - before
    peak = H.peak_bytes(devices)
    n_steps = len(win_losses)
    tok_s = n_steps * gb * seq / elapsed
    metrics = {"setup_s": setup_s, "train_tokens_per_s": tok_s}
    extra = {"window_steps": n_steps, "window_s": elapsed,
             "window_compiles": window_compiles,
             "global_batch": gb, "seq_len": seq,
             "finite_losses": bool(np.all(np.isfinite(win_losses)))}
    H.log(f"window: {n_steps} steps in {elapsed:.2f} s, {tok_s:.1f} "
          f"tokens/s, {window_compiles} compiles")
    ctx: Dict[str, Any] = {}
    if trace:
        ctx = _reduce(tdir, c, devices, traced_steps, traced_s, gb, seq)
        H.discard(tdir)

    batches = [data.batch(s) for s in range(CHECK_STEPS)]
    del box, step_fn, init_state
    gc.collect()
    ref = R.train(c, seed, batches, "f32", devices=devices)
    checks = compare(c, losses, g_prog, d_prog, ref)
    ok = H.all_ok(checks) and extra["finite_losses"]
    return H.Run(correct=ok, attempted=n_steps, failed=0 if ok else 1,
                 metrics=metrics, memory_peak_bytes=peak, checks=checks,
                 ctx=ctx, extra=extra,
                 outputs={"batches": batches, "reference": ref,
                          "losses": losses, "grad_norms": g_prog,
                          "delta_norms": d_prog})


def gaps(c, losses, g_prog, d_prog, ref) -> Dict[str, Any]:
    """The three compared numbers and what each is made of.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone under Adam; they are left out of the
    change (by that rule on the reference, not by name)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    gr = ref["grad_norms"]
    g_med = float(np.median(list(gr.values())))
    grad = {k: abs(g_prog[k] - gr[k]) / max(gr[k], g_med) for k in gr}
    dr = ref["delta_norms"]
    d_med = float(np.median(list(dr.values())))
    moved = [k for k in dr if gr[k] >= 1e-3 * g_med]
    delta = {k: abs(d_prog[k] - dr[k]) / max(dr[k], d_med) for k in moved}
    return {"loss": loss, "grad": grad, "delta": delta,
            "left_out": sorted(set(dr) - set(moved))}


def compare(c, losses, g_prog, d_prog, ref) -> Dict[str, Dict[str, Any]]:
    g = gaps(c, losses, g_prog, d_prog, ref)
    lim = c["correct"]
    wg = max(g["grad"], key=g["grad"].get)
    wd = max(g["delta"], key=g["delta"].get)
    H.log(f"loss program {losses} reference {ref['losses']}")
    H.log(f"worst grad leaf {wg}: {g['grad'][wg]:.3g}; worst change leaf "
          f"{wd}: {g['delta'][wd]:.3g}; left out {g['left_out']}")
    return {
        "loss_rel_gap": H.check(g["loss"], lim["loss_rel_gap"],
                                f"largest relative gap of the first "
                                f"{len(losses)} steps' losses"),
        "grad_norm_gap": H.check(g["grad"][wg], lim["grad_norm_gap"],
                                 f"worst leaf ({wg}) of |first gradient "
                                 "norm gap| / max(ref leaf, ref median)"),
        "delta_norm_gap": H.check(g["delta"][wd], lim["delta_norm_gap"],
                                  f"worst leaf ({wd}) of |change norm gap "
                                  "after 3 steps| / max(ref leaf, ref "
                                  "median)"),
    }


def _reduce(tdir, c, devices, steps, seconds, gb, seq) -> Dict[str, Any]:
    tr = TR.load(str(tdir))
    devs = [d.id for d in devices]
    lo, hi = tr.window()
    return {"trace": tr, "devices": devs, "config": c,
            "traced_steps": steps, "traced_s": seconds,
            "tokens_per_step": gb * seq, "seq_len": seq,
            "peak": F.peaks(devices[0].device_kind),
            "window_s": (hi - lo) / 1e9,
            "busy_s": TR.busy_seconds(tr, devs),
            "breakdown": TR.breakdown(tr, devs)}

"""Serving path: the continuous engine over a paged KV pool, under an
open-loop arrival schedule.

Set-up (counted in ``setup_s``): weights made on the device from the seed
in one jitted call; the engine; every program and shape the traffic uses
compiled by a few warm-up requests; then the cell's own arrivals, from
``warmup_s`` before the window on, served until the window opens, so the
window starts in steady state.

Window: the benchmark calls ``engine.step()`` until ``seconds`` have
passed, sleeping only while no slot is busy and the next arrival is not
due.  After each step it notes the time of every token that appeared.
Requests are timed from their scheduled arrival, so a stall also delays
the requests behind it.

Metrics: ``ttft_mean_ms`` over requests due in the window (the engine keeps
stepping after the close until each has its first token; untraced runs
print their 90th percentile beside the metrics as ``ttft_p90_ms``),
``itl_p95_ms`` over every gap between consecutive tokens of a request
inside the window, ``serve_tokens_per_s`` = tokens that appeared in the
window / its length.  With ``--trace 1`` the profiler records the last
``TRACE_S`` seconds of the window; the scheduler's queue wait is read
from requests that arrived and were admitted before the profiler started,
so no reading holds the profiler's start or the stall while it writes.

Correctness: once the window has closed and the program's state is freed,
a sample of the requests that arrived inside the window and finished,
drawn from the seed and holding the one with the most tokens, is run
through the plain float32 reference; the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position is held to the configuration's limit.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench.harness import common as H
from bench.harness import flops as F
from bench.harness import model as M
from bench.harness import reference as R
from bench.harness import trace as TR
from bench.harness import traffic as TF
from bench.harness import weights as W

TRACE_S = 6.0            # seconds of the window the profiler records
DRAIN_S = 60.0           # longest wait after the close for a first token
WARM_ID = 1 << 30        # request ids of the warm-up requests


def _engine(cfg, params, c: Dict[str, Any]):
    """The engine as the configuration states it.  Its sampling seed is
    fixed: decoding is greedy, and a seed baked into the sampler would
    give each run's seed a program of its own to compile."""
    from repro.serve.engine import EngineConfig, ServeEngine

    e = c["engine"]
    ecfg = EngineConfig(
        max_slots=e["max_slots"], max_len=e["max_len"],
        prefill_chunk=e["prefill_chunk"], kv_mode="paged",
        block_size=e["block_size"], kv_blocks=e["kv_blocks"],
        temperature=e["temperature"], seed=0, clock="wall")
    return ServeEngine(cfg, params, ecfg)


def _warm_up(engine, reqs: List[TF.Req], c: Dict[str, Any]) -> None:
    """Compile every program and shape the cell's traffic will use: a
    decode step, interior and final prefill chunks, sampling, and the
    host-side slice of each final chunk's last real row."""
    import jax
    import jax.numpy as jnp
    from repro.serve.metrics import ServeMetrics
    from repro.serve.queue import Request

    C = c["engine"]["prefill_chunk"]
    vocab = int(c["vocab_size"])
    warm = [Request(req_id=WARM_ID + i, prompt=[1 + i] * (C + 7),
                    max_new_tokens=3, arrival_s=0.0) for i in range(2)]
    engine.run(warm)
    # a prompt of at most one chunk ends its prefill at row len - 1: the
    # engine slices that row out eagerly, one small program per row index
    rows = sorted({min(len(r.prompt), C) - 1 for r in reqs})
    dummy = jax.device_put(np.zeros((1, C, vocab), np.float32),
                           jax.devices()[0])
    for r in rows:
        jnp.asarray(dummy)[:, r].block_until_ready()
    del dummy
    engine.results.clear()
    engine.metrics = ServeMetrics(max_slots=engine.ecfg.max_slots,
                                  clock="wall")


class Tokens:
    """Times at which each request's tokens appeared (host clock)."""

    def __init__(self):
        self.times: Dict[int, List[float]] = {}

    def observe(self, engine, t: float) -> None:
        def seen(rid, n):
            ts = self.times.setdefault(rid, [])
            while len(ts) < n:          # a preempted request re-serves
                ts.append(t)            # tokens it already sent: not new
        for slot in engine.table.busy():
            if slot.request is not None:
                seen(slot.request.req_id, len(slot.output))
        for rid, out in engine.results.items():
            seen(rid, len(out))


def _percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _wrap(engine, name: str, record=None):
    """Open a host span around an engine phase (while tracing).  With
    ``record``, each call first appends the (live rows, K/V tokens they
    attend over) of the decode it is about to run."""
    fn = getattr(engine, name)

    def wrapped(*a, **k):
        if record is not None:
            record.append(_kv_live(engine))
        with H.span(True, "bench." + name.strip("_")):
            return fn(*a, **k)
    setattr(engine, name, wrapped)


def run(cell, *, devices, seed: int, seconds: float, trace: bool,
        process_start: float, workdir, fault=None) -> H.Run:
    import jax
    from repro.models import transformer as T
    from repro.serve.queue import Request

    c, mix = cell.config, cell.traffic
    cfg = M.arch_config(c)
    compiles = H.Compiles()
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    params = W.make_params(shapes, seed,
                           jax.sharding.SingleDeviceSharding(devices[0]))
    engine = _engine(cfg, params, c)
    del params
    reqs = TF.requests(mix, seconds, seed, int(c["vocab_size"]))
    if fault is not None:
        fault(engine)
    _warm_up(engine, reqs, c)
    W0 = float(mix.get("warmup_s", 0.0))
    engine.submit([Request(req_id=r.req_id, prompt=r.prompt.tolist(),
                           max_new_tokens=r.max_new_tokens,
                           arrival_s=r.arrival_s + W0) for r in reqs])
    tokens = Tokens()
    m = engine.metrics
    m.start()                            # engine time 0 = warm-up start

    def step_until(t_end, spans=False):
        while True:
            t = m.now()
            if t >= t_end:
                return
            if not engine.table.busy():
                nxt = engine.queue.next_arrival()
                if nxt is None or nxt > t:
                    m.wait_until(min(t_end, nxt if nxt is not None
                                     else t_end))
                    continue
            with H.span(spans, "bench.engine_step"):
                engine.step()
            tokens.observe(engine, m.now())

    step_until(W0)                       # steady state before the window
    setup_s = time.monotonic() - process_start
    H.log(f"setup {setup_s:.1f} s; {compiles.count} compiles so far")
    compiles_before = compiles.count

    ticks: List[tuple] = []
    win_lo, win_hi = W0, W0 + seconds
    tdir = None
    if trace:
        t_trace = win_hi - min(seconds, TRACE_S)
        step_until(t_trace)
        _wrap(engine, "_admit_ready")
        _wrap(engine, "_prefill_tick")
        _wrap(engine, "_decode_tick", ticks)
        with H.profiled(True, workdir, f"{cell.name}-{seed}") as tdir:
            step_until(win_hi, spans=True)
            closed = m.now()
    else:
        step_until(win_hi)
        closed = m.now()
    window_compiles = compiles.count - compiles_before
    peak = H.peak_bytes(devices)

    # every request due in the window gets its first token (late, not lost)
    due = [r for r in reqs if 0.0 <= r.arrival_s < seconds]
    drain_end = closed + DRAIN_S
    while m.now() < drain_end and any(
            len(tokens.times.get(r.req_id, [])) == 0 for r in due):
        engine.step()
        tokens.observe(engine, m.now())

    ttft, itl, n_tok = [], [], 0
    failed = 0
    for r in due:
        ts = tokens.times.get(r.req_id, [])
        if not ts:
            failed += 1
            continue
        ttft.append(ts[0] - (r.arrival_s + W0))
    for ts in tokens.times.values():
        n_tok += sum(1 for t in ts if win_lo <= t < closed)
        itl += [b - a for a, b in zip(ts, ts[1:])
                if win_lo <= a and b < closed]
    elapsed = closed - win_lo
    metrics = {
        "setup_s": setup_s,
        "ttft_mean_ms": float(np.mean(ttft)) * 1e3 if ttft else float("nan"),
        "itl_p95_ms": _percentile(itl, 95) * 1e3 if itl else float("nan"),
        "serve_tokens_per_s": n_tok / elapsed,
    }
    due_ids = {r.req_id for r in due}
    finished = {rid: list(out) for rid, out in engine.results.items()
                if rid in due_ids}
    prompts = {r.req_id: r.prompt for r in due}
    extra = {"requests_due": len(due), "tokens_in_window": n_tok,
             "itl_samples": len(itl), "window_compiles": window_compiles,
             "finished": len(finished),
             "preemptions": int(engine.metrics.preemptions),
             "window_s": elapsed}
    if ttft and not trace:       # the traced run's tail holds the stall
        extra["ttft_p90_ms"] = _percentile(ttft, 90) * 1e3
    H.log("ttft ms, sorted: " + " ".join(f"{1e3 * x:.0f}" for x in sorted(ttft)))
    H.log(f"window: {len(due)} requests due, {n_tok} tokens, "
          f"{len(itl)} gaps, {window_compiles} compiles, "
          f"{engine.metrics.preemptions} preemptions")

    ctx: Dict[str, Any] = {}
    if trace:
        queue_wait = [rec.admitted_s - rec.arrival_s for rec in
                      engine.metrics.requests.values()
                      if rec.admitted_s is not None
                      and win_lo <= rec.arrival_s
                      and rec.admitted_s < t_trace]
        ctx = _reduce(tdir, c, devices, ticks, queue_wait)
        H.discard(tdir)

    # the program's state goes before the reference runs
    del engine, m
    gc.collect()
    checks = check_outputs(c, seed, finished, prompts)
    ok = H.all_ok(checks) and failed == 0
    return H.Run(correct=ok, attempted=len(due), failed=failed,
                 metrics=metrics, memory_peak_bytes=peak, checks=checks,
                 ctx=ctx, extra=extra,
                 outputs={"finished": finished, "prompts": prompts})


def _kv_live(engine):
    """(live rows, K/V tokens they attend over) of the next decode step:
    each row attends over its cache and its own new token."""
    rows = engine.table.active()
    return (len(rows), sum(s.length + 1 for s in rows))


def sample(finished: Dict[int, List[int]], prompts, n: int, seed: int
           ) -> List[int]:
    """``n`` finished requests drawn from the seed, the one with the most
    served tokens (then the longest prompt) always among them."""
    ids = sorted(finished)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (len(finished[i]), len(prompts[i]), -i))
    rest = [i for i in ids if i != longest]
    rng = np.random.default_rng([5, seed & 0xFFFFFFFF, seed >> 32])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) \
        if rest and n > 1 else []
    return [longest] + sorted(int(i) for i in pick)


def _seqs(c, seed, finished, prompts):
    ids = sample(finished, prompts, int(c["correct"]["sample_requests"]),
                 seed)
    return ids, [(np.asarray(prompts[i], np.int32),
                  np.asarray(finished[i], np.int32)) for i in ids]


def check_outputs(c, seed, finished, prompts, control: str = ""
                  ) -> Dict[str, Dict[str, Any]]:
    """The served tokens of a sample of finished requests, held to the
    configuration's limit.  With ``control`` (a precision below the
    configuration's), the control in the program's place: at each
    position of the same prompts and served tokens, the token that the
    reference at that precision puts first, judged the same way."""
    lim = c["correct"]
    ids, seqs = _seqs(c, seed, finished, prompts)
    if not ids:
        return {"served_logit_gap": H.check(1, 0, "at least one request "
                                            "finished")}
    tokens = [s for _, s in seqs]
    if control:
        _, tokens = R.logit_gaps(c, seed, seqs, tokens, control)
    gaps, _ = R.logit_gaps(c, seed, seqs, tokens, "f32")
    widest = max(float(g.max()) for g in gaps)
    n = sum(len(s) for _, s in seqs)
    H.log(f"reference: {len(ids)} requests, {n} served tokens, widest "
          f"gap {widest:.4f}" + (f" ({control} control)" if control else ""))
    return {"served_logit_gap": H.check(
        widest, lim["served_logit_gap"],
        f"widest gap, in logits, by which a served token lies below the "
        f"float32 reference's best, over {n} tokens of {len(ids)} "
        "requests")}


def _reduce(tdir, c, devices, ticks, queue_wait) -> Dict[str, Any]:
    tr = TR.load(str(tdir))
    devs = [d.id for d in devices]
    lo, hi = tr.window()
    busy_s = TR.busy_seconds(tr, devs)
    return {"trace": tr, "devices": devs, "config": c,
            "decode_ticks": ticks, "queue_wait_s": queue_wait,
            "peak": F.peaks(devices[0].device_kind),
            "window_s": (hi - lo) / 1e9, "busy_s": busy_s,
            "breakdown": TR.breakdown(tr, devs)}

"""Serving path of a DeepSeek-V3-style model (MLA attention, a group-limited
router over all experts, the held experts' share computed dropless) at
one chip's expert-parallel share: the continuous engine over a paged
latent pool, under an open-loop arrival schedule.

Everything but the model is ``serve.py``'s, by import: the engine as the
configuration states it, the warm-up, the token timing and the sample of
finished requests.  The window loop is ``serve.py``'s, timed the same
way; the only additions are the configuration's mapping onto the
program's ``ArchConfig`` (``arch_config``), the plain reference
(``bench/harness/reference_mla_moe.py``), and, while tracing, the
engine's routing counter read after each decode tick beside the work
recorded before it (``decode_routed`` beside ``decode_ticks``), for the
readers in ``bench/harness/flops_mla_moe.py``.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench.drivers import serve as S
from bench.harness import common as H
from bench.harness import reference_mla_moe as R
from bench.harness import traffic as TF
from bench.harness import weights as W


def arch_config(c: Dict[str, Any]):
    """The program's ``ArchConfig`` for a DeepSeek-V3 configuration file:
    the router keeps ``router_experts`` outputs and the layer holds
    ``n_routed_experts`` of them from ``experts_held_from`` on."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig

    for key, want in (("hidden_act", "silu"), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1)):
        if c[key] != want:
            raise ValueError(f"{c['name']}: {key} {c[key]!r} is not mapped "
                             f"(only {want!r})")
    ys = c["rope_scaling"]
    if ys is not None and ys.get("type") != "yarn":
        raise ValueError(f"{c['name']}: rope_scaling {ys.get('type')!r} is "
                         "not mapped (only yarn)")
    if c["num_nextn_predict_layers"]:
        raise ValueError(f"{c['name']}: MTP layers are not served")
    dense = int(c["first_k_dense_replace"])
    L = int(c["num_hidden_layers"])
    moe = MoEConfig(
        num_experts=int(c["router_experts"]),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c["moe_intermediate_size"]),
        num_shared=int(c["n_shared_experts"]), router="noaux_tc",
        norm_topk=bool(c["norm_topk_prob"]), n_group=int(c["n_group"]),
        topk_group=int(c["topk_group"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        held_from=int(c["experts_held_from"]),
        num_held=int(c["n_routed_experts"]))
    mla = MLAConfig(q_lora_rank=int(c["q_lora_rank"]),
                    kv_lora_rank=int(c["kv_lora_rank"]),
                    qk_nope_head_dim=int(c["qk_nope_head_dim"]),
                    qk_rope_head_dim=int(c["qk_rope_head_dim"]),
                    v_head_dim=int(c["v_head_dim"]),
                    yarn=None if ys is None else YaRNConfig(
                        factor=float(ys["factor"]),
                        original_max_position=int(
                            ys["original_max_position_embeddings"]),
                        beta_fast=float(ys["beta_fast"]),
                        beta_slow=float(ys["beta_slow"]),
                        mscale=float(ys["mscale"]),
                        mscale_all_dim=float(ys["mscale_all_dim"])))
    return ArchConfig(
        name=c["name"], family="moe", num_layers=L,
        d_model=int(c["hidden_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        head_dim=int(c["v_head_dim"]), mlp="swiglu",
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        layer_pattern=("mla",) * dense + ("mla_moe",) * (L - dense),
        moe=moe, mla=mla, max_seq=int(c["max_position_embeddings"]),
        param_dtype=c["precision"]["params"], source=c["source"])


def _wrap_decode(engine, ticks: List[tuple], routed: List[Any]) -> None:
    """``serve._wrap`` of the decode tick, and after each call the step's
    routing counter (None when the tick ran no decode program)."""
    S._wrap(engine, "_decode_tick", ticks)
    fn = engine._decode_tick

    def wrapped(*a, **k):
        steps = engine.metrics.decode_steps
        out = fn(*a, **k)
        ran = engine.metrics.decode_steps > steps
        routed.append(np.array(engine.metrics.routed_last) if ran else None)
        return out
    engine._decode_tick = wrapped


def run(cell, *, devices, seed: int, seconds: float, trace: bool,
        process_start: float, workdir, fault=None) -> H.Run:
    import jax
    from repro.models import transformer as T
    from repro.serve.queue import Request

    c, mix = cell.config, cell.traffic
    cfg = arch_config(c)
    compiles = H.Compiles()
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    params = W.make_params(shapes, seed,
                           jax.sharding.SingleDeviceSharding(devices[0]))
    engine = S._engine(cfg, params, c)
    del params
    reqs = TF.requests(mix, seconds, seed, int(c["vocab_size"]))
    if fault is not None:
        fault(engine)
    S._warm_up(engine, reqs, c)
    W0 = float(mix.get("warmup_s", 0.0))
    engine.submit([Request(req_id=r.req_id, prompt=r.prompt.tolist(),
                           max_new_tokens=r.max_new_tokens,
                           arrival_s=r.arrival_s + W0) for r in reqs])
    tokens = S.Tokens()
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
    routed: List[Any] = []
    win_lo, win_hi = W0, W0 + seconds
    tdir = None
    if trace:
        t_trace = win_hi - min(seconds, S.TRACE_S)
        step_until(t_trace)
        S._wrap(engine, "_admit_ready")
        S._wrap(engine, "_prefill_tick")
        _wrap_decode(engine, ticks, routed)
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
    drain_end = closed + S.DRAIN_S
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
        "itl_p95_ms": S._percentile(itl, 95) * 1e3 if itl else float("nan"),
        "serve_tokens_per_s": n_tok / elapsed,
    }
    due_ids = {r.req_id for r in due}
    finished = {rid: list(out) for rid, out in engine.results.items()
                if rid in due_ids}
    # a request still decoding when the window closed is checked on the
    # tokens it has served so far
    for slot in engine.table.busy():
        rid = slot.request.req_id if slot.request is not None else None
        if rid in due_ids and rid not in finished and len(slot.output) > 1:
            finished[rid] = list(slot.output)
    prompts = {r.req_id: r.prompt for r in due}
    routed_total = engine.metrics.routed_total
    extra = {"requests_due": len(due), "tokens_in_window": n_tok,
             "itl_samples": len(itl), "window_compiles": window_compiles,
             "finished": len(engine.results.keys() & due_ids),
             "checked": len(finished),
             "preemptions": int(engine.metrics.preemptions),
             "window_s": elapsed,
             "routed_per_expert": (None if routed_total is None
                                   else routed_total.tolist())}
    if ttft and not trace:       # the traced run's tail holds the stall
        extra["ttft_p90_ms"] = S._percentile(ttft, 90) * 1e3
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
        ctx = S._reduce(tdir, c, devices, ticks, queue_wait)
        ctx["decode_routed"] = routed
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


def check_outputs(c, seed, finished, prompts, control: str = ""
                  ) -> Dict[str, Dict[str, Any]]:
    """The served tokens of a sample of requests (the one with the most
    tokens always among them), each held to the float32 reference's best
    logit at its position, by two numbers: the widest gap, and the mean
    gap over every served token.  The group-limited router is
    discontinuous: where two groups' or experts' scores nearly tie,
    rounding alone can move a token to other experts and serve another
    token, so the widest tokens are judged also across their narrowest
    router tie (``reference_mla_moe.tie_judged_gaps``); the mean reads
    how far the whole served stream lies from the reference.  With
    ``control``, the reference at that lower precision in the program's
    place."""
    lim = c["correct"]
    ids, seqs = S._seqs(c, seed, finished, prompts)
    if not ids:
        return {"served_logit_gap": H.check(1, 0, "at least one request "
                                            "served tokens")}
    tokens = [s for _, s in seqs]
    if control:
        _, tokens = R.logit_gaps(c, seed, seqs, tokens, control)
    gaps, judged, picks = R.tie_judged_gaps(c, seed, seqs, tokens)
    allg = np.concatenate(gaps)
    widest, mean = float(np.concatenate(judged).max()), float(allg.mean())
    n = len(allg)
    for pk in picks:
        H.log("tie-judged: " + " ".join(f"{k} {v:.4g}" if isinstance(
            v, float) else f"{k} {v}" for k, v in pk.items()))
    H.log(f"reference: {len(ids)} requests, {n} served tokens, widest "
          f"gap {widest:.4f} ({float(allg.max()):.4f} before the tie "
          f"judgement), mean {mean:.5f}"
          + (f" ({control} control)" if control else ""))
    return {
        "served_logit_gap": H.check(
            widest, lim["served_logit_gap"],
            f"widest gap, in logits, by which a served token lies below "
            f"the float32 reference's best, over {n} tokens of {len(ids)} "
            f"requests; the {len(picks)} widest judged also across their "
            "narrowest router tie"),
        "served_logit_gap_mean": H.check(
            mean, lim["served_logit_gap_mean"],
            f"mean of that gap (before the tie judgement) over the same "
            f"{n} tokens")}

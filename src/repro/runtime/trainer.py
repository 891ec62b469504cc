"""BSP training step builders.

Two tiers (DESIGN.md §4):

  * ``make_gspmd_train_step`` — jit + GSPMD: parameters FSDP×TP sharded
    (ZeRO-3 style), gradient reduction scheduled by XLA.  This is the
    baseline every (arch × shape) dry-run cell uses.

  * ``make_bsp_train_step`` — the paper's technique as a first-class feature:
    the whole step runs inside ``shard_map`` with the DP axes *manual* and the
    model axis auto (TP stays GSPMD).  Parameters are DP-replicated; gradients
    are partitioned by the SuperstepEngine into reverse-layer buckets and
    pipelined through explicit FractalSync-family schedules — one collective
    per bucket, autotuned per bucket under ``schedule="auto"``, ± payload
    compression; optimizer moments are ZeRO-1 sharded per bucket — each BSP
    rank updates 1/world of every bucket between its reduce-scatter and
    all-gather (the bandwidth-optimal H-tree form), then a single fsync
    barrier closes the superstep.  ``grad_accum`` splits the rank batch into
    micro-batches (the knob elastic re-meshing scales to preserve the global
    batch).
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import collectives as C
from repro.core import superstep
from repro.core.barrier import barrier_tie
from repro.core.bsp import BSPConfig, bsp_shard_map
from repro.models import act_sharding as ACT
from repro.models import sharding as SH
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim.compression import error_feedback_step


# ---------------------------------------------------------------------------
# Tier A: GSPMD (baseline for all dry-run cells)
# ---------------------------------------------------------------------------


def make_gspmd_train_step(cfg: ArchConfig, mesh: Mesh,
                          acfg: adamw.AdamWConfig):
    """jit'd (params, opt_state, batch) → (params, opt_state, metrics)."""
    ACT.set_policy(mesh, SH.fsdp_axes(mesh))
    ACT.SERVE_EP = False

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            T.loss_fn, has_aux=True)(params, cfg, batch)
        params, opt_state, om = adamw.apply_updates(params, grads, opt_state,
                                                    acfg)
        metrics = dict(metrics, loss=loss, **om)
        return params, opt_state, metrics

    pshape = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))
    pspec = SH.param_specs(cfg, pshape, mesh)
    oshape = jax.eval_shape(lambda: adamw.init(pshape, acfg))
    ospec = adamw.AdamWState(step=P(), mu=pspec, nu=pspec)
    bspec_all = SH.batch_spec(mesh)
    bspec = {"tokens": bspec_all["tokens"], "labels": bspec_all["labels"]}
    if cfg.frontend:
        bspec["frontend"] = bspec_all["frontend"]

    n = lambda s: SH.named(mesh, s)
    step = jax.jit(
        train_step,
        in_shardings=(n(pspec), n(ospec), n(bspec)),
        out_shardings=(n(pspec), n(ospec), None),
        donate_argnums=(0, 1),
    )
    return step, (pspec, ospec, bspec)


# ---------------------------------------------------------------------------
# Tier A: serving steps (prefill / decode)
# ---------------------------------------------------------------------------


def _serve_mode(cfg: ArchConfig) -> str:
    """MoE archs serve with pinned weights (TP+EP: tokens move, weights
    stay) — 35-41× on the big-MoE cells; small dense archs keep the FSDP
    layout whose per-layer weight gather is cheaper than 16× the HBM reads
    (measured: musicgen/granite serve_layout variants, EXPERIMENTS §Perf)."""
    return "serve" if cfg.moe else "train"


def make_prefill_step(cfg: ArchConfig, mesh: Mesh, batch: int, max_len: int):
    ACT.set_policy(mesh, SH.fsdp_axes(mesh))
    ACT.SERVE_EP = cfg.moe is not None

    def prefill_step(params, tokens, cache, frontend=None):
        return T.prefill(params, cfg, tokens, cache, frontend)

    pshape = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))
    pspec = SH.param_specs(cfg, pshape, mesh, mode=_serve_mode(cfg))
    cshape = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))
    cspec = SH.cache_specs(cfg, cshape, mesh)
    dp = SH.fsdp_axes(mesh)
    if batch % SH.axis_size(mesh, dp):
        dp = ()
    n = lambda s: SH.named(mesh, s)
    in_sh = [n(pspec), NamedSharding(mesh, P(dp or None, None)), n(cspec)]
    if cfg.frontend:
        in_sh.append(NamedSharding(mesh, P(dp, None, None)))
    step = jax.jit(prefill_step, in_shardings=tuple(in_sh),
                   out_shardings=(None, n(cspec), None),
                   donate_argnums=(2,))
    return step, (pspec, cspec)


def make_decode_step(cfg: ArchConfig, mesh: Mesh, batch: int, max_len: int):
    ACT.set_policy(mesh, SH.fsdp_axes(mesh))
    ACT.SERVE_EP = cfg.moe is not None

    def serve_step(params, token, cache, offset):
        logits, cache = T.decode_step(params, cfg, token, cache, offset)
        return logits, cache

    pshape = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))
    pspec = SH.param_specs(cfg, pshape, mesh, mode=_serve_mode(cfg))
    cshape = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))
    cspec = SH.cache_specs(cfg, cshape, mesh)
    dp = SH.fsdp_axes(mesh)
    if batch % SH.axis_size(mesh, dp):
        dp = ()                      # long_500k: global batch 1
    n = lambda s: SH.named(mesh, s)
    step = jax.jit(
        serve_step,
        in_shardings=(n(pspec), NamedSharding(mesh, P(dp or None, None)),
                      n(cspec), NamedSharding(mesh, P())),
        out_shardings=(None, n(cspec)),
        donate_argnums=(2,),
    )
    return step, (pspec, cspec)


# ---------------------------------------------------------------------------
# Tier B: explicit BSP superstep (the paper's technique, first-class)
# ---------------------------------------------------------------------------


@dataclass
class BSPTrainState:
    params: Any                # DP-replicated pytree (TP-sharded on "model")
    flat_mu: jax.Array         # ZeRO-1: this rank's shard of flat moments
    flat_nu: jax.Array
    ef_residual: Optional[jax.Array]   # error-feedback state (compression)
    step: jax.Array


def make_bsp_train_step(cfg: ArchConfig, mesh: Mesh, acfg: adamw.AdamWConfig,
                        bsp: BSPConfig, grad_accum: int = 1,
                        shares: Optional[Sequence[int]] = None):
    """Explicit-schedule BSP superstep, pipelined over gradient buckets:

      compute:     local fwd/bwd on this rank's micro-batch(es) —
                   ``grad_accum`` > 1 splits the rank batch and accumulates
                   (the knob ElasticPlan.grad_accum_scale raises to keep the
                   global batch after re-meshing)
      communicate: per SuperstepEngine bucket (reverse-layer order, schedule
                   autotuned per bucket under ``schedule="auto"``):
                   flat bucket grads → [EF] → reduce-scatter
      update:      AdamW on this rank's 1/world shard of each bucket (ZeRO-1)
      publish:     all-gather of the updated shards, bucket by bucket
      barrier:     one fsync(level) token closes the whole superstep

    The per-bucket collectives are data-independent, so XLA may overlap
    bucket i's communication with the compute that feeds bucket j>i — the
    structural overlap the monolithic path (one bucket) cannot express.

    ``shares`` (length-world, each ≥ 1) actuates a straggler rebalance:
    rank r runs ``shares[r]`` micro-batches instead of an even split —
    slow ranks genuinely do less work, flattening barrier arrival.  The
    batch must arrive in the padded per-rank layout of
    ``data.pipeline.reshard_for_shares`` (``max(shares)`` micro-batch
    rows per rank; only the first ``shares[r]`` are real).  The global
    gradient is the mean over ``sum(shares)`` micro-batches, weighted
    correctly by construction — AND bit-identical in f32 across every
    share partition of the same micro-batch set: each rank accumulates
    its micro-gradients as a Neumaier compensated pair (value + running
    error), both halves are all-gathered, and every rank sums all
    ``2·world`` components in one fixed canonical order.  The result is
    partition-independent to O(eps²), so uneven and even splits of
    identical data produce byte-identical parameter updates (asserted in
    tests/train_soak_checks.py).  The downstream reduce-scatter then sums
    ``world`` identical copies — exactly ``world × shard`` in floats
    (power-of-two doubling) — and the ``/world`` recovers the combined
    gradient unchanged, so the whole superstep pipeline needs no other
    modification.
    """
    ACT.clear_policy()   # manual-DP body: no data-axis GSPMD constraints
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    sizes = tuple(mesh.shape[a] for a in bsp.sync_axes)
    world = math.prod(sizes)
    if shares is not None:
        if grad_accum != 1:
            raise ValueError(
                "shares= and grad_accum>1 are mutually exclusive: shares IS "
                "the per-rank micro-batch count")
        shares = tuple(int(s) for s in shares)
        if len(shares) != world:
            raise ValueError(
                f"shares has {len(shares)} entries for world size {world}")
        if any(s < 1 for s in shares):
            raise ValueError(f"every rank needs >= 1 micro-batch: {shares}")

    pshape = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))
    # the engine's flat layout is f32 (grads/moments are f32 regardless of
    # param dtype); plan once at build time and log the bucket decisions
    engine = superstep.engine_for(pshape, bsp, sizes,
                                  force_dtype=jnp.float32, zero1=True)
    flat_total = engine.total_padded
    # Per-bucket codec plan: uniform `compression` under bucket_codec=None
    # (the historical EF-then-f32-wire path, bit-for-bit); an explicit
    # bucket_codec additionally wire-compresses the fractal reduce-scatter
    # exchanges of codec'd buckets (per-hop quantization, EF-corrected).
    bucket_codecs = engine.bucket_codecs
    has_codec = any(c is not None for c in bucket_codecs)
    wire_codecs = bucket_codecs if bsp.bucket_codec is not None \
        else (None,) * engine.n_buckets
    print(f"superstep: {engine.describe()} (link={engine.link.name})")
    # fingerprint of the flat moment layout (bucket boundaries × world):
    # checkpoints carry it so a resume under a different --bucket-mb (or a
    # pre-engine moment ordering) fails loudly instead of silently binding
    # moments to the wrong parameter slices (same shape, different layout)
    layout = ",".join(f"{b.offset}+{b.length}" for b in engine.buckets)
    layout_tag = "zero1:" + hashlib.sha1(
        f"w{world}:{layout}".encode()).hexdigest()[:12]
    shard_lens = [engine.shard_len(b) for b in engine.buckets]
    shard_offs = engine.shard_offsets()

    def local_grads(params, batch):
        """loss/metrics/grads for this rank, with optional accumulation.

        Accumulation runs as a ``lax.scan`` over micro-batches so the
        compiled program holds ONE forward/backward regardless of
        ``grad_accum`` — an elastic re-mesh that raises the factor must
        not also inflate recompile time linearly.
        """
        vag = jax.value_and_grad(T.loss_fn, has_aux=True)
        if grad_accum == 1:
            (loss, metrics), grads = vag(params, cfg, batch)
            return loss, metrics, grads
        b_local = jax.tree.leaves(batch)[0].shape[0]
        if b_local % grad_accum:
            raise ValueError(f"per-rank batch {b_local} not divisible by "
                             f"grad_accum {grad_accum}")
        micro = jax.tree.map(
            lambda v: v.reshape((grad_accum, v.shape[0] // grad_accum)
                                + v.shape[1:]), batch)
        first = jax.tree.map(lambda v: v[0], micro)
        rest = jax.tree.map(lambda v: v[1:], micro)
        (loss, metrics), grads = vag(params, cfg, first)

        def body(carry, mb):
            l_a, m_a, g_a = carry
            (l, m), g = vag(params, cfg, mb)
            return (l_a + l, jax.tree.map(jnp.add, m_a, m),
                    jax.tree.map(jnp.add, g_a, g)), None

        (loss, metrics, grads), _ = jax.lax.scan(
            body, (loss, metrics, grads), rest)
        inv = 1.0 / grad_accum
        return (loss * inv, jax.tree.map(lambda v: v * inv, metrics),
                jax.tree.map(lambda v: v * inv, grads))

    def _pair_add(s, e, t):
        """One Neumaier step on the compensated pair (s, e): s' = fl(s+t)
        with the rounding error folded into e — (s'+e') carries the exact
        sum to O(eps²)."""
        x = s + t
        e = e + jnp.where(jnp.abs(s) >= jnp.abs(t),
                          (s - x) + t, (t - x) + s)
        return x, e

    def _tree_pair_add(s_tree, e_tree, t_tree):
        x_tree = jax.tree.map(jnp.add, s_tree, t_tree)
        e_tree = jax.tree.map(
            lambda s, t, x, e: e + jnp.where(jnp.abs(s) >= jnp.abs(t),
                                             (s - x) + t, (t - x) + s),
            s_tree, t_tree, x_tree, e_tree)
        return x_tree, e_tree

    def local_grads_shares(params, batch):
        """Uneven micro-batch accumulation, partition-independent in f32.

        This rank's batch slice is ``max(shares)`` micro-batch rows; a
        ``fori_loop`` with DYNAMIC trip count ``shares[rank]`` runs only
        the real ones (padding rows are never computed), pair-accumulating
        (loss, metrics, grads) in f32.  Both pair halves are all-gathered
        over the sync axes and every rank reduces all ``2·world``
        components in the same canonical order, so the returned global
        means are replicated AND independent of how the micro-batches
        were partitioned.  The cross-rank combine is unrolled over world
        (fine at fsync-domain scale; a fixed-order segmented tree would
        serve thousands of ranks).
        """
        vag = jax.value_and_grad(T.loss_fn, has_aux=True)
        rows = jax.tree.leaves(batch)[0].shape[0]
        n_max, m_total = max(shares), sum(shares)
        if rows % n_max:
            raise ValueError(f"per-rank batch {rows} rows not divisible by "
                             f"max(shares) = {n_max} — re-shard the batch "
                             "with data.pipeline.reshard_for_shares")
        mb = rows // n_max
        micro = jax.tree.map(
            lambda v: v.reshape((n_max, mb) + v.shape[1:]), batch)
        idx = 0                       # linear BSP rank, row-major sync axes
        for ax, sz in zip(bsp.sync_axes, sizes):
            idx = idx * sz + jax.lax.axis_index(ax)
        n_r = jnp.asarray(shares, jnp.int32)[idx]

        out_sd = jax.eval_shape(lambda p, b: vag(p, cfg, b), params,
                                jax.tree.map(lambda v: v[0], micro))
        zeros = jax.tree.map(lambda sd: jnp.zeros(sd.shape, jnp.float32),
                             out_sd)

        def body(i, carry):
            mb_i = jax.tree.map(
                lambda v: jax.lax.dynamic_index_in_dim(v, i, keepdims=False),
                micro)
            t = jax.tree.map(lambda v: v.astype(jnp.float32),
                             vag(params, cfg, mb_i))
            return _tree_pair_add(carry[0], carry[1], t)

        s_tree, e_tree = jax.lax.fori_loop(0, n_r, body, (zeros, zeros))

        def combine(s, e):
            ag_s = jax.lax.all_gather(s, bsp.sync_axes, tiled=False)
            ag_s = ag_s.reshape((world,) + s.shape)
            ag_e = jax.lax.all_gather(e, bsp.sync_axes, tiled=False)
            ag_e = ag_e.reshape((world,) + e.shape)
            ts, te = jnp.zeros_like(s), jnp.zeros_like(s)
            for rr in range(world):
                ts, te = _pair_add(ts, te, ag_s[rr])
            for rr in range(world):
                ts, te = _pair_add(ts, te, ag_e[rr])
            return (ts + te) / m_total

        (loss, metrics), grads = jax.tree.map(combine, s_tree, e_tree)
        return loss, metrics, grads

    def local_step(params, flat_mu, flat_nu, ef, step, batch):
        if shares is not None:
            # shares path: loss/metrics/grads come back as GLOBAL means,
            # replicated on every rank (fixed-order compensated combine) —
            # the reduce-scatter below sums world identical copies, which
            # its /world recovers exactly (power-of-two doubling)
            loss, metrics, grads = local_grads_shares(params, batch)
        else:
            loss, metrics, grads = local_grads(params, batch)
            # report the GLOBAL mean loss (each rank saw its own micro-batch)
            loss = jax.lax.psum(loss, bsp.sync_axes) / world
            metrics = jax.tree.map(
                lambda v: jax.lax.psum(v, bsp.sync_axes) / world, metrics)

        g_parts = engine.pack(jax.tree.leaves(grads), dtype=jnp.float32)
        if has_codec and ef is not None:
            # per-rank EF residual, bucket-ordered like the flat layout.
            # The wire payload is the QUANTIZED corrected gradient —
            # corrected − residual ≡ dequant(quant(corrected)) — so the
            # residual compensates a quantization that actually reached the
            # reduction (classic EF-SGD), not a hypothetical one.  Buckets
            # whose policy skips compression pass through untouched (their
            # residual slice stays zero).
            new_ef = []
            for bkt, part, c in zip(engine.buckets, g_parts, bucket_codecs):
                res = jax.lax.dynamic_slice_in_dim(
                    ef, bkt.offset, bkt.length)
                if c is not None:
                    corrected, res = error_feedback_step(part, res, c)
                    g_parts[bkt.index] = corrected - res
                new_ef.append(res)
            ef = jnp.concatenate(new_ef)

        rev = C.bit_reversed_index(bsp.sync_axes, sizes)
        p_parts = engine.pack(jax.tree.leaves(params), dtype=jnp.float32)

        # --- pipelined communicate/update/publish, one bucket at a time ----
        new_p_parts, new_mu_parts, new_nu_parts, om = [], [], [], {}
        for bkt, schedule, wc, g_part, p_part, s_len, s_off in zip(
                engine.buckets, engine.schedules, wire_codecs, g_parts,
                p_parts, shard_lens, shard_offs):
            g_shard = engine.reduce_scatter_bucket(
                g_part, schedule, codec=wc) / world
            p_shard = jax.lax.dynamic_slice_in_dim(
                p_part, rev * s_len, s_len)
            mu_b = jax.lax.dynamic_slice_in_dim(flat_mu, s_off, s_len)
            nu_b = jax.lax.dynamic_slice_in_dim(flat_nu, s_off, s_len)
            new_p, new_mu, new_nu, om = _adamw_flat(
                p_shard, g_shard, mu_b, nu_b, step, acfg)
            # publish: the all-gather inverts the bit-reversed scatter, so
            # the bucket's flat layout comes back in original order
            new_p_parts.append(engine.all_gather_bucket(new_p))
            new_mu_parts.append(new_mu)
            new_nu_parts.append(new_nu)

        leaves = engine.unpack(new_p_parts, jax.tree.leaves(params))
        params = jax.tree.unflatten(jax.tree.structure(params), leaves)
        flat_mu = jnp.concatenate(new_mu_parts)
        flat_nu = jnp.concatenate(new_nu_parts)

        # --- fsync barrier closes the superstep ONCE ------------------------
        token = C.fractal_barrier(bsp.sync_axes, sizes, level=bsp.fsync_level)
        params = jax.tree.map(lambda x: barrier_tie(x, token), params)
        metrics = dict(metrics, loss=loss, **om)
        return params, flat_mu, flat_nu, ef, step + 1, metrics

    # --- shard_map plumbing: DP manual, model auto ---------------------------
    rep = jax.tree.map(lambda _: P(), pshape)       # DP-replicated params
    shard_spec = P(bsp.sync_axes)
    bspec = {"tokens": P(bsp.sync_axes, None),
             "labels": P(bsp.sync_axes, None)}
    if cfg.frontend:
        bspec["frontend"] = P(bsp.sync_axes, None, None)

    in_specs = (rep, shard_spec, shard_spec,
                shard_spec if has_codec else P(),
                P(), bspec)
    out_specs = (rep, shard_spec, shard_spec,
                 shard_spec if has_codec else P(),
                 P(), P())

    def wrapped(params, flat_mu, flat_nu, ef, step, batch):
        return local_step(params, flat_mu, flat_nu, ef, step, batch)

    fn = bsp_shard_map(wrapped, mesh, in_specs=in_specs, out_specs=out_specs,
                       sync_axes=bsp.sync_axes)
    # donating the pass-through ef placeholder trips XLA aliasing when the
    # codec is off (output aliases a deleted input on the next call) — donate
    # only the genuinely-consumed moment shards
    step_fn = jax.jit(fn, donate_argnums=(1, 2))

    # ZeRO-1 moments are made in place, each rank's shard on its own device
    # (never the full length on one device)
    shard = NamedSharding(mesh, shard_spec)
    replicated = NamedSharding(mesh, P())

    @partial(jax.jit, out_shardings=(
        shard, shard, shard if has_codec else replicated, replicated))
    def _zero_state():
        mu = jnp.zeros((flat_total,), jnp.float32)
        nu = jnp.zeros((flat_total,), jnp.float32)
        # EF residual is PER-RANK state of full bucket-ordered length:
        # global (world × flat_total) sharded over the sync axes
        ef = jnp.zeros((world * flat_total,), jnp.float32) \
            if has_codec \
            else jnp.zeros((world,), jnp.float32)   # placeholder
        return mu, nu, ef, jnp.zeros((), jnp.int32)

    def init_state(params) -> Tuple:
        return (params,) + _zero_state()

    init_state.superstep_layout = layout_tag
    return step_fn, init_state


def _adamw_flat(p, g, mu, nu, step, acfg: adamw.AdamWConfig):
    """AdamW on a flat f32 shard (global-norm clip is per-shard-approx here;
    exact global clipping would add one scalar psum — left to the schedule)."""
    b1, b2 = acfg.beta1, acfg.beta2
    stepf = (step + 1).astype(jnp.float32)
    lr = adamw.schedule(step, acfg)
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    mhat = mu / (1 - b1 ** stepf)
    nhat = nu / (1 - b2 ** stepf)
    upd = mhat / (jnp.sqrt(nhat) + acfg.eps) + acfg.weight_decay * p
    return p - lr * upd, mu, nu, {"lr": lr}

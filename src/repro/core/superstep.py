"""SuperstepEngine: bucketed, overlap-aware BSP gradient synchronization.

The paper makes the BSP barrier nearly free, which moves the superstep
bottleneck to the communication phase itself.  The monolithic path
(flatten → one all-reduce → unflatten) serializes compute and
communication: no gradient byte moves until the *whole* backward pass has
finished.  This module makes the Schedule IR a **runtime** concept:

  1. the gradient pytree is partitioned into size-bounded **buckets** in
     reverse-layer order (leaf order reversed), so bucket 0 — the LAST
     layers — is complete while backward is still chewing on the first
     layers;
  2. each bucket is compiled to its own Schedule-IR ``Program`` (tagged
     with ``BucketMeta`` so all IR consumers agree on bucket identity),
     with the autotuner picking a schedule *per bucket* — small late
     buckets lean butterfly (latency-bound), large early buckets lean ring
     (bandwidth-bound);
  3. the runtime lowering issues one collective per bucket inside the same
     jitted superstep.  The collectives are data-independent, so XLA's
     latency-hiding scheduler may overlap bucket i's communication with
     whatever compute still feeds bucket j>i — the structural opportunity
     the monolithic path denies it;
  4. ``cost_model.overlap_step_cost`` and ``simulator.pipelined_on_noc``
     price/replay the bucket pipeline on a *shared* fabric timeline, so
     predicted step time reflects compute/comm overlap instead of a sum
     (``benchmarks/overlap.py`` sweeps this against the monolithic
     baseline).

Numerics: bucketing permutes and re-groups the flat vector but reduces
every element through the same schedule arithmetic, so the bucketed sync
is equivalent to the monolithic path within f32 tolerance (bit-identical
for codec-free schedules; asserted in ``tests/superstep_checks.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives as C
from . import schedule_ir
from .bsp import BSPConfig, make_codec
from .cost_model import (LinkParams, OverlapTimeline, TPU_V5E_ICI,
                         overlap_step_cost)


@dataclass(frozen=True)
class LeafSpec:
    """Host-static shape/dtype of one gradient (or parameter) leaf."""

    shape: Tuple[int, ...]
    dtype: str

    @property
    def size(self) -> int:
        return int(math.prod(self.shape))


@dataclass(frozen=True)
class Bucket:
    """One size-bounded slice of the bucket-ordered flat payload.

    ``leaf_ids`` index the *original* pytree leaf list; buckets concatenate
    leaves in reverse-layer order, so bucket 0 holds the tail of the model.
    ``offset``/``length`` locate the bucket's padded segment in the
    bucket-ordered flat vector (elements, not bytes).
    """

    index: int
    leaf_ids: Tuple[int, ...]
    raw: int                      # unpadded element count
    offset: int                   # start in the bucket-ordered flat vector
    length: int                   # padded element count (divides by world)

    def meta(self, n_buckets: int,
             codec: Optional[str] = None) -> schedule_ir.BucketMeta:
        return schedule_ir.BucketMeta(index=self.index, n_buckets=n_buckets,
                                      offset_elems=self.offset,
                                      length_elems=self.length,
                                      codec=codec)


def partition_buckets(leaf_sizes: Sequence[int], order: Sequence[int],
                      bucket_elems: Optional[int], pad_unit: int
                      ) -> Tuple[Bucket, ...]:
    """Greedy size-bounded partition of leaves (in ``order``) into buckets.

    A bucket closes once it holds ≥ ``bucket_elems`` raw elements (None →
    one bucket holds everything).  A single leaf larger than the bound gets
    its own bucket — the bound is a target, not a hard cap.  Every bucket
    is padded up to a multiple of ``pad_unit`` (world × pad_align, so the
    halving steps and per-rank shards stay lane-aligned).
    """
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_elems = 0
    for i in order:
        if cur and bucket_elems is not None and \
                cur_elems + leaf_sizes[i] > bucket_elems:
            groups.append(cur)
            cur, cur_elems = [], 0
        cur.append(i)
        cur_elems += leaf_sizes[i]
    if cur:
        groups.append(cur)
    buckets: List[Bucket] = []
    offset = 0
    for bi, ids in enumerate(groups):
        raw = sum(leaf_sizes[i] for i in ids)
        length = ((raw + pad_unit - 1) // pad_unit) * pad_unit
        buckets.append(Bucket(index=bi, leaf_ids=tuple(ids), raw=raw,
                              offset=offset, length=length))
        offset += length
    return tuple(buckets)


# ---------------------------------------------------------------------------
# DP bucket-boundary search (BSPConfig(bucket_mb="auto"))
# ---------------------------------------------------------------------------
#
# A fixed ``bucket_mb`` is one point on a curve: small buckets start
# communication early but pay per-collective latency and padding; big
# buckets amortize both but idle the fabric while backward still computes.
# The overlapped finish time of a partition follows the shared-fabric
# recurrence
#
#     finish_k = max(finish_{k-1}, ready_k) + cost(bytes_k)
#
# which is monotone in finish_{k-1} — so the minimal finish over all
# boundary placements decomposes over prefixes and an O(n²) dynamic program
# over leaf prefix sums finds the EXACT optimum (the property test
# cross-checks it against brute-force boundary enumeration).  The greedy
# packer supplies the initial upper bound (branch pruning) and remains the
# fallback if float noise ever puts the DP above it.


@dataclass(frozen=True)
class PartitionPlan:
    """A searched bucket partition plus the objective it was chosen by."""

    buckets: Tuple[Bucket, ...]
    objective_s: float            # overlapped finish time under cost_fn
    source: str                   # "dp" | "greedy:<mb>MB"
    backward_s: float             # the backward duration the search assumed


GREEDY_FALLBACK_MBS = (4.0, 16.0, 64.0, 256.0)


def partition_objective(buckets: Sequence[Bucket],
                        cost_of_bytes: Callable[[float], float],
                        itemsize: int, backward_s: float) -> float:
    """Overlapped finish time of a partition on the shared-fabric timeline:
    bucket k enters the fabric at max(fabric-free, ready_k) — the same
    recurrence ``cost_model.overlap_step_cost`` prices, with per-bucket
    costs delegated to ``cost_of_bytes(padded bytes)``."""
    total_raw = max(1, sum(b.raw for b in buckets))
    fabric, cum = 0.0, 0
    for b in buckets:
        cum += b.raw
        ready = backward_s * cum / total_raw
        fabric = max(fabric, ready) + cost_of_bytes(b.length * itemsize)
    return fabric


def dp_partition(leaf_sizes: Sequence[int], order: Sequence[int],
                 pad_unit: int, itemsize: int,
                 cost_of_bytes: Callable[[float], float],
                 backward_s: float,
                 upper_bound: float = math.inf) -> Tuple[Bucket, ...]:
    """Optimal contiguous partition of ``order``-ed leaves into buckets,
    minimizing ``partition_objective``.

    ``f[i]`` = minimal fabric-free time after syncing the first ``i`` leaves;
    ``f[i] = min_j max(f[j], ready_i) + cost(bytes(j..i))``.  States already
    at or above ``upper_bound`` (the greedy packer's objective) are pruned —
    they cannot lead to a better plan since costs are nonnegative.
    """
    sizes_o = [leaf_sizes[i] for i in order]
    n = len(sizes_o)
    prefix = [0] * (n + 1)
    for i, s in enumerate(sizes_o):
        prefix[i + 1] = prefix[i] + s
    total_raw = max(1, prefix[n])

    def padded(raw: int) -> int:
        return ((raw + pad_unit - 1) // pad_unit) * pad_unit

    f = [math.inf] * (n + 1)
    f[0] = 0.0
    parent = [0] * (n + 1)
    for i in range(1, n + 1):
        ready = backward_s * prefix[i] / total_raw
        best, arg = math.inf, 0
        for j in range(i):
            if f[j] >= upper_bound or f[j] >= best:
                continue
            c = cost_of_bytes(padded(prefix[i] - prefix[j]) * itemsize)
            v = max(f[j], ready) + c
            if v < best:
                best, arg = v, j
        f[i], parent[i] = best, arg

    bounds: List[Tuple[int, int]] = []
    i = n
    while i > 0:
        bounds.append((parent[i], i))
        i = parent[i]
    bounds.reverse()
    buckets: List[Bucket] = []
    offset = 0
    for bi, (j, i) in enumerate(bounds):
        ids = tuple(order[j:i])
        raw = prefix[i] - prefix[j]
        length = padded(raw)
        buckets.append(Bucket(index=bi, leaf_ids=ids, raw=raw,
                              offset=offset, length=length))
        offset += length
    return tuple(buckets)


def search_bucket_partition(leaf_sizes: Sequence[int], order: Sequence[int],
                            pad_unit: int, itemsize: int,
                            cost_of_bytes: Callable[[float], float],
                            backward_s: Optional[float] = None,
                            greedy_mbs: Sequence[float] = GREEDY_FALLBACK_MBS
                            ) -> PartitionPlan:
    """Greedy candidates for the upper bound, then the DP for the optimum.

    ``backward_s`` is the assumed backward-pass duration the ready times
    scale against; None defaults to the cost of one monolithic collective
    over the whole payload — the balanced compute≈comm regime where bucket
    boundaries matter most (a workload-measured value refines it).
    """
    total = sum(leaf_sizes)
    total_padded = ((total + pad_unit - 1) // pad_unit) * pad_unit
    if backward_s is None:
        backward_s = cost_of_bytes(total_padded * itemsize)
    best: Optional[PartitionPlan] = None
    for mb in greedy_mbs:
        elems = max(1, int(mb * 1e6 / itemsize))
        g = partition_buckets(leaf_sizes, order, elems, pad_unit)
        obj = partition_objective(g, cost_of_bytes, itemsize, backward_s)
        if best is None or obj < best.objective_s:
            best = PartitionPlan(g, obj, f"greedy:{mb:g}MB", backward_s)
    dp = dp_partition(leaf_sizes, order, pad_unit, itemsize, cost_of_bytes,
                      backward_s, upper_bound=best.objective_s)
    dp_obj = partition_objective(dp, cost_of_bytes, itemsize, backward_s)
    if dp_obj <= best.objective_s:
        return PartitionPlan(dp, dp_obj, "dp", backward_s)
    return best


class SuperstepEngine:
    """Compile-once bucket plan + runtime lowering for one (pytree, mesh).

    Everything the engine computes is host-static (leaf specs, mesh shape,
    config), so it is safe to build at trace time and cache; the runtime
    methods (``pack``/``sync``/ZeRO helpers) are pure traced functions.
    """

    def __init__(self, leaf_specs: Sequence[LeafSpec], cfg: BSPConfig,
                 sizes: Sequence[int], zero1: bool = False,
                 backward_s: Optional[float] = None):
        self.cfg = cfg
        self.sizes = tuple(sizes)
        self.axes = cfg.sync_axes
        self.world = math.prod(self.sizes)
        self.leaf_specs = tuple(leaf_specs)
        self.codec = make_codec(cfg.compression)   # uniform legacy codec
        # zero1: schedule picks price the trainer lowering (RS + shard
        # update + publish all-gather) instead of a bare all-reduce
        self.zero1 = zero1
        # cost-model link the tuner prices with: fitted (calibrated) params
        # when the config carries them, analytic TPU defaults otherwise
        self.link = cfg.link if cfg.link is not None else TPU_V5E_ICI
        self.backward_s_hint = backward_s

        from . import autotune
        leaf_sizes = [s.size for s in self.leaf_specs]
        order = tuple(reversed(range(len(self.leaf_specs))))
        pad_unit = max(1, self.world) * cfg.pad_align
        self.flat_itemsize = int(jnp.dtype(self._flat_dtype()).itemsize)

        auto_codec = cfg.bucket_codec == "auto"
        # int8's per-128-block scales need 128-aligned wire payloads
        codec_candidates = ("none", "bf16") + \
            (("int8",) if cfg.pad_align % 128 == 0 else ())
        if cfg.schedule == "auto":
            sched_candidates = None
        elif cfg.schedule == "xla":
            sched_candidates = ("fractal",)    # price psum as the butterfly
        else:
            sched_candidates = (cfg.schedule,)

        def policy_rank(payload_bytes: float):
            return autotune.rank_policies(
                self.sizes, payload_bytes, link=self.link,
                schedules=sched_candidates,
                codecs=codec_candidates if auto_codec else ("none",),
                zero1_publish=zero1)

        self.plan: Optional[PartitionPlan] = None
        if cfg.overlap and cfg.bucket_mb == "auto":
            self.plan = search_bucket_partition(
                leaf_sizes, order, pad_unit, self.flat_itemsize,
                cost_of_bytes=lambda by: policy_rank(by)[0].predicted_s,
                backward_s=backward_s)
            self.buckets = self.plan.buckets
        else:
            bucket_elems = None
            if cfg.bucket_mb is not None and cfg.overlap:
                bucket_elems = max(
                    1, int(cfg.bucket_mb * 1e6 / self.flat_itemsize))
            self.buckets = partition_buckets(leaf_sizes, order, bucket_elems,
                                             pad_unit)
        self.total_padded = sum(b.length for b in self.buckets)

        bucket_bytes = [b.length * self.flat_itemsize for b in self.buckets]
        if cfg.schedule == "xla" or \
                (cfg.schedule != "auto" and not auto_codec):
            self.schedules = (cfg.schedule,) * len(self.buckets)
            self.codec_names = self._uniform_codec_names()
        else:
            policies = [policy_rank(by)[0] for by in bucket_bytes]
            self.schedules = tuple(p.schedule for p in policies)
            self.codec_names = tuple(p.codec for p in policies) \
                if auto_codec else self._uniform_codec_names()
        if cfg.bucket_codec is not None:
            # only the fractal lowering carries a wire codec — a forced
            # codec on any other schedule would be silently inert on the
            # wire while still costing EF quantization in the trainer, so
            # it is normalized away per bucket.  (The legacy uniform
            # `compression` keeps its historical EF-always semantics.)
            self.codec_names = tuple(
                c if s == "fractal" else "none"
                for s, c in zip(self.schedules, self.codec_names))
        self.bucket_codecs = tuple(make_codec(n) for n in self.codec_names)

    def _uniform_codec_names(self) -> Tuple[str, ...]:
        name = self.cfg.bucket_codec \
            if self.cfg.bucket_codec not in (None, "auto") \
            else (self.cfg.compression or "none")
        return (name,) * len(self.buckets)

    def refined(self, measure: Callable[[str, float], float],
                measure_budget: int,
                measure_top_k: int = 2) -> "SuperstepEngine":
        """Measured-refinement of the per-bucket schedule picks.

        Spends up to ``measure_budget`` calls of ``measure(schedule,
        payload_bytes) → seconds`` (real jitted timings) re-picking the
        analytic winners, priciest buckets first — see
        ``autotune.pick_bucket_schedules``.  Returns a shallow copy with
        the refined picks.  The engine's existing (codec-aware) picks are
        the refinement's baseline: buckets the budget never reaches keep
        them untouched, and a measured bucket only changes when another
        candidate out-measured its incumbent.  A bucket whose schedule
        does change keeps its codec only if the new schedule can carry one
        (the fractal lowering is the only wire-codec path).  A forced
        schedule (anything but "auto") is respected: refinement then has
        nothing to re-pick and the engine comes back unchanged.
        """
        import copy

        from .autotune import pick_bucket_schedules
        if self.cfg.schedule != "auto":
            return copy.copy(self)     # forced/xla: no candidates to try
        names = pick_bucket_schedules(
            self.sizes,
            [b.length * self.flat_itemsize for b in self.buckets],
            link=self.link, zero1_publish=self.zero1, measure=measure,
            measure_budget=measure_budget, measure_top_k=measure_top_k,
            baseline=self.schedules)
        eng = copy.copy(self)
        eng.schedules = tuple(names)
        eng.codec_names = tuple(
            c if new == "fractal" else "none"
            for new, c in zip(names, self.codec_names))
        eng.bucket_codecs = tuple(make_codec(n) for n in eng.codec_names)
        return eng

    # -- plan inspection ----------------------------------------------------

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def shard_len(self, bucket: Bucket) -> int:
        return bucket.length // self.world

    def shard_offsets(self) -> Tuple[int, ...]:
        """Per-bucket start of this rank's shard in the rank-local moment
        vector (bucket-ordered concat of per-bucket shards)."""
        out, acc = [], 0
        for b in self.buckets:
            out.append(acc)
            acc += self.shard_len(b)
        return tuple(out)

    def programs(self) -> Tuple[schedule_ir.Program, ...]:
        """Bucket-tagged IR programs (one per bucket; "xla" not lowerable)."""
        out = []
        for b, name, codec in zip(self.buckets, self.schedules,
                                  self.codec_names):
            if name == "xla":
                raise ValueError("'xla' buckets have no IR program")
            prog = schedule_ir.build_program(name, self.sizes)
            meta = b.meta(self.n_buckets,
                          codec=None if codec == "none" else codec)
            out.append(prog.with_bucket(meta))
        return tuple(out)

    def describe(self) -> str:
        bs = self.flat_itemsize
        parts = ", ".join(
            f"b{b.index}:{b.length * bs / 1e6:.1f}MB→{s}"
            + ("" if c == "none" else f"+{c}")
            for b, s, c in zip(self.buckets, self.schedules,
                               self.codec_names))
        src = f" [{self.plan.source}]" if self.plan is not None else ""
        return (f"{self.n_buckets} bucket(s) over world {self.world} "
                f"({self.total_padded * bs / 1e6:.1f}MB padded){src}: "
                f"{parts}")

    def timeline(self, backward_s: float,
                 link: Optional[LinkParams] = None,
                 outer_link: Optional[LinkParams] = None,
                 mesh_contention: bool = True) -> OverlapTimeline:
        """Overlap-aware predicted step time for a given backward duration.

        Bucket i (reverse-layer) becomes ready once backward has produced
        its slice of the gradients: ready_i = backward_s × (cumulative
        parameter fraction through bucket i) — last layers first.
        ``link=None`` prices with the engine's own link (the calibrated
        params when ``BSPConfig(link=…)`` carries them).  Per-bucket codecs
        shrink the priced wire volume by their wire-bytes ratio and pay
        their quant/dequant launch overhead — the same terms the policy
        pricing (``autotune.rank_policies``) chose them by.
        """
        from .autotune import CODEC_STEP_ALPHAS as alphas, CODEC_WIRE_RATIO
        link = link if link is not None else self.link
        total_raw = max(1, sum(b.raw for b in self.buckets))
        ready, cum = [], 0
        for b in self.buckets:
            cum += b.raw
            ready.append(backward_s * cum / total_raw)
        vols = [float(b.length * self.flat_itemsize)
                * CODEC_WIRE_RATIO.get(c, 1.0)
                for b, c in zip(self.buckets, self.codec_names)]
        progs = self.programs()
        extra = [alphas.get(c, 0.0) * link.alpha_s * p.num_steps
                 for c, p in zip(self.codec_names, progs)]
        return overlap_step_cost(progs, vols, ready, link,
                                 outer_link, mesh_contention, extra_s=extra)

    # -- runtime lowering ---------------------------------------------------

    def _flat_dtype(self):
        if not self.leaf_specs:
            return jnp.dtype(jnp.float32)
        return jnp.result_type(*[jnp.dtype(s.dtype)
                                 for s in self.leaf_specs])

    def pack(self, leaves: Sequence[jax.Array],
             dtype=None) -> List[jax.Array]:
        """Leaves → per-bucket padded flat vectors (bucket-ordered)."""
        dtype = dtype or self._flat_dtype()
        parts = []
        for b in self.buckets:
            segs = [leaves[i].reshape(-1).astype(dtype) for i in b.leaf_ids]
            flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
            if b.raw != b.length:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((b.length - b.raw,), dtype)])
            parts.append(flat)
        return parts

    def unpack(self, parts: Sequence[jax.Array],
               like_leaves: Sequence[jax.Array]) -> List[jax.Array]:
        """Per-bucket flat vectors → leaves (original order, original
        dtypes)."""
        out: List[Optional[jax.Array]] = [None] * len(self.leaf_specs)
        for b, part in zip(self.buckets, parts):
            off = 0
            for i in b.leaf_ids:
                spec = self.leaf_specs[i]
                seg = lax.slice_in_dim(part, off, off + spec.size)
                out[i] = seg.reshape(spec.shape).astype(like_leaves[i].dtype)
                off += spec.size
        return out  # type: ignore[return-value]

    def _bucket_all_reduce(self, part: jax.Array, schedule: str,
                           codec=None) -> jax.Array:
        if schedule == "xla":
            return lax.psum(part, self.axes)
        if schedule == "fractal":
            return C.fractal_all_reduce(part, self.axes, self.sizes,
                                        codec=codec)
        return C.all_reduce(part, schedule, self.axes, self.sizes)

    def sync(self, grads: Any, mean: bool = True) -> Any:
        """Bucketed all-reduce of a gradient pytree — the drop-in
        replacement for the monolithic ``bsp.sync_gradients`` body.
        Each bucket rides its own codec (per-bucket policy under
        ``bucket_codec="auto"``; the uniform ``compression`` otherwise)."""
        if self.world == 1:
            return grads
        leaves, treedef = jax.tree.flatten(grads)
        parts = self.pack(leaves)
        out_parts = []
        for b, schedule, codec, part in zip(self.buckets, self.schedules,
                                            self.bucket_codecs, parts):
            red = self._bucket_all_reduce(part, schedule, codec)
            if mean:
                red = red / self.world
            out_parts.append(red)
        return treedef.unflatten(self.unpack(out_parts, leaves))

    def reduce_scatter_bucket(self, part: jax.Array, schedule: str,
                              codec=None) -> jax.Array:
        """Sum-reduce-scatter of one bucket part (ZeRO-1 grad shard);
        ``codec`` wire-compresses the fractal halving exchanges."""
        return C.reduce_scatter(part, schedule, self.axes, self.sizes,
                                codec=codec)

    def all_gather_bucket(self, shard: jax.Array) -> jax.Array:
        """Gather updated per-rank shards back into bucket flat order."""
        return C.all_gather_flat(shard, self.axes, self.sizes)


def leaf_specs_of(tree: Any, force_dtype=None) -> Tuple[LeafSpec, ...]:
    """LeafSpecs of a pytree of arrays / ShapeDtypeStructs."""
    return tuple(
        LeafSpec(shape=tuple(l.shape),
                 dtype=jnp.dtype(force_dtype or l.dtype).name)
        for l in jax.tree.leaves(tree))


@lru_cache(maxsize=64)
def _cached_engine(leaf_specs: Tuple[LeafSpec, ...], cfg: BSPConfig,
                   sizes: Tuple[int, ...], zero1: bool,
                   backward_s: Optional[float]) -> SuperstepEngine:
    return SuperstepEngine(leaf_specs, cfg, sizes, zero1=zero1,
                           backward_s=backward_s)


def engine_for(tree: Any, cfg: BSPConfig, sizes: Sequence[int],
               force_dtype=None, zero1: bool = False,
               backward_s: Optional[float] = None) -> SuperstepEngine:
    """The (cached) engine for this pytree's leaf structure.

    The plan depends only on leaf shapes/dtypes + config + mesh (+ the
    zero1 pricing mode and the DP search's backward hint), all host-static,
    so repeated traces reuse one engine.
    """
    return _cached_engine(leaf_specs_of(tree, force_dtype), cfg,
                          tuple(sizes), zero1, backward_s)

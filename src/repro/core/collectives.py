"""FractalSync collective schedules in JAX (shard_map + lax.ppermute).

The paper's H-tree barrier is recursive-pairwise: level l synchronizes pairs
of level-(l−1) groups, alternating mesh axes.  The software (all-ranks-active)
equivalent of that recursion is the **butterfly**: at step b every device
exchanges with the partner whose flat mesh index differs in bit b.  After
log2(N) steps every device has synchronized with all N.  We implement, inside
``shard_map``:

  * ``fractal_barrier``        — pure-control fsync: recursive doubling on a
                                 unit token (the paper's fsync(level)).
  * ``fractal_all_reduce``     — recursive halving-doubling all-reduce
                                 (reduce-scatter by halves + all-gather by
                                 doubles): 2·log2(N) steps (latency-optimal,
                                 like the H-tree) and 2·V·(N−1)/N bytes
                                 (bandwidth-optimal).  This is the schedule we
                                 deploy for BSP gradient synchronization.
  * ``fractal_reduce_scatter`` / ``fractal_all_gather`` — the two halves.
  * ``xy_all_reduce``          — the paper's XY baseline: dimension-ordered
                                 ring all-reduce (rows then columns).
  * ``naive_all_reduce``       — the paper's Naïve baseline: serial
                                 gather-to-root + broadcast-from-root.
  * ``hierarchical_all_reduce``— beyond-paper: the fractal recursion applied at
                                 pod granularity (intra-pod reduce-scatter →
                                 inter-pod all-reduce on 1/inner of the bytes →
                                 intra-pod all-gather), for meshes whose outer
                                 axis rides slower links.

All schedules are numerically validated against ``jax.lax.psum`` in
``tests/test_collectives.py`` on a 16-device host-platform mesh.

Conventions: ``axis_names`` is a tuple of mesh axis names, flattened row-major
into one logical rank index (outermost first), so bit 0 of the flat index is
the innermost axis — neighbors first, pods last, exactly the H-tree order.
Every axis size must be a power of two (as in the paper's meshes).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import schedule_ir

AxisNames = Tuple[str, ...]


# ---------------------------------------------------------------------------
# flat index helpers (inside shard_map)
# ---------------------------------------------------------------------------


def axis_sizes(axis_names: AxisNames) -> Tuple[int, ...]:
    return tuple(lax.psum(1, a) for a in axis_names)  # static under shard_map


def _static_sizes(mesh: jax.sharding.Mesh, axis_names: AxisNames) -> Tuple[int, ...]:
    return tuple(mesh.shape[a] for a in axis_names)


def flat_index(axis_names: AxisNames) -> jax.Array:
    """Row-major flat rank over ``axis_names`` (outermost first)."""
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


def _flat_perm(sizes: Sequence[int], fn: Callable[[int], int]):
    """Permutation [(src, fn(src))] over the flattened axis product."""
    n = math.prod(sizes)
    return [(i, fn(i)) for i in range(n)]


def _ppermute_flat(x, axis_names: AxisNames, perm):
    """ppermute over the flattened product of ``axis_names``.

    jax supports tuple axis_name for ppermute; indices are row-major over the
    named axes, matching ``flat_index``.
    """
    return lax.ppermute(x, axis_names, perm)


def _codec_exchange(send, axis_names: AxisNames, perm, codec):
    """One point-to-point exchange, optionally codec-compressed on the wire
    (the single definition of the wire protocol: encode → permute every
    wire leaf → decode).  Shared by every fractal halving/doubling step."""
    if codec is None:
        return _ppermute_flat(send, axis_names, perm)
    wire = codec.encode(send)
    wire = jax.tree.map(
        lambda leaf: _ppermute_flat(leaf, axis_names, perm), wire)
    return codec.decode(wire, send.shape, send.dtype)


def _codec_exchange_add(keep, send, axis_names: AxisNames, perm, codec):
    """``keep + exchange(send)`` — the receive side of every reduce hop.

    With a codec, the wire-decode is fused into the accumulate via
    ``kernels.tree_reduce.ops.decode_add`` (one launch instead of
    dequant-then-add; the fused per-step α that
    ``autotune.CODEC_STEP_ALPHAS`` prices).  Off-TPU ``decode_add``
    IS ``keep + codec.decode(wire)``, so CPU numerics are bit-identical
    to the unfused expression the collective tests pin."""
    if codec is None:
        return keep + _ppermute_flat(send, axis_names, perm)
    from repro.kernels.tree_reduce.ops import decode_add
    wire = codec.encode(send)
    wire = jax.tree.map(
        lambda leaf: _ppermute_flat(leaf, axis_names, perm), wire)
    return decode_add(keep, wire, codec)


# ---------------------------------------------------------------------------
# fractal (H-tree / butterfly) schedules
# ---------------------------------------------------------------------------


def _n_levels(sizes: Sequence[int]) -> int:
    n = math.prod(sizes)
    L = int(math.log2(n))
    if 1 << L != n:
        raise ValueError(f"fractal schedules need power-of-two world, got {n}")
    return L


def fractal_barrier(axis_names: AxisNames, sizes: Sequence[int],
                    level: int | None = None, token=None) -> jax.Array:
    """fsync(level): recursive-doubling barrier over the lowest ``level``
    levels of the synchronization tree (level=None → root = full world).

    Returns a scalar token that equals the number of devices in the sync
    domain — threading it into downstream computation enforces the barrier
    dependency (see ``core.barrier.fsync``)."""
    L = _n_levels(sizes)
    level = L if level is None else level
    if not 0 <= level <= L:
        raise ValueError(f"fsync level {level} outside 0..{L}")
    tok = jnp.ones((), jnp.int32) if token is None else token
    for b in range(level):
        recv = _ppermute_flat(tok, axis_names,
                              _flat_perm(sizes, lambda i, b=b: i ^ (1 << b)))
        tok = tok + recv
    return tok


def fractal_all_reduce(x: jax.Array, axis_names: AxisNames,
                       sizes: Sequence[int], codec=None) -> jax.Array:
    """Recursive halving-doubling all-reduce (the FractalSync schedule).

    Phase 1 (reduce-scatter by halves): at step b exchange half the working
    buffer with partner ``i ^ (1<<b)``; devices with bit b = 0 keep the low
    half.  Phase 2 (all-gather by doubles) mirrors it.  Requires the leading
    dim of ``x`` to be divisible by N (pad upstream; ``sync_gradients`` does).

    ``codec`` (optim.compression.Codec) compresses each exchanged payload —
    gradient compression rides the schedule's point-to-point hops.
    """
    L = _n_levels(sizes)
    n = 1 << L
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by world {n}")
    idx = flat_index(axis_names)

    def exchange(send, b):
        perm = _flat_perm(sizes, lambda i: i ^ (1 << b))
        return _codec_exchange(send, axis_names, perm, codec)

    # ---- reduce-scatter by halves ----
    for b in range(L):
        half = x.shape[0] // 2
        bit = (idx >> b) & 1
        # keep-low if bit==0 (start 0) else keep-high (start half)
        keep = lax.dynamic_slice_in_dim(x, bit * half, half, axis=0)
        send = lax.dynamic_slice_in_dim(x, (1 - bit) * half, half, axis=0)
        perm = _flat_perm(sizes, lambda i, b=b: i ^ (1 << b))
        x = _codec_exchange_add(keep, send, axis_names, perm, codec)

    # ---- all-gather by doubles ----
    for b in reversed(range(L)):
        bit = (idx >> b) & 1
        recv = exchange(x, b)
        # my piece is the low part if bit==0
        x = lax.cond(bit == 0,
                     lambda a, r: jnp.concatenate([a, r], axis=0),
                     lambda a, r: jnp.concatenate([r, a], axis=0),
                     x, recv)
    return x


def fractal_reduce_scatter(x: jax.Array, axis_names: AxisNames,
                           sizes: Sequence[int], codec=None) -> jax.Array:
    """Reduce-scatter by recursive halving: log2(N) steps, V·(N−1)/N bytes.
    Output is this device's shard (leading dim / N). Shard order follows the
    butterfly bit order (LSB-first); ``fractal_all_gather`` inverts it.

    ``codec`` compresses each exchanged half on the wire (the RS half of the
    per-bucket compression policy; partial sums are re-quantized per hop, so
    accuracy rides the codec's tolerance like the all-reduce codec path).
    """
    L = _n_levels(sizes)
    n = 1 << L
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by world {n}")
    idx = flat_index(axis_names)
    for b in range(L):
        half = x.shape[0] // 2
        bit = (idx >> b) & 1
        keep = lax.dynamic_slice_in_dim(x, bit * half, half, axis=0)
        send = lax.dynamic_slice_in_dim(x, (1 - bit) * half, half, axis=0)
        perm = _flat_perm(sizes, lambda i, b=b: i ^ (1 << b))
        x = _codec_exchange_add(keep, send, axis_names, perm, codec)
    return x


def fractal_all_gather(x: jax.Array, axis_names: AxisNames,
                       sizes: Sequence[int]) -> jax.Array:
    """Inverse of ``fractal_reduce_scatter`` (all-gather by doubling)."""
    L = _n_levels(sizes)
    idx = flat_index(axis_names)
    for b in reversed(range(L)):
        recv = _ppermute_flat(x, axis_names,
                              _flat_perm(sizes, lambda i, b=b: i ^ (1 << b)))
        bit = (idx >> b) & 1
        x = lax.cond(bit == 0,
                     lambda a, r: jnp.concatenate([a, r], axis=0),
                     lambda a, r: jnp.concatenate([r, a], axis=0),
                     x, recv)
    return x


# ---------------------------------------------------------------------------
# paper baselines
# ---------------------------------------------------------------------------


def ring_all_reduce(x: jax.Array, axis_name: str, size: int) -> jax.Array:
    """Flat ring all-reduce along one axis: reduce-scatter ring + all-gather
    ring, 2(k−1) steps. (The bandwidth-optimal flat baseline.)"""
    k = size
    if k == 1:
        return x
    if x.shape[0] % k:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by ring {k}")
    idx = lax.axis_index(axis_name)
    chunk = x.shape[0] // k
    shift_down = [(i, (i - 1) % k) for i in range(k)]

    def chunk_at(buf, c):
        return lax.dynamic_slice_in_dim(buf, c * chunk, chunk, axis=0)

    # reduce-scatter: after k−1 steps, device i owns reduced chunk i
    acc = chunk_at(x, (idx + 1) % k)
    for s in range(k - 1):
        acc = lax.ppermute(acc, axis_name, shift_down)
        c = (idx + 1 + s + 1) % k  # chunk arriving at this step
        acc = acc + chunk_at(x, c)
    # now acc = full sum of chunk idx  (c ends at idx)

    # all-gather ring
    pieces = [acc]
    cur = acc
    for s in range(k - 1):
        cur = lax.ppermute(cur, axis_name, shift_down)
        pieces.append(cur)
    # piece j (0-based, in arrival order) is chunk (idx + j) % k
    out = jnp.zeros_like(x)
    for j, piece in enumerate(pieces):
        c = (idx + j) % k
        out = lax.dynamic_update_slice_in_dim(out, piece, c * chunk, axis=0)
    return out


def xy_all_reduce(x: jax.Array, axis_x: str, axis_y: str,
                  size_x: int, size_y: int) -> jax.Array:
    """Paper's XY scheme: 1D ring all-reduce along x, then along y."""
    x = ring_all_reduce(x, axis_x, size_x)
    x = ring_all_reduce(x, axis_y, size_y)
    return x


def naive_all_reduce(x: jax.Array, axis_names: AxisNames,
                     sizes: Sequence[int]) -> jax.Array:
    """Paper's Naïve scheme: every device's contribution is serially funneled
    to rank 0 (gather-to-root along a ring into the root), reduced there, then
    broadcast back out the same way.  O(N) serial steps — the quadratic-cost
    baseline (each step moves full V through the root's port)."""
    n = math.prod(sizes)
    if n == 1:
        return x
    idx = flat_index(axis_names)
    shift_down = _flat_perm(sizes, lambda i: (i - 1) % n)
    # gather: pass contributions toward root; root accumulates
    acc = x
    buf = x
    for _ in range(n - 1):
        buf = _ppermute_flat(buf, axis_names, shift_down)
        acc = jnp.where(idx == 0, acc + buf, acc)
    # broadcast from root: push the total outward ring-wise
    shift_up = _flat_perm(sizes, lambda i: (i + 1) % n)
    out = acc
    for _ in range(n - 1):
        nxt = _ppermute_flat(out, axis_names, shift_up)
        out = jnp.where(idx == 0, out, nxt)
    return jnp.where(idx == 0, acc, out)


# ---------------------------------------------------------------------------
# beyond-paper: hierarchical (multi-pod) schedule
# ---------------------------------------------------------------------------


def hierarchical_all_reduce(x: jax.Array, inner_axes: AxisNames,
                            inner_sizes: Sequence[int], outer_axes: AxisNames,
                            outer_sizes: Sequence[int]) -> jax.Array:
    """Fractal recursion at pod granularity: intra-pod reduce-scatter (fast
    links), inter-pod all-reduce on V/inner bytes (slow links), intra-pod
    all-gather.  Inter-pod traffic shrinks by the intra-pod world size —
    the property that makes BSP viable across pods."""
    x = fractal_reduce_scatter(x, inner_axes, inner_sizes)
    x = fractal_all_reduce(x, outer_axes, outer_sizes) \
        if math.prod(outer_sizes) > 1 else x
    x = fractal_all_gather(x, inner_axes, inner_sizes)
    return x


# ---------------------------------------------------------------------------
# Schedule IR lowering: any all-reduce Program → shard_map + ppermute
# ---------------------------------------------------------------------------


def _step_tables(prog: schedule_ir.Program, step: schedule_ir.Step):
    """Host-side constant tables for one IR step (hashable for jit reuse):
    per-rank send/recv chunk ids, destination mask, reduce-vs-copy mask."""
    world, k = prog.world, step.max_chunks_moved
    S = np.zeros((world, k), np.int32)
    R = np.zeros((world, k), np.int32)
    is_dst = np.zeros((world,), bool)
    red = np.zeros((world,), bool)
    perm = []
    for t in step.transfers:
        S[t.src] = t.chunks
        R[t.dst] = t.chunks
        is_dst[t.dst] = True
        red[t.dst] = t.reduce
        perm.append((t.src, t.dst))
    return perm, S, R, is_dst, red


def ir_all_reduce(x: jax.Array, prog: schedule_ir.Program,
                  axis_names: AxisNames) -> jax.Array:
    """Execute an all-reduce IR Program inside ``shard_map``.

    The generic lowering that subsumes the hand-rolled per-schedule loops:
    the payload is viewed as ``[n_chunks, chunk]``; each IR step becomes one
    ``lax.ppermute`` (the IR validator guarantees every step is a partial
    permutation with uniform message shapes) bracketed by chunk gathers and
    reduce-or-overwrite scatters driven by per-rank constant tables.
    """
    if prog.kind != schedule_ir.ALL_REDUCE:
        raise ValueError(f"cannot lower {prog.kind!r} program {prog.name!r}")
    n_chunks = prog.n_chunks
    if prog.world == 1:
        return x
    if x.shape[0] % n_chunks:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by "
                         f"{n_chunks} chunks of {prog.name!r}")
    idx = flat_index(axis_names)
    buf = x.reshape(n_chunks, x.shape[0] // n_chunks, *x.shape[1:])
    for step in prog.steps:
        if not step.transfers:
            continue
        perm, S, R, is_dst, red = _step_tables(prog, step)
        send = jnp.take(buf, jnp.asarray(S)[idx], axis=0)
        recv = lax.ppermute(send, axis_names, perm)
        rids = jnp.asarray(R)[idx]
        merged = jnp.where(jnp.asarray(red)[idx],
                           buf.at[rids].add(recv),
                           buf.at[rids].set(recv))
        buf = jnp.where(jnp.asarray(is_dst)[idx], merged, buf)
    return buf.reshape(x.shape)


# ---------------------------------------------------------------------------
# schedule registry + flat-tensor entry point (used by BSP gradient sync)
# ---------------------------------------------------------------------------

SCHEDULES = schedule_ir.SCHEDULES + ("xla",)


def all_reduce(x: jax.Array, schedule: str, axis_names: AxisNames,
               sizes: Sequence[int]) -> jax.Array:
    """Dispatch an all-reduce over the flattened ``axis_names`` world.

    Every software schedule routes through the Schedule IR (one builder per
    schedule, one generic lowering); ``"xla"`` short-circuits to
    ``lax.psum``.  ``x`` must have a leading dim divisible by the world size
    (BSP gradient sync pads to this).  The pre-IR hand-rolled lowerings
    above remain exported for the reduce-scatter/all-gather split that the
    ZeRO-1 trainer uses and as cross-checks in the test-suite.
    """
    if schedule == "xla":
        return lax.psum(x, axis_names)
    prog = schedule_ir.build_program(schedule, tuple(sizes))
    return ir_all_reduce(x, prog, axis_names)


def bit_reversed_index(axis_names: AxisNames, sizes: Sequence[int]
                       ) -> jax.Array:
    """Bit-reversal of this rank's flat index over log2(world) bits.

    After recursive-halving reduce-scatter, rank i holds the CONTIGUOUS
    payload chunk at bit-reversed position rev(i) — the coarsest split is
    decided by bit 0.  Every consumer of the ZeRO-1 shard layout (trainer,
    SuperstepEngine) derives shard placement from this one definition.
    """
    L = _n_levels(sizes)   # raises unless the world is a power of two
    idx = flat_index(axis_names)
    rev = jnp.zeros((), jnp.int32)
    for b in range(L):
        rev = rev | (((idx >> b) & 1) << (L - 1 - b))
    return rev


def reduce_scatter(x: jax.Array, schedule: str, axis_names: AxisNames,
                   sizes: Sequence[int], codec=None) -> jax.Array:
    """Schedule-dispatched reduce-scatter of a flat payload (sum, no mean).

    Returns this rank's shard (leading dim / world) at the bit-reversed
    position ``bit_reversed_index`` describes.  The fractal schedule
    reduce-scatters natively (half the butterfly); every other schedule
    falls back to its full all-reduce followed by a local slice — same
    bytes on the wire as its all-reduce, same shard layout out.

    ``codec`` wire-compresses the fractal path only (the per-bucket codec
    policy never assigns codecs to other schedules).
    """
    world = math.prod(sizes)
    if schedule == "fractal":
        return fractal_reduce_scatter(x, axis_names, sizes, codec=codec)
    shard_len = x.shape[0] // world
    full = all_reduce(x, schedule, axis_names, sizes)
    rev = bit_reversed_index(axis_names, sizes)
    return lax.dynamic_slice_in_dim(full, rev * shard_len, shard_len, axis=0)


def all_gather_flat(shard: jax.Array, axis_names: AxisNames,
                    sizes: Sequence[int]) -> jax.Array:
    """Inverse of ``reduce_scatter``'s placement: gather shards back into
    the original flat order (the butterfly all-gather inverts the
    bit-reversed scatter for every schedule, since the layout is shared)."""
    return fractal_all_gather(shard, axis_names, sizes)

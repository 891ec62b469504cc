"""Continuous-batching serve engine over the SlotState protocol: per-layer
decode-state backends (contiguous KV, paged KV, recurrent rows) composed
from the architecture config.

The wave-based loop this replaces admitted B requests, decoded until the
whole wave drained, and only then admitted again — freed slots idled behind
the wave's straggler.  Here a fixed pool of ``max_slots`` decode slots runs
over one shared cache and a queued request is admitted the moment EOS or
the per-request budget frees a slot:

  * **jit-stable decode**: every decode step is one compiled call over the
    full [S] slot batch — fixed slot count, per-slot cache offsets (the
    vector-``offset`` form of ``transformer.decode_step``), inactive rows
    masked by writing to the cache sentinel position the causal mask hides
    (KV) and by gating the state advance on the sentinel row (recurrent).
    Slot churn never recompiles anything.
  * **chunked admission prefill**: prompts stream through one compiled
    [1, prefill_chunk] function (``transformer.prefill_chunk``) into the
    admitted slot's state, interleaved between decode steps so ongoing
    decodes keep making progress while newcomers prefill.
  * **single RNG split discipline**: token t of request r is sampled with
    ``fold_in(fold_in(seed_key, r), t)`` — including the FIRST token (the
    wave-era loop sampled it from the unsplit top-level key).  Sampling is
    deterministic per request, independent of slot assignment, admission
    order, pool size, state backend, or preemption.
  * **mesh composition**: given a 1-axis ("data",) mesh the slot batch dim
    of every per-step input shards across devices; params are replicated
    (serve-style), activations follow ``act_sharding``.

Per-layer state backends (``serve.slot_state.StatePlan``): attention / MLA
layers follow the engine's KV mode, recurrent layers (mamba / xLSTM)
always take the recurrent-row backend — hybrid stacks (Jamba) mix both
inside one engine run:

  * ``contiguous`` KV — one ``max_len`` cache row per slot (the slot index
    IS the cache batch row); admission is free-slot driven.  Simple, but
    HBM caps concurrency at ``pool_positions / max_len`` even when
    requests use a fraction of their reservation.
  * ``paged`` KV — one pooled tensor of ``kv_blocks`` × ``block_size``
    positions per cache leaf; each slot maps virtual positions onto
    physical blocks through a block table (``blocks.BlockAllocator`` owns
    the host bookkeeping).  Admission is free-BLOCK driven, identical
    prompt prefixes share refcounted blocks (copy-on-write when a shared
    block must be rewritten), and when the pool runs dry mid-decode the
    YOUNGEST request is preempted: its resources are freed and the request
    requeued — the fold-in RNG regenerates its tokens exactly on re-serve,
    so preemption is invisible in outputs.

    Token identity with the contiguous backend holds by construction:
    ``max_len % block_size == 0`` makes the gathered virtual KV view the
    same shape AND the same values as a contiguous row, and prefix-cache
    hits are rounded down to the prefill-chunk grid so chunk boundaries —
    hence the cached k/v content — match a from-scratch prefill (the
    paged suite and serve benchmarks assert exact token identity end to
    end).
  * ``recurrent`` rows — O(1) per-request state in a pooled
    ``[rec_slots + 1, ...]`` leaf (row 0 = sentinel).  Admission takes one
    row (a SECOND resource next to KV blocks: both must be free before
    either commits); the row never grows, so recurrent state can defer
    admission but never triggers mid-decode preemption.  Prefill chunks
    stay on the aligned ``[k·C, (k+1)·C)`` grid with the padded tail gated
    off by a validity mask — the state advances over every prompt token
    exactly once, which is what makes continuous-path outputs
    token-identical to the wave loop.  Prefix-cache sharing is disabled
    for recurrent-bearing archs: a prefix hit would skip the state
    computation the recurrence needs.

Tracing: the engine names its own phases, always on (with no profiler
running, a span is a couple of Python calls).  ``serve.step`` (a step
annotation) holds ``serve.admit``, one ``serve.prefill`` per chunk
(``req_id=``, ``chunk=``; children ``.prepare``, ``.dispatch``,
``.first_token``, ``.publish``) and
``serve.decode.{prepare,dispatch,readback,commit}``; under a profiler they
land on the host's trace beside the device's ``jit_serve_*`` programs.

``serve_waves`` keeps the old wave-at-a-time loop alive as the TEST ORACLE
(plus the measured baseline for ``benchmarks/serve_bench.py``): it batch-
prefills whole prompts with no chunking, no masking and no slot reuse, so
any engine output can be checked against it token for token.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ArchConfig
from repro.kernels import on_tpu
from repro.models import transformer as T

from .blocks import BlockAllocator, NoFreeBlocks
from .metrics import ServeMetrics
from .queue import Request, RequestQueue
from .slot_state import RecurrentRows, StatePlan
from .slots import ACTIVE, PREFILL, SlotTable


@dataclass(frozen=True)
class EngineConfig:
    """Engine knobs (everything the serve CLI exposes lands here)."""

    max_slots: int = 8
    max_len: int = 256           # cache positions per request (prompt + gen)
    prefill_chunk: int = 16      # admission prefill chunk length
    chunks_per_step: int = 1     # prefill chunks interleaved per decode step
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    kv_mode: str = "contiguous"  # "contiguous" | "paged"
    slot_state: str = "auto"     # "auto" (follow kv_mode) | "contiguous" |
                                 # "paged" — KV-layer backend override;
                                 # recurrent layers always take the
                                 # recurrent-row backend
    rec_slots: int = 0           # recurrent rows (0 = match max_slots);
                                 # < max_slots makes rows the scarce
                                 # admission resource
    block_size: int = 16         # paged: positions per physical block
    kv_blocks: int = 0           # paged: pool size (0 = match contiguous
                                 # capacity: 1 + max_slots * max_len / bs)
    paged_kernel: str = "auto"   # paged decode attention lowering:
                                 # "pallas" (fused block-table kernel) |
                                 # "ref" (gather-then-attend oracle) |
                                 # "auto" (pallas on TPU, ref elsewhere)
    clock: str = "step"          # "step" (virtual, deterministic — the
                                 # loops never sleep) | "wall" (measured
                                 # seconds; idle gaps really sleep)
    step_s: float = 0.01         # virtual seconds per engine step


def _check_arch(cfg: ArchConfig) -> None:
    """Every token-only architecture serves: attention/MLA layers through a
    KV backend, recurrent layers (mamba/xlstm) through pooled state rows,
    hybrids through both at once (``slot_state.StatePlan``).  Only the
    frontend (prefix-image) path is rejected — it needs per-request
    embeddings at admission and requests are token-only."""
    if cfg.frontend:
        raise ValueError(
            f"{cfg.name}: frontend architectures are not servable "
            "(requests are token-only)")


def _named_jit(fn, name: str, donate=()):
    """``jax.jit(fn)`` under a stable program name: ``jit_<name>`` in a
    profiler trace and in the compiled module.  ``donate`` lists the
    arguments the program consumes: the serve programs take the cache
    there, so its output aliases the input and the pool is updated in
    place instead of written anew every call."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, donate_argnums=donate)


def serve_programs(cfg: ArchConfig, *, paged_kernel: str, contig_kv: bool):
    """The engine's jitted cache programs by name.  Each takes the cache
    as a donated argument and returns its successor, which aliases it: a
    call updates the pool in place.

    One decode / admit pair serves every backend mix: unused backend
    inputs are passed as None (an empty pytree — traced away).  MoE stacks:
    the decode program also returns the step's tokens routed to each held
    expert of each MoE layer (live rows only).  Admission: contiguous KV
    (``contig_kv``) slices the slot's row, prefills one chunk into it,
    writes it back (paged mode addresses the pool through the slot's
    [1, n_max] table row instead; recurrent state is row-addressed in
    place via ``rec_row``).  Interior chunks only feed the cache, so they
    skip the full-vocab head projection (the dominant admission FLOPs at
    real vocab sizes)."""
    moe = cfg.moe is not None

    def admit(with_logits):
        def fn(p, c, tokens, slot, offset, table, rec_row, valid):
            sub = T.take_state(cfg, c, slot) if contig_kv else c
            logits, sub = T.prefill_chunk(
                p, cfg, tokens, sub, offset, with_logits=with_logits,
                block_tables=table, rec_rows=rec_row, valid=valid)
            if contig_kv:
                return logits, T.write_state(cfg, c, sub, slot)
            return logits, sub
        return fn

    fns = {
        "serve_decode": (
            lambda p, tok, c, off, bt, rows, act: T.decode_step(
                p, cfg, tok, c, off, block_tables=bt,
                paged_kernel=paged_kernel, rec_rows=rows, active=act,
                return_routed=moe), 2),
        "serve_prefill": (admit(True), 1),
        "serve_prefill_quiet": (admit(False), 1),
        "serve_reset": (
            lambda c, slot, row: T.reset_slot_state(cfg, c, slot=slot,
                                                    rec_row=row), 0),
        "serve_copy_block": (
            lambda c, src, dst: T.copy_block(c, src, dst), 0),
    }
    return {name: _named_jit(fn, name, donate=(arg,))
            for name, (fn, arg) in fns.items()}


def _make_sampler(base_key, temperature: float):
    """The single RNG split discipline both serving modes share: token t of
    request r is drawn with ``fold_in(fold_in(base_key, r), t)``.  One
    definition — the wave/continuous token-identity invariant (asserted in
    ``benchmarks/serve_bench.py``) depends on the two modes never drifting.
    """

    def sample(logits, req_ids, tok_idx):
        """logits [N,V] → tokens [N]."""
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(l, r, t):
            k = jax.random.fold_in(jax.random.fold_in(base_key, r), t)
            return jax.random.categorical(k, l / temperature).astype(
                jnp.int32)

        return jax.vmap(one)(logits, req_ids, tok_idx)

    return sample


class ServeEngine:
    """Fixed slot pool + per-layer SlotState backends + arrival queue."""

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig,
                 mesh=None):
        _check_arch(cfg)
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        if ecfg.chunks_per_step < 1:
            raise ValueError("chunks_per_step must be >= 1")
        if ecfg.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if ecfg.kv_mode not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_mode {ecfg.kv_mode!r}")
        if ecfg.slot_state not in ("auto", "contiguous", "paged"):
            raise ValueError(f"unknown slot_state {ecfg.slot_state!r}")
        if ecfg.paged_kernel not in ("auto", "pallas", "ref"):
            raise ValueError(f"unknown paged_kernel {ecfg.paged_kernel!r}")
        if ecfg.clock not in ("step", "wall"):
            raise ValueError(f"unknown clock {ecfg.clock!r}")
        if ecfg.rec_slots < 0:
            raise ValueError("rec_slots must be >= 0")
        kv_mode = (ecfg.kv_mode if ecfg.slot_state == "auto"
                   else ecfg.slot_state)
        self.plan = StatePlan.resolve(cfg, kv_mode)
        self.has_rec = self.plan.has_recurrent
        self.has_kv = self.plan.has_kv
        # "paged" only means something when there are positional leaves to
        # page: a pure-recurrent arch ignores the KV mode entirely
        self.paged = self.has_kv and kv_mode == "paged"
        # "auto" follows the platform: the fused kernel on TPU, the gather
        # oracle elsewhere (interpret-mode kernels would crawl); explicit
        # "pallas" forces the kernel anywhere (interpret off-TPU) so parity
        # tests can pin fused-vs-ref token identity on any host.
        if ecfg.paged_kernel == "auto":
            self.paged_kernel = "pallas" if on_tpu() else "ref"
        else:
            self.paged_kernel = ecfg.paged_kernel
        # a padded chunk must fit the cache row (a clamped dynamic-slice
        # write would silently shift over live positions)
        self._chunk = min(ecfg.prefill_chunk, ecfg.max_len)

        if self.paged:
            bs = ecfg.block_size
            if ecfg.max_len % bs:
                raise ValueError(
                    f"paged mode needs max_len ({ecfg.max_len}) divisible "
                    f"by block_size ({bs}): the gathered virtual KV view "
                    "must match the contiguous row shape bit-for-bit")
            nblocks = ecfg.kv_blocks or (
                1 + ecfg.max_slots * (ecfg.max_len // bs))
            self.allocator: Optional[BlockAllocator] = \
                BlockAllocator(nblocks, bs)
            self.table = SlotTable(ecfg.max_slots, ecfg.max_len,
                                   block_size=bs)
        else:
            self.allocator = None
            self.table = SlotTable(ecfg.max_slots, ecfg.max_len)

        # the second admission resource: one pooled state row per live
        # request on recurrent-bearing archs
        self.rec: Optional[RecurrentRows] = None
        if self.has_rec:
            self.rec = RecurrentRows(ecfg.rec_slots or ecfg.max_slots)

        self.queue = RequestQueue()
        self.metrics = ServeMetrics(max_slots=ecfg.max_slots,
                                    clock=ecfg.clock, step_s=ecfg.step_s)
        self.results: Dict[int, List[int]] = {}
        self._key = jax.random.key(ecfg.seed)
        self._admission_hold = 0     # steps left with admission stalled
        self._steps = 0              # engine steps taken (trace step_num)
        self._step_t0 = 0.0          # serve-clock start of the current step

        self._data_spec = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if ecfg.max_slots % mesh.devices.size:
                raise ValueError(
                    f"--max-slots {ecfg.max_slots} must divide across "
                    f"{mesh.devices.size} devices")
            self._data_spec = lambda ndim: NamedSharding(
                mesh, P("data", *([None] * (ndim - 1))))
            replicated = NamedSharding(mesh, P())
            params = jax.device_put(params, jax.tree.map(
                lambda _: replicated, params))
        self.params = params

        if self.has_rec:
            # hybrid/recurrent cache: KV leaves sized by the KV backend's
            # geometry, recurrent leaves by the row pool (+ sentinel row 0)
            if self.paged:
                kv_batch, kv_len = self.allocator.num_blocks, ecfg.block_size
            else:
                kv_batch, kv_len = ecfg.max_slots, ecfg.max_len
            cache = T.init_hybrid_cache(cfg, kv_batch=kv_batch,
                                        kv_len=kv_len,
                                        rec_batch=self.rec.capacity + 1)
            if mesh is not None:
                # pooled recurrent rows (and paged pools) have no slot dim:
                # replicate the whole cache and let the data-sharded
                # per-step inputs drive the layout
                from jax.sharding import NamedSharding, PartitionSpec as P
                replicated = NamedSharding(mesh, P())
                cache = jax.tree.map(
                    lambda x: jax.device_put(x, replicated), cache)
        elif self.paged:
            cache = T.init_paged_cache(cfg, self.allocator.num_blocks,
                                       ecfg.block_size)
            if mesh is not None:
                # the pooled leaves have no slot dim: replicate them and
                # let the data-sharded per-step inputs drive the layout
                from jax.sharding import NamedSharding, PartitionSpec as P
                replicated = NamedSharding(mesh, P())
                cache = jax.tree.map(
                    lambda x: jax.device_put(x, replicated), cache)
        else:
            cache = T.init_cache(cfg, ecfg.max_slots, ecfg.max_len)
            if self._data_spec is not None:
                # cache leaves are [reps, S, ...]: slot batch dim is axis 1
                from jax.sharding import NamedSharding, PartitionSpec as P
                cache = jax.tree.map(
                    lambda x: jax.device_put(x, NamedSharding(
                        mesh, P(None, "data", *([None] * (x.ndim - 2))))),
                    cache)
        self.cache = cache

        self.moe = cfg.moe is not None
        progs = serve_programs(cfg, paged_kernel=self.paged_kernel,
                               contig_kv=self.has_kv and not self.paged)
        self._decode = progs["serve_decode"]
        self._admit = progs["serve_prefill"]
        self._admit_quiet = progs["serve_prefill_quiet"]
        self._reset = progs["serve_reset"]
        if self.paged:
            self._copy = progs["serve_copy_block"]
        self._sample = _named_jit(
            _make_sampler(self._key, ecfg.temperature), "serve_sample")

    def _took_cache(self, old) -> None:
        """Count a program call that took the cache ``old``: in place when
        the call consumed its buffers (a donated input is deleted at
        dispatch; a refused donation leaves it alive, and JAX warns).  A
        host check: nothing waits for the device."""
        self.metrics.on_cache_call(jax.tree.leaves(old)[0].is_deleted())

    def _put(self, x):
        if self._data_spec is None:
            return x
        return jax.device_put(x, self._data_spec(np.ndim(x)))

    # -- request intake ---------------------------------------------------
    def submit(self, requests) -> None:
        if isinstance(requests, Request):
            requests = [requests]
        # validate the WHOLE batch before recording anything: a bad request
        # must not leave phantom metrics records for its batchmates
        for r in requests:
            need = len(r.prompt) + r.max_new_tokens
            if need > self.ecfg.max_len:
                raise ValueError(
                    f"request {r.req_id}: prompt+gen {need} exceeds "
                    f"max_len {self.ecfg.max_len}")
            if self.paged:
                # the last decode write lands at position prompt+gen-2, so
                # a lone request must fit the pool or it would preempt
                # itself forever
                worst = (len(r.prompt) + r.max_new_tokens - 2) \
                    // self.allocator.block_size + 1
                if worst > self.allocator.capacity:
                    raise ValueError(
                        f"request {r.req_id}: worst case {worst} blocks "
                        f"exceeds the pool ({self.allocator.capacity} "
                        "usable blocks)")
        for r in requests:
            self.metrics.on_submit(r.req_id, r.arrival_s, len(r.prompt))
        self.queue.submit(requests)

    # -- backend resource plumbing ----------------------------------------
    def _record_blocks(self) -> None:
        self.metrics.on_blocks(self.allocator.num_used,
                               self.allocator.capacity)

    def _free_resources(self, slot) -> None:
        """Hand every backend resource the slot holds back to its pool."""
        if self.allocator is not None and slot.blocks:
            self.allocator.free_blocks(slot.blocks)
            slot.blocks = []
            self._record_blocks()
        if self.rec is not None and slot.rec_row:
            self.rec.free(slot.rec_row)
            slot.rec_row = 0

    def _preempt(self, victim) -> None:
        """Free the victim's resources (blocks AND recurrent row) and send
        its request back to the queue.  The fold-in RNG regenerates its
        tokens exactly on re-serve, so the only trace is the
        ``preemptions`` counter (and the wasted decode tokens, which
        ``metrics.wasted_decode_tokens`` books)."""
        req = victim.request
        self._free_resources(victim)
        self.table.release(victim)
        self.metrics.on_preempt(req.req_id)
        self.queue.submit(req)

    def _make_room(self, slot) -> bool:
        """The pool is dry: preempt the youngest busy request.  Returns
        False when the victim was ``slot`` itself (the caller must stop
        touching it)."""
        victim = self.table.youngest_busy()
        if victim is slot and len(self.table.busy()) == 1:
            # cannot happen given submit()'s worst-case validation, but
            # fail loudly rather than spin
            raise RuntimeError("KV pool too small for the only live request")
        self._preempt(victim)
        return victim is not slot

    def _alloc_block(self, slot) -> Optional[int]:
        """Allocate one block for ``slot``, preempting the youngest busy
        request while the pool is dry.  Returns None when ``slot`` itself
        was the youngest and got preempted."""
        while True:
            try:
                return self.allocator.alloc()
            except NoFreeBlocks:
                if not self._make_room(slot):
                    return None

    def _ensure_writable(self, slot, block_idx: int,
                         need_copy: bool = True) -> bool:
        """Copy-on-write: make ``slot.blocks[block_idx]`` private before a
        write (``allocator.cow`` forks the host side, ``copy_block`` clones
        the device payload — skipped when the imminent write overwrites
        the whole block anyway).  Returns False if ``slot`` was preempted
        while making room for the copy."""
        while True:
            blk = slot.blocks[block_idx]
            try:
                new, copied = self.allocator.cow(blk)
            except NoFreeBlocks:
                if not self._make_room(slot):
                    return False
                continue        # a preemption may even have unshared blk
            if copied:
                if need_copy:
                    old = self.cache
                    self.cache = self._copy(old, blk, new)
                    self._took_cache(old)
                slot.blocks[block_idx] = new
            return True

    def _ensure_writable_range(self, slot, lo: int, hi: int) -> bool:
        """COW every allocated block covering positions [lo, hi); blocks
        fully inside the range skip the device copy (every position is
        about to be rewritten)."""
        bs = self.allocator.block_size
        for bi in range(lo // bs, min(-(-hi // bs), len(slot.blocks))):
            full = lo <= bi * bs and (bi + 1) * bs <= hi
            if not self._ensure_writable(slot, bi, need_copy=not full):
                return False
        return True

    def _try_admit_paged(self, slot, req) -> bool:
        """Map the request's prompt onto blocks: prefix-cache hits share
        published blocks (refcounted), the tail gets fresh ones.  Fails
        (False) when the free list cannot cover the tail — the caller
        requeues the request and stops admitting this step.

        Recurrent-bearing archs skip prefix matching entirely: a prefix
        hit would skip the prompt positions the recurrent state must
        advance over, serving from a stale (zero) recurrence."""
        alloc = self.allocator
        bs = alloc.block_size
        plen = len(req.prompt)
        matched = [] if self.has_rec else alloc.match_prefix(req.prompt)
        fresh_needed = alloc.blocks_for(plen) - len(matched)
        if fresh_needed > alloc.num_free:
            alloc.free_blocks(matched)
            return False
        # prefill restarts on the chunk grid so every chunk has the same
        # shape — hence bit-identical k/v — as a from-scratch prefill; the
        # cap at the last grid point below plen guarantees the final chunk
        # still produces the first token's logits
        C = self._chunk
        pos0 = min((len(matched) * bs // C) * C, ((plen - 1) // C) * C)
        self.table.assign(slot, req)
        slot.blocks = matched + [alloc.alloc() for _ in range(fresh_needed)]
        slot.prefill_pos = pos0
        self.metrics.on_admit(req.req_id)
        if not self.has_rec:
            self.metrics.on_prefix_lookup(pos0, plen)
        self._record_blocks()
        return True

    # -- engine phases (one call each per step) ---------------------------
    def _admit_ready(self, now_s: float) -> None:
        with TraceAnnotation("serve.admit"):
            for slot in self.table.free():
                req = self.queue.pop_ready(now_s)
                if req is None:
                    return
                # TWO-RESOURCE admission: every backend must have capacity
                # before either commits (nothing to unwind on failure).
                # Recurrent rows never free mid-decode, so a deferral clears
                # only when a request finishes (or is preempted); FIFO order
                # is preserved by requeueing and admitting nobody behind the
                # blocked request.
                if self.rec is not None and self.rec.num_free == 0:
                    self.queue.submit(req)
                    return
                if self.paged:
                    if not self._try_admit_paged(slot, req):
                        # not enough free blocks: put the request back (the
                        # queue re-sorts it into place) and keep FIFO order by
                        # not admitting anyone behind it
                        self.queue.submit(req)
                        return
                else:
                    self.table.assign(slot, req)
                    self.metrics.on_admit(req.req_id)
                if self.rec is not None:
                    slot.rec_row = self.rec.alloc()
                # device-side hygiene: a reused contiguous slot row and/or
                # recurrent row starts zeroed (paged blocks need no reset —
                # fresh blocks are written before they are ever read)
                if self.rec is not None or not self.paged:
                    slot_idx = (slot.index if self.has_kv and not self.paged
                                else None)
                    row = slot.rec_row if self.rec is not None else None
                    old = self.cache
                    self.cache = self._reset(old, slot_idx, row)
                    self._took_cache(old)

    def _finish(self, slot) -> None:
        req = slot.request
        self.results[req.req_id] = list(slot.output)
        self._free_resources(slot)
        self.table.release(slot)
        self.metrics.on_finish(req.req_id)

    def _complete_if_done(self, slot, token: int) -> bool:
        eos = self.ecfg.eos_id
        if (eos is not None and token == eos) \
                or slot.generated >= slot.request.max_new_tokens:
            self._finish(slot)
            return True
        return False

    def _prefill_tick(self) -> None:
        """Advance up to ``chunks_per_step`` admission prefills one chunk.

        Chunk geometry, KV-only archs: short prompts (≤ chunk) pad at the
        END (garbage positions are causally masked until overwritten by
        decode); a ragged TAIL chunk is RIGHT-ALIGNED at ``plen - chunk``,
        re-writing the overlap with bit-identical k/v (k/v at a position
        depend only on its token, its position, and the already-written
        prefix).

        Recurrent-bearing archs instead keep every chunk on the ALIGNED
        ``[k·C, (k+1)·C)`` grid with the final chunk end-padded and gated
        off by ``valid``: re-running an overlap would advance the
        recurrence twice over those tokens.  KV layers in the same stack
        tolerate the end padding exactly like the short-prompt case.

        Paged mode starts at the prefix-cache hit point (chunk-grid
        aligned, so the geometry — and the written bits — match the
        contiguous backend exactly); a tail chunk that dips into shared
        blocks copy-on-writes them first.
        """
        C = self._chunk
        budget = self.ecfg.chunks_per_step
        fed = set()
        for slot in self.table.prefilling():
            if budget <= 0:
                break
            if slot.state != PREFILL:   # preempted earlier this tick
                continue
            with TraceAnnotation("serve.prefill", req_id=slot.req_id,
                                 chunk=slot.prefill_pos // C):
                if not self._prefill_chunk(slot):
                    continue                    # preempted mid-COW
            fed.add(slot.req_id)
            budget -= 1
        self.metrics.on_prefill_passed_over(
            [s.req_id for s in self.table.prefilling()
             if s.req_id not in fed], self._step_t0)

    def _prefill_chunk(self, slot) -> bool:
        """Run ``slot``'s next prompt chunk (geometry: ``_prefill_tick``);
        on the prompt's last chunk, sample its first token.  Returns False
        when the slot was preempted while its chunk's blocks were made
        writable."""
        C = self._chunk
        with TraceAnnotation("serve.prefill.prepare"):
            prompt = np.asarray(slot.request.prompt, np.int32)
            plen = len(prompt)
            remaining = plen - slot.prefill_pos
            chunk = np.zeros((1, C), np.int32)
            valid = None
            if self.has_rec:                    # aligned grid, masked tail
                start = slot.prefill_pos
                n = min(C, remaining)
                last_row = n - 1
                chunk[0, :n] = prompt[start:start + n]
                valid = n
            elif plen <= C:                     # whole prompt, end-padded
                start, last_row = 0, plen - 1
                chunk[0, :plen] = prompt
            elif remaining > C:                 # full interior chunk
                start, last_row = slot.prefill_pos, C - 1
                chunk[0] = prompt[start:start + C]
            else:                               # right-aligned tail chunk
                start, last_row = plen - C, C - 1
                chunk[0] = prompt[start:plen]
            final = remaining <= C
            admit = self._admit if final else self._admit_quiet
            if self.paged:
                if not self._ensure_writable_range(slot, start, start + C):
                    return False
                table = jnp.asarray(self.table.block_table_row(slot))
            else:
                table = None
            rec_row = (None if self.rec is None
                       else jnp.asarray([slot.rec_row], jnp.int32))
            inputs = (jnp.asarray(chunk), slot.index,
                      jnp.asarray(start, jnp.int32), table, rec_row,
                      None if valid is None else jnp.asarray(valid, jnp.int32))
        with TraceAnnotation("serve.prefill.dispatch"):
            old = self.cache
            logits, self.cache = admit(self.params, old, *inputs)
        self._took_cache(old)
        slot.prefill_pos += min(remaining, C)
        slot.length = slot.prefill_pos
        self.metrics.on_prefill_chunk(min(remaining, C))
        if slot.prefill_pos < plen:
            return True
        # prompt fully cached: sample the request's token 0 from the logits
        # at the REAL last prompt position of this chunk
        with TraceAnnotation("serve.prefill.first_token"):
            row = jnp.asarray(logits)[:, last_row]              # [1,V]
            tok = int(self._sample(
                row, jnp.asarray([slot.req_id], jnp.int32),
                jnp.asarray([0], jnp.int32))[0])
        self.table.activate(slot, tok)
        if self.paged and not self.has_rec:
            # publish the full prompt blocks so identical prompts admitted
            # later share them (first writer wins); recurrent archs never
            # share — see _try_admit_paged
            with TraceAnnotation("serve.prefill.publish"):
                keys = self.allocator.prefix_keys(slot.request.prompt)
                for i, key in enumerate(keys):
                    self.allocator.publish(slot.blocks[i], key)
        self.metrics.on_first_token(slot.req_id)
        self._complete_if_done(slot, tok)
        return True

    def _grow_decode_blocks(self) -> None:
        """Paged: every ACTIVE slot writes its pending token at position
        ``length`` this step — allocate the covering block when the write
        crosses into a new one, preempting the youngest request while the
        pool is dry (oldest slots grow first, so preemption pressure lands
        on the newest work).  Recurrent rows never grow: blocks are the
        only resource that can run out mid-decode."""
        bs = self.allocator.block_size
        for slot in sorted(self.table.active(), key=lambda s: s.admit_seq):
            if slot.state != ACTIVE:    # preempted by an earlier growth
                continue
            while slot.state == ACTIVE and slot.length // bs == \
                    len(slot.blocks):
                blk = self._alloc_block(slot)
                if blk is None:         # slot itself was the victim
                    break
                slot.blocks.append(blk)
        self._record_blocks()

    def _decode_tick(self) -> None:
        with TraceAnnotation("serve.decode.prepare"):
            if self.paged:
                self._grow_decode_blocks()
            if self.table.n_active == 0:
                return
            tokens, offsets, active, req_ids, tok_idx = \
                self.table.decode_inputs()
            bt = rows = act = None
            if self.paged:
                bt = self._put(jnp.asarray(self.table.block_tables()))
            if self.rec is not None:
                rows = self._put(jnp.asarray(self.table.rec_rows()))
            if self.rec is not None or self.moe:
                act = self._put(jnp.asarray(active))
            tok_in = self._put(jnp.asarray(tokens))
            off_in = self._put(jnp.asarray(offsets))
        with TraceAnnotation("serve.decode.dispatch"):
            old = self.cache
            logits, self.cache, *routed = self._decode(
                self.params, tok_in, old, off_in, bt, rows, act)
        self._took_cache(old)
        with TraceAnnotation("serve.decode.readback"):
            toks, *routed = jax.device_get((self._sample(
                logits[:, 0], self._put(jnp.asarray(req_ids)),
                self._put(jnp.asarray(tok_idx))), *routed))
            toks = np.asarray(toks)
        if routed:
            self.metrics.on_routed(routed[0])
        with TraceAnnotation("serve.decode.commit"):
            self.metrics.on_decode_step(int(active.sum()))
            for slot in self.table.active():
                tok = int(toks[slot.index])
                slot.length += 1      # pending token was cached this step
                slot.pending_token = tok
                slot.generated += 1
                slot.output.append(tok)
                self.metrics.on_token(slot.req_id)
                self._complete_if_done(slot, tok)

    def hold_admission(self, steps: int) -> None:
        """Stall admission for the next ``steps`` engine steps (fault
        injection: a hung scheduler / admission-control brown-out).  Live
        slots keep prefilling and decoding; only NEW admissions wait, so
        the backlog — and TTFT — grows until the hold clears.  Overlapping
        holds extend, not stack."""
        if steps < 0:
            raise ValueError(f"hold steps must be >= 0, got {steps}")
        self._admission_hold = max(self._admission_hold, steps)

    def step(self) -> None:
        """One engine iteration: admissions, a prefill tick, a decode step,
        and a clock tick (virtual mode — wall time passes on its own)."""
        with StepTraceAnnotation("serve.step", step_num=self._steps):
            self._steps += 1
            self._step_t0 = self.metrics.now()
            if self._admission_hold > 0:
                self._admission_hold -= 1
            else:
                self._admit_ready(self._step_t0)
            self._prefill_tick()
            self._decode_tick()
            self.metrics.on_queue_depth(len(self.queue))
            self.metrics.tick()

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> Dict[int, List[int]]:
        """Serve until the queue and every slot drain; returns outputs."""
        if requests:
            self.submit(list(requests))
        self.metrics.start()
        while len(self.queue) or self.table.busy():
            if not self.table.busy():
                nxt = self.queue.next_arrival()
                if nxt is not None:
                    # open-loop idle: the virtual clock jumps to the next
                    # arrival, the wall clock actually sleeps the gap
                    self.metrics.wait_until(nxt)
            self.step()
        self.metrics.stop()
        return self.results


# ---------------------------------------------------------------------------
# wave-at-a-time baseline (what PR 2 shipped) — the token-identity TEST
# ORACLE, and the measured baseline for benchmarks/serve_bench.py
# ---------------------------------------------------------------------------


def serve_waves(cfg: ArchConfig, params, ecfg: EngineConfig,
                requests: Sequence[Request]):
    """Admit ≤ max_slots requests per wave; decode until the wave drains.

    This is the engine's TEST ORACLE: it batch-prefills whole prompts in
    one call (no chunking, no padding masks, no slot reuse, no paging), so
    its per-request outputs are the ground truth the continuous engine —
    every backend mix, including recurrent and hybrid stacks — must match
    token for token (same fold-in sampling discipline).  It doubles as the
    measured baseline whose occupancy/throughput gap on ragged output
    lengths ``benchmarks/serve_bench.py`` quantifies: freed slots idle
    until the whole wave finishes.  Prompts within a wave must share one
    length (the wave loop batch-prefills).
    """
    _check_arch(cfg)
    S, max_len = ecfg.max_slots, ecfg.max_len
    metrics = ServeMetrics(max_slots=S, clock=ecfg.clock, step_s=ecfg.step_s)
    results: Dict[int, List[int]] = {}

    prefill = jax.jit(lambda p, t, c: T.prefill(p, cfg, t, c, None))
    decode = jax.jit(lambda p, t, c, o: T.decode_step(p, cfg, t, c, o))
    sample_j = jax.jit(_make_sampler(jax.random.key(ecfg.seed),
                                     ecfg.temperature))

    reqs = sorted(requests, key=lambda r: (r.arrival_s, r.req_id))
    for r in reqs:
        metrics.on_submit(r.req_id, r.arrival_s, len(r.prompt))
    metrics.start()
    for w0 in range(0, len(reqs), S):
        wave = reqs[w0:w0 + S]
        plens = {len(r.prompt) for r in wave}
        if len(plens) != 1:
            raise ValueError("wave baseline needs uniform prompt lengths "
                             f"within a wave, got {sorted(plens)}")
        P = plens.pop()
        # a wave starts only once its LAST member arrived — slots freed
        # mid-wave cannot admit (that is the baseline's pathology)
        wave_start = max(r.arrival_s for r in wave)
        metrics.wait_until(wave_start)
        B = len(wave)
        cache = T.init_cache(cfg, B, max_len)
        prompts = jnp.asarray([list(r.prompt) for r in wave], jnp.int32)
        req_ids = jnp.asarray([r.req_id for r in wave], jnp.int32)
        for r in wave:
            metrics.on_admit(r.req_id)
        logits, cache, offset = prefill(params, prompts, cache)
        metrics.on_prefill_chunk(B * P)
        metrics.tick()
        toks = np.asarray(sample_j(logits[:, -1], req_ids,
                                   jnp.zeros((B,), jnp.int32)))
        outs = [[int(t)] for t in toks]
        done = np.zeros((B,), bool)
        for i, r in enumerate(wave):
            metrics.on_first_token(r.req_id)
            if (ecfg.eos_id is not None and outs[i][0] == ecfg.eos_id) \
                    or r.max_new_tokens == 1:
                done[i] = True
                metrics.on_finish(r.req_id)
        gen = 1
        max_gen = max(r.max_new_tokens for r in wave)
        while not done.all() and gen < max_gen:
            tok_in = jnp.asarray(toks, jnp.int32)[:, None]
            logits, cache = decode(params, tok_in, cache,
                                   jnp.asarray(P + gen - 1, jnp.int32))
            toks = np.asarray(sample_j(
                logits[:, 0], req_ids, jnp.full((B,), gen, jnp.int32)))
            metrics.on_decode_step(int((~done).sum()))
            metrics.tick()
            for i, r in enumerate(wave):
                if done[i]:
                    continue       # slot idles until the wave drains
                outs[i].append(int(toks[i]))
                metrics.on_token(r.req_id)
                if (ecfg.eos_id is not None and outs[i][-1] == ecfg.eos_id) \
                        or len(outs[i]) >= r.max_new_tokens:
                    done[i] = True
                    metrics.on_finish(r.req_id)
            gen += 1
        for i, r in enumerate(wave):
            results[r.req_id] = outs[i]
            if not done[i]:
                metrics.on_finish(r.req_id)
    metrics.stop()
    return results, metrics

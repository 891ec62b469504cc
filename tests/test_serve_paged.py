"""Paged-KV backend correctness: token identity with the contiguous
backend, prefix sharing, copy-on-write, preemption, EOS threading.

The invariant everything rests on: with ``block_size | max_len`` the
gathered virtual KV view has the SAME shape and the SAME values as a
contiguous cache row, and prefix hits restart prefill on the chunk grid —
so the paged backend emits token-identical outputs, across ragged prompt
lengths whose chunk boundaries straddle block edges, and across a
preempt-and-requeue cycle.  (Raw logits may differ in the last mantissa
bit: XLA fuses the gather-fed and where-fed attention graphs differently;
the primitive-level tests pin tight numeric agreement + argmax equality,
and every engine-level test asserts exact token identity.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serve import (EngineConfig, Request, ServeEngine, serve_waves)
from repro.serve.blocks import SENTINEL
from repro.serve.slots import SlotTable
from test_serve_engine import _cut_at_eos, _eos_after_first_token

ARCH = "gemma2-2b-smoke"


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.key(0))


def _requests(cfg, lens, gens, seed=0, arrivals=None):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
               for n in lens]
    return [Request(req_id=i, prompt=p, max_new_tokens=g,
                    arrival_s=0.0 if arrivals is None else arrivals[i])
            for i, (p, g) in enumerate(zip(prompts, gens))]


def _paged(**kw):
    base = dict(max_slots=2, max_len=24, prefill_chunk=4, chunks_per_step=2,
                kv_mode="paged", block_size=4, kv_blocks=0)
    base.update(kw)
    return EngineConfig(**base)


def _contig(**kw):
    base = dict(max_slots=2, max_len=24, prefill_chunk=4, chunks_per_step=2)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# model-level primitives: paged ≡ contiguous, bit for bit
# ---------------------------------------------------------------------------


def test_paged_prefill_straddling_block_edges_matches_contiguous(
        cfg, params):
    """Chunked prefill (interior + right-aligned tail) through a block
    table must write the same logits and cache bits as the contiguous row
    — block_size 4 does NOT divide plen 10, so the tail chunk [6,10)
    straddles a block edge."""
    plen, C, bs, max_len = 10, 4, 4, 16
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=(1, plen)).astype(np.int32)
    chunks = [(0, prompt[:, 0:C]), (4, prompt[:, 4:8]),
              (plen - C, prompt[:, plen - C:plen])]

    ccache = T.init_cache(cfg, 1, max_len)
    for off, chunk in chunks:
        cl, ccache = T.prefill_chunk(params, cfg, jnp.asarray(chunk), ccache,
                                     jnp.asarray(off, jnp.int32))

    pcache = T.init_paged_cache(cfg, 8, bs)
    table = jnp.asarray([[2, 5, 1, SENTINEL]], jnp.int32)  # scrambled blocks
    for off, chunk in chunks:
        pl, pcache = T.prefill_chunk(params, cfg, jnp.asarray(chunk), pcache,
                                     jnp.asarray(off, jnp.int32),
                                     block_tables=table)
    cl, pl = np.asarray(cl), np.asarray(pl)
    np.testing.assert_allclose(pl, cl, rtol=2e-5, atol=2e-5)
    assert np.array_equal(cl.argmax(-1), pl.argmax(-1))

    # the gathered virtual view holds the same prompt content as the row
    for cleaf, pleaf in zip(jax.tree.leaves(ccache), jax.tree.leaves(pcache)):
        cleaf, pleaf = np.asarray(cleaf), np.asarray(pleaf)
        tbl = np.asarray(table[0])
        virt = pleaf[:, tbl].reshape(
            (pleaf.shape[0], len(tbl) * bs) + pleaf.shape[3:])
        np.testing.assert_allclose(
            virt[:, :plen].astype(np.float32),
            cleaf[:, 0, :plen].astype(np.float32), rtol=2e-5, atol=2e-5)


def test_paged_decode_matches_contiguous(cfg, params):
    """Vector-offset batched decode through block tables == contiguous —
    given the same chunk-prefill geometry on both sides (the engines
    always use matching chunk grids; that is the identity invariant).
    Tight numeric agreement + identical argmax per step."""
    B, P, bs, max_len = 3, 6, 4, 12
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size, size=(B, P)).astype(np.int32)
    chunk_offs = (0, P - 4)         # chunk [2,6) straddles the block edge
    ccache = T.init_cache(cfg, B, max_len)
    for b in range(B):
        sub = T.take_slot(ccache, b)
        for off2 in chunk_offs:
            chunk = prompts[b:b + 1, off2:off2 + 4]
            _, sub = T.prefill_chunk(params, cfg, jnp.asarray(chunk), sub,
                                     jnp.asarray(off2, jnp.int32))
        ccache = T.write_slot(ccache, sub, b)
    pcache = T.init_paged_cache(cfg, 12, bs)
    tables = np.asarray([[1, 4, 7], [2, 5, 8], [3, 6, 9]], np.int32)
    for b in range(B):
        for off2 in chunk_offs:
            chunk = prompts[b:b + 1, off2:off2 + 4]
            _, pcache = T.prefill_chunk(
                params, cfg, jnp.asarray(chunk), pcache,
                jnp.asarray(off2, jnp.int32),
                block_tables=jnp.asarray(tables[b:b + 1]))
    tok = rng.integers(0, cfg.vocab_size, size=(B, 1)).astype(np.int32)
    offs = np.full((B,), P, np.int32)
    for _ in range(3):
        cl, ccache = T.decode_step(params, cfg, jnp.asarray(tok), ccache,
                                   jnp.asarray(offs))
        pl, pcache = T.decode_step(params, cfg, jnp.asarray(tok), pcache,
                                   jnp.asarray(offs),
                                   block_tables=jnp.asarray(tables))
        cl, pl = np.asarray(cl), np.asarray(pl)
        np.testing.assert_allclose(pl, cl, rtol=2e-5, atol=2e-5)
        assert np.array_equal(cl.argmax(-1), pl.argmax(-1))
        tok = cl[:, 0].argmax(-1).astype(np.int32)[:, None]
        offs = offs + 1


def test_copy_block_copies_one_block_only(cfg):
    cache = T.init_paged_cache(cfg, 6, 4)
    cache = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape), cache)
    out = T.copy_block(cache, jnp.asarray(2, jnp.int32),
                       jnp.asarray(4, jnp.int32))
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(b[:, 4], a[:, 2])
        keep = [i for i in range(6) if i != 4]
        assert np.array_equal(b[:, keep], a[:, keep])


def test_init_paged_cache_rejects_recurrent_arch():
    with pytest.raises(ValueError, match="recurrent"):
        T.init_paged_cache(get_config("xlstm-1.3b-smoke"), 8, 4)


# ---------------------------------------------------------------------------
# engine level: token identity across backends
# ---------------------------------------------------------------------------


def test_paged_engine_token_identical_ragged(cfg, params):
    """Ragged prompt lengths (block_size divides none of them) + sampled
    temperature: the paged engine must reproduce the contiguous engine's
    outputs exactly."""
    lens, gens = [5, 9, 13, 7, 10, 3], [4, 6, 2, 5, 7, 3]
    kw = dict(max_slots=3, temperature=0.7, seed=3)
    a = ServeEngine(cfg, params, _contig(**kw)).run(_requests(cfg, lens, gens))
    eng = ServeEngine(cfg, params, _paged(**kw))
    b = eng.run(_requests(cfg, lens, gens))
    assert a == b
    eng.allocator.assert_consistent()
    assert eng.allocator.num_used == 0      # every table was freed


def test_paged_default_pool_matches_contiguous_capacity(cfg, params):
    eng = ServeEngine(cfg, params, _paged())    # kv_blocks=0 → auto
    assert eng.allocator.capacity == 2 * (24 // 4)


def test_paged_rejects_block_size_not_dividing_max_len(cfg, params):
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(cfg, params, _paged(max_len=22))


def test_paged_rejects_request_larger_than_pool(cfg, params):
    eng = ServeEngine(cfg, params, _paged(kv_blocks=4))   # 3 usable blocks
    with pytest.raises(ValueError, match="worst case"):
        eng.submit(_requests(cfg, [10], [8]))
    assert eng.metrics.requests == {} and len(eng.queue) == 0


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------


def test_prefix_sharing_hits_and_outputs_identical(cfg, params):
    """Identical prompts admitted over time share published blocks (the
    gauge shows hits) without perturbing outputs."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=(12,)).tolist()
    mk = lambda: [Request(req_id=i, prompt=list(prompt), max_new_tokens=4)  # noqa: E731
                  for i in range(6)]
    eng = ServeEngine(cfg, params, _paged(kv_blocks=40))
    out = eng.run(mk())
    assert eng.metrics.prefix_hit_tokens > 0
    assert 0 < eng.metrics.prefix_hit_rate < 1
    eng.allocator.assert_consistent()
    cont = ServeEngine(cfg, params, _contig()).run(mk())
    assert out == cont


def test_cow_on_prefix_hit_tail_rewrite(cfg, params):
    """plen 12, chunk 4, block 4: a full-block prefix hit restarts prefill
    at the grid point 8, and the right-aligned tail [8,12) rewrites the
    hit's last shared block — which must be copy-on-written, leaving the
    original's bits (and the first request's recorded output) intact."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(12,)).tolist()
    mk = lambda: [Request(req_id=i, prompt=list(prompt),  # noqa: E731
                          max_new_tokens=6) for i in range(2)]
    # one slot: strictly sequential, so request 1 hits request 0's blocks
    eng = ServeEngine(cfg, params, _paged(max_slots=1, kv_blocks=40))
    out = eng.run(mk())
    assert eng.metrics.prefix_hit_tokens == 8       # pos0 = 8 of plen 12
    eng.allocator.assert_consistent()
    cont = ServeEngine(cfg, params, _contig(max_slots=1)).run(mk())
    assert out == cont


# ---------------------------------------------------------------------------
# preemption: pool runs dry mid-decode → youngest requeued, outputs intact
# ---------------------------------------------------------------------------


def test_preempt_and_requeue_token_identical(cfg, params):
    """A pool too small for three growing requests must preempt (youngest
    first), requeue, and still emit exactly the contiguous outputs."""
    lens, gens = [8, 8, 8], [12, 10, 8]
    kw = dict(max_slots=3, max_len=32, temperature=0.6, seed=9)
    eng = ServeEngine(cfg, params, _paged(kv_blocks=11, **kw))  # 10 usable
    out = eng.run(_requests(cfg, lens, gens, seed=5))
    assert eng.metrics.preemptions >= 1
    ref = ServeEngine(cfg, params, _contig(**kw)).run(
        _requests(cfg, lens, gens, seed=5))
    assert out == ref
    eng.allocator.assert_consistent()
    assert eng.allocator.num_used == 0
    s = eng.metrics.summary()
    assert s["completed"] == 3 and s["preemptions"] == eng.metrics.preemptions
    # the discarded decode work is booked EXACTLY: every decode-step token
    # either reached a surviving output (tokens_out minus the prefill-born
    # first tokens) or landed in wasted_decode_tokens
    assert s["wasted_decode_tokens"] > 0
    assert eng.metrics.decode_tokens == \
        (s["tokens_out"] - s["first_tokens"]) + s["wasted_decode_tokens"]


def test_preempt_resets_request_record(cfg, params):
    """After a preempt-requeue cycle every request still reports exactly
    its budgeted tokens (the re-serve must not double-count)."""
    lens, gens = [8, 8, 8], [12, 10, 8]
    eng = ServeEngine(cfg, params,
                      _paged(max_slots=3, max_len=32, kv_blocks=11))
    out = eng.run(_requests(cfg, lens, gens, seed=5))
    assert eng.metrics.preemptions >= 1
    for i, g in enumerate(gens):
        assert len(out[i]) == g
        assert eng.metrics.requests[i].tokens_out == g
    # wasted accounting survives the reset: per-request tokens_out restart
    # at zero on preemption, but the decode-step tally keeps every token
    s = eng.metrics.summary()
    assert eng.metrics.decode_tokens == \
        (s["tokens_out"] - s["first_tokens"]) + s["wasted_decode_tokens"]
    assert s["wasted_decode_tokens"] > 0


# ---------------------------------------------------------------------------
# EOS threading: wave / continuous / paged terminate identically
# ---------------------------------------------------------------------------


def test_eos_consistent_across_modes(cfg, params):
    """--eos-id must cut generation at the same token in every serving
    mode (wave baseline, continuous contiguous, continuous paged)."""
    lens, gens = [6] * 3, [8] * 3
    probe = ServeEngine(cfg, params, _contig()).run(
        _requests(cfg, lens, gens, seed=5))
    eos = _eos_after_first_token(probe)
    kw = dict(eos_id=eos)
    cont = ServeEngine(cfg, params, _contig(**kw)).run(
        _requests(cfg, lens, gens, seed=5))
    paged = ServeEngine(cfg, params, _paged(**kw)).run(
        _requests(cfg, lens, gens, seed=5))
    wave, _ = serve_waves(cfg, params, _contig(**kw),
                          _requests(cfg, lens, gens, seed=5))
    assert cont == paged == wave
    assert cont == _cut_at_eos(probe, eos)
    assert any(len(cont[i]) < len(probe[i]) for i in probe)


# ---------------------------------------------------------------------------
# host plumbing: paged slot table + gauges
# ---------------------------------------------------------------------------


def test_paged_slot_table_block_tables_padding():
    table = SlotTable(max_slots=3, max_len=16, block_size=4)
    assert table.n_max == 4
    s0 = table.slots[0]
    table.assign(s0, Request(req_id=1, prompt=[1, 2, 3], max_new_tokens=2))
    s0.blocks = [5, 7]
    bt = table.block_tables()
    assert bt.shape == (3, 4)
    assert bt[0].tolist() == [5, 7, SENTINEL, SENTINEL]
    assert (bt[1:] == SENTINEL).all()
    row = table.block_table_row(s0)
    assert row.shape == (1, 4) and row[0].tolist() == [5, 7, 0, 0]
    # masked rows write to the virtual sentinel position
    _, offsets, active, _, _ = table.decode_inputs()
    assert offsets[1] == offsets[2] == 15
    assert not active.any()


def test_release_with_live_blocks_raises():
    table = SlotTable(max_slots=1, max_len=16, block_size=4)
    s0 = table.slots[0]
    table.assign(s0, Request(req_id=1, prompt=[1, 2], max_new_tokens=2))
    s0.blocks = [3]
    with pytest.raises(RuntimeError, match="live"):
        table.release(s0)
    s0.blocks = []
    table.release(s0)


def test_paged_metrics_gauges_in_report(cfg, params):
    eng = ServeEngine(cfg, params, _paged(kv_blocks=20))
    eng.run(_requests(cfg, [6, 9], [3, 4], seed=6))
    s = eng.metrics.summary()
    assert s["blocks_total"] == 19
    assert s["blocks_peak"] > 0
    assert s["blocks_in_use"] == 0          # drained
    assert s["peak_active"] >= 1
    assert "paged" in eng.metrics.report()
    # the contiguous engine never shows the paged line
    cont = ServeEngine(cfg, params, _contig())
    cont.run(_requests(cfg, [6], [2], seed=6))
    assert "paged" not in cont.metrics.report()


# ---------------------------------------------------------------------------
# ragged multi-token paged writes (vector offset, T > 1)
# ---------------------------------------------------------------------------


def test_paged_scatter_ragged_vector_offsets_multi_token():
    """A [B] offset vector with T > 1 writes each row's span at its own
    start — identical to per-row scalar scatters, with out-of-span tail
    positions redirected to the sentinel block."""
    from repro.models.layers import paged_scatter
    B, T, n, bs, N = 3, 3, 2, 4, 8
    rng = np.random.default_rng(4)
    pool = jnp.zeros((N, bs, 2), jnp.float32)
    new = jnp.asarray(rng.normal(size=(B, T, 2)), dtype=jnp.float32)
    tables = jnp.asarray([[1, 4], [2, 5], [3, 6]], jnp.int32)
    offs = np.asarray([0, 3, 6], np.int32)   # row 1 straddles a block edge,
                                             # row 2 runs past the span
    ragged = paged_scatter(pool, new, tables, jnp.asarray(offs))
    oracle = pool
    for b in range(B):
        oracle = paged_scatter(oracle, new[b:b + 1], tables[b:b + 1],
                               jnp.asarray(offs[b]))
    assert np.array_equal(np.asarray(ragged), np.asarray(oracle))
    # in-span values landed at their virtual positions...
    from repro.models.layers import paged_gather
    view = np.asarray(paged_gather(ragged, tables))
    for b in range(B):
        for t in range(T):
            p = offs[b] + t
            if p < n * bs:
                assert np.array_equal(view[b, p], np.asarray(new[b, t]))
    # ...and row 2's overflow (positions 8) hit only the sentinel block
    untouched = [i for i in range(1, N) if i not in (3, 6)
                 and i not in (1, 4, 2, 5)]
    assert np.asarray(ragged)[untouched].sum() == 0


# ---------------------------------------------------------------------------
# fused paged-attention decode kernel: token identity with the ref lowering
# ---------------------------------------------------------------------------


def test_paged_engine_fused_kernel_token_identical(cfg, params):
    """paged_kernel="pallas" (fused block-table decode kernel, interpret
    mode on CPU) must emit exactly the tokens of paged_kernel="ref" (the
    gather-then-attend oracle) — ragged lengths, sampled temperature."""
    lens, gens = [5, 9, 13, 7], [4, 6, 2, 5]
    kw = dict(max_slots=3, temperature=0.7, seed=3)
    a = ServeEngine(cfg, params, _paged(paged_kernel="ref", **kw)).run(
        _requests(cfg, lens, gens))
    b = ServeEngine(cfg, params, _paged(paged_kernel="pallas", **kw)).run(
        _requests(cfg, lens, gens))
    assert a == b


def test_paged_engine_fused_kernel_token_identical_mla():
    """Same invariant through the MLA absorbed-decode kernel (latent
    pools, fused q_eff/W_uv absorption)."""
    mcfg = get_config("deepseek-v3-671b-smoke")
    mparams = T.init_params(mcfg, jax.random.key(0))
    lens, gens = [5, 9, 6], [3, 4, 3]
    a = ServeEngine(mcfg, mparams, _paged(paged_kernel="ref")).run(
        _requests(mcfg, lens, gens))
    b = ServeEngine(mcfg, mparams, _paged(paged_kernel="pallas")).run(
        _requests(mcfg, lens, gens))
    assert a == b


def test_paged_kernel_auto_resolves_ref_off_tpu(cfg, params):
    import jax as _jax
    eng = ServeEngine(cfg, params, _paged())          # paged_kernel="auto"
    if _jax.default_backend() != "tpu":
        assert eng.paged_kernel == "ref"
    else:
        assert eng.paged_kernel in ("pallas", "ref")


def test_paged_kernel_rejects_unknown(cfg, params):
    with pytest.raises(ValueError, match="paged_kernel"):
        ServeEngine(cfg, params, _paged(paged_kernel="cuda"))

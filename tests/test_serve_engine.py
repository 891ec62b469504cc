"""Continuous-batching engine correctness.

The three properties slot reuse stands on:

  * **prefill + N decode ≡ full forward** for ragged prompt lengths served
    from one batched cache with per-slot (vector) offsets;
  * **slot isolation**: resetting / re-admitting one slot leaves every
    other slot's logits BIT-identical (same-shape batched calls, rows are
    independent);
  * **RNG discipline**: token t of request r is sampled with
    ``fold_in(fold_in(seed_key, r), t)`` — deterministic per request,
    independent of admission order; the wave-era first-token-from-unsplit-
    key bug stays fixed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serve import (EngineConfig, Request, ServeEngine, serve_waves)

ARCH = "gemma2-2b-smoke"


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.key(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(n,)).tolist() for n in lens]


def _requests(cfg, lens, gens, seed=0, arrivals=None):
    prompts = _prompts(cfg, lens, seed)
    return [Request(req_id=i, prompt=p, max_new_tokens=g,
                    arrival_s=0.0 if arrivals is None else arrivals[i])
            for i, (p, g) in enumerate(zip(prompts, gens))]


def _eos_after_first_token(outputs):
    """A token that first occurs at index >= 1 of some request's output:
    as EOS it must cut that request after a real decode step."""
    for out in outputs.values():
        for i in range(1, len(out)):
            if out[i] not in out[:i]:
                return out[i]
    raise AssertionError(f"every output repeats its first token: {outputs}")


def _cut_at_eos(outputs, eos):
    """Each output as an EOS-terminated run produces it: through its first
    ``eos``, whole when ``eos`` never occurs."""
    return {r: out[:out.index(eos) + 1] if eos in out else out
            for r, out in outputs.items()}


# ---------------------------------------------------------------------------
# architecture gating
# ---------------------------------------------------------------------------


def test_engine_serves_recurrent_arch():
    """Recurrent archs serve on the CONTINUOUS path (the SlotState row
    backend) — the historical ValueError is gone.  Full wave-vs-continuous
    token-identity coverage lives in test_serve_slot_state.py."""
    xcfg = get_config("xlstm-1.3b-smoke")
    xparams = T.init_params(xcfg, jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, max_len=16, prefill_chunk=4)
    eng = ServeEngine(xcfg, xparams, ecfg)
    assert eng.plan.has_recurrent and not eng.plan.has_kv
    out = eng.run(_requests(xcfg, [4, 4], [3, 2]))
    assert sorted(out) == [0, 1]
    assert [len(out[0]), len(out[1])] == [3, 2]
    assert eng.metrics.summary()["completed"] == 2


def test_engine_rejects_frontend_arch():
    with pytest.raises(ValueError, match="frontend"):
        ServeEngine(get_config("paligemma-3b-smoke"), None, EngineConfig())


def test_wave_baseline_still_serves_recurrent_arch():
    """The wave loop batch-prefills without chunk padding, keeping
    recurrent caches exact by construction — it is the token-identity
    oracle the continuous recurrent path is checked against."""
    xcfg = get_config("xlstm-1.3b-smoke")
    xparams = T.init_params(xcfg, jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, max_len=16)
    out, m = serve_waves(xcfg, xparams, ecfg,
                         _requests(xcfg, [4, 4], [3, 2]))
    assert sorted(out) == [0, 1]
    assert [len(out[0]), len(out[1])] == [3, 2]
    assert m.summary()["completed"] == 2


def test_prefill_chunk_rejects_blocked_attention_lengths(cfg, params):
    """Offset prefill must stay below the blocked-attention threshold whose
    static key extents assume positions start at 0."""
    from repro.models.layers import QUERY_CHUNK_THRESHOLD
    Tlen = QUERY_CHUNK_THRESHOLD
    cache = T.init_cache(cfg, 1, Tlen + 8)
    tokens = jnp.zeros((1, Tlen), jnp.int32)
    with pytest.raises(ValueError, match="blocked-attention"):
        T.prefill_chunk(params, cfg, tokens, cache,
                        jnp.asarray(0, jnp.int32))


def test_engine_rejects_oversize_request(cfg, params):
    eng = ServeEngine(cfg, params, EngineConfig(max_slots=1, max_len=8))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(_requests(cfg, [6], [4]))


def test_engine_submit_validates_whole_batch_first(cfg, params):
    """A bad request in a batch must not leave phantom metrics records or
    queued batchmates behind."""
    eng = ServeEngine(cfg, params, EngineConfig(max_slots=1, max_len=8))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(_requests(cfg, [3, 6], [4, 4]))   # second is oversize
    assert eng.metrics.requests == {}
    assert len(eng.queue) == 0


# ---------------------------------------------------------------------------
# prefill + decode ≡ forward, over ragged prompt lengths (vector offsets)
# ---------------------------------------------------------------------------


def test_prefill_then_decode_matches_forward_ragged(cfg, params):
    """Three slots at prompt lengths 5/9/12 share one batched cache; each
    is chunk-prefilled (C=4 exercises interior + right-aligned tail
    chunks), then all decode IN ONE CALL with per-slot vector offsets.
    Every step's logits must match the slot's own full-sequence forward."""
    lens, total, C, max_len = [5, 9, 12], 16, 4, 24
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, cfg.vocab_size, size=(total,)).astype(np.int32)
            for _ in lens]
    full = [np.asarray(T.forward(params, cfg, jnp.asarray(s)[None]))
            for s in seqs]

    cache = T.init_cache(cfg, len(lens), max_len)
    for i, L in enumerate(lens):
        sub = T.take_slot(cache, i)
        start = 0
        while start < L:
            if L <= C:
                chunk, off = np.zeros((1, C), np.int32), 0
                chunk[0, :L] = seqs[i][:L]
                start = L
            elif L - start > C:
                chunk, off = seqs[i][None, start:start + C], start
                start += C
            else:                       # right-aligned tail
                chunk, off = seqs[i][None, L - C:L], L - C
                start = L
            _, sub = T.prefill_chunk(params, cfg, jnp.asarray(chunk), sub,
                                     jnp.asarray(off, jnp.int32))
        cache = T.write_slot(cache, sub, i)

    offsets = np.asarray(lens, np.int32)
    got, want = [], []
    while (offsets < total).any():
        # feed each slot ITS OWN next token; finished slots re-feed their
        # last token at a frozen offset (masked by comparison selection)
        tok = np.asarray([seqs[i][min(offsets[i], total - 1)]
                          for i in range(len(lens))], np.int32)[:, None]
        logits, cache = T.decode_step(params, cfg, jnp.asarray(tok), cache,
                                      jnp.asarray(offsets))
        for i in range(len(lens)):
            if offsets[i] < total:
                got.append(np.asarray(logits[i, 0]))
                want.append(full[i][0, offsets[i]])
        offsets = np.minimum(offsets + 1, total)
    got, want = np.stack(got), np.stack(want)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got, want, atol=2e-3 * scale, rtol=2e-2)


def test_chunked_prefill_matches_full_prefill(cfg, params):
    """Chunked (interior + right-aligned tail) admission == one-shot
    prefill: same cache contents, same last-position logits."""
    L, C, max_len = 10, 4, 16
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, size=(1, L)).astype(np.int32)

    ref_cache = T.init_cache(cfg, 1, max_len)
    ref_logits, ref_cache, _ = T.prefill(params, cfg, jnp.asarray(prompt),
                                         ref_cache, None)

    cache = T.init_cache(cfg, 1, max_len)
    for off in (0, 4, L - C):           # 0..3, 4..7, right-aligned 6..9
        chunk = prompt[:, off:off + C]
        logits, cache = T.prefill_chunk(params, cfg, jnp.asarray(chunk),
                                        cache, jnp.asarray(off, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(ref_logits[:, -1]),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        # positions [0, L) hold the prompt in both (beyond L is scratch)
        np.testing.assert_allclose(np.asarray(a)[:, :, :L],
                                   np.asarray(b)[:, :, :L],
                                   rtol=2e-5, atol=2e-5)


def test_vector_offset_matches_scalar_offset(cfg, params):
    """A uniform offset vector must reproduce the scalar-offset decode
    bit-for-bit (same shapes, same math)."""
    B, P, max_len = 3, 6, 12
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab_size, size=(B, P)).astype(np.int32)
    cache = T.init_cache(cfg, B, max_len)
    _, cache, off = T.prefill(params, cfg, jnp.asarray(prompts), cache, None)
    tok = rng.integers(0, cfg.vocab_size, size=(B, 1)).astype(np.int32)
    l_scalar, c_scalar = T.decode_step(params, cfg, jnp.asarray(tok), cache,
                                       off)
    l_vec, c_vec = T.decode_step(params, cfg, jnp.asarray(tok), cache,
                                 jnp.full((B,), int(off), jnp.int32))
    assert np.array_equal(np.asarray(l_scalar), np.asarray(l_vec))
    for a, b in zip(jax.tree.leaves(c_scalar), jax.tree.leaves(c_vec)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# per-slot cache surgery: isolation is bit-exact
# ---------------------------------------------------------------------------


def test_reset_slot_zeroes_only_that_slot(cfg):
    cache = T.init_cache(cfg, 3, 8)
    cache = jax.tree.map(lambda x: jnp.ones_like(x), cache)
    cache = T.reset_slot(cache, 1)
    for leaf in jax.tree.leaves(cache):
        x = np.asarray(leaf)
        assert (x[:, 1] == 0).all()
        assert (x[:, 0] == 1).all() and (x[:, 2] == 1).all()


def test_take_write_slot_roundtrip(cfg):
    cache = T.init_cache(cfg, 3, 8)
    cache = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape), cache)
    back = T.write_slot(cache, T.take_slot(cache, 2), 2)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_slot_reset_leaves_other_slots_logits_bit_identical(cfg, params):
    """THE slot-reuse correctness property: run 4 slots for a few decode
    steps; in a parallel universe slot 2 is reset and re-admitted with a
    different request.  Slots 0/1/3 must produce BIT-identical logits in
    both universes."""
    S, P, max_len, steps = 4, 6, 20, 4
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, cfg.vocab_size, size=(S, P)).astype(np.int32)
    cache = T.init_cache(cfg, S, max_len)
    logits0, cache, off = T.prefill(params, cfg, jnp.asarray(prompts),
                                    cache, None)
    tok0 = np.asarray(jnp.argmax(logits0[:, -1], -1), np.int32)

    def decode_run(cache, first_tok, offsets):
        outs, tok = [], np.asarray(first_tok, np.int32)[:, None]
        offs = np.asarray(offsets, np.int32)
        for _ in range(steps):
            logits, cache = T.decode_step(params, cfg, jnp.asarray(tok),
                                          cache, jnp.asarray(offs))
            outs.append(np.asarray(logits[:, 0]))
            tok = np.asarray(jnp.argmax(logits[:, 0], -1), np.int32)[:, None]
            offs = offs + 1
        return np.stack(outs)

    # universe A: all four slots keep decoding their original requests
    outs_a = decode_run(cache, tok0, [P] * S)

    # universe B: slot 2 is reset and re-admitted with a NEW prompt (len 3,
    # chunk-prefilled), then everyone decodes together at ragged offsets
    new_prompt = rng.integers(0, cfg.vocab_size, size=(1, 3)).astype(np.int32)
    cache_b = T.reset_slot(cache, 2)
    sub = T.take_slot(cache_b, 2)
    nl, sub = T.prefill_chunk(params, cfg, jnp.asarray(new_prompt), sub,
                              jnp.asarray(0, jnp.int32))
    cache_b = T.write_slot(cache_b, sub, 2)
    tok_b = tok0.copy()
    tok_b[2] = int(jnp.argmax(nl[0, new_prompt.shape[1] - 1]))
    outs_b = decode_run(cache_b, tok_b, [P, P, 3, P])

    keep = [0, 1, 3]
    assert np.array_equal(outs_a[:, keep], outs_b[:, keep]), (
        "resetting slot 2 perturbed other slots' logits")
    # and slot 2 itself genuinely changed (the reset did something)
    assert not np.array_equal(outs_a[:, 2], outs_b[:, 2])


# ---------------------------------------------------------------------------
# engine end-to-end: budgets, EOS slot reuse, metrics accounting
# ---------------------------------------------------------------------------


def _ecfg(**kw):
    base = dict(max_slots=2, max_len=24, prefill_chunk=4, chunks_per_step=2)
    base.update(kw)
    return EngineConfig(**base)


def test_engine_serves_more_requests_than_slots(cfg, params):
    gens = [3, 5, 2, 4, 6, 1]
    reqs = _requests(cfg, [5, 7, 3, 6, 4, 5], gens)
    eng = ServeEngine(cfg, params, _ecfg())
    out = eng.run(reqs)
    assert sorted(out) == list(range(6))
    for i, g in enumerate(gens):
        assert len(out[i]) == g, f"request {i} budget {g}, got {len(out[i])}"
    s = eng.metrics.summary()
    assert s["completed"] == 6
    assert s["tokens_out"] == sum(gens)


def test_engine_eos_frees_slot_and_output_ends_at_eos(cfg, params):
    reqs = _requests(cfg, [6, 6, 6], [8, 8, 8], seed=5)
    eng = ServeEngine(cfg, params, _ecfg())
    out = eng.run(reqs)
    eos = _eos_after_first_token(out)
    reqs2 = _requests(cfg, [6, 6, 6], [8, 8, 8], seed=5)
    eng2 = ServeEngine(cfg, params, _ecfg(eos_id=eos))
    out2 = eng2.run(reqs2)
    assert out2 == _cut_at_eos(out, eos)
    assert any(len(out2[i]) < len(out[i]) for i in out)


def test_engine_metrics_account_every_token(cfg, params):
    lens, gens, C = [5, 9, 4, 7], [4, 2, 5, 3], 4
    reqs = _requests(cfg, lens, gens)
    eng = ServeEngine(cfg, params, _ecfg(prefill_chunk=C))
    out = eng.run(reqs)
    s = eng.metrics.summary()
    assert s["tokens_out"] == sum(len(v) for v in out.values()) == sum(gens)
    assert s["prefill_tokens"] == sum(lens)
    assert s["prefill_chunks"] == sum(-(-n // C) for n in lens)
    assert 0 < s["occupancy"] <= 1
    assert len(eng.metrics.ttfts()) == len(reqs)


def test_engine_continuous_beats_wave_on_ragged_budgets(cfg, params):
    lens = [6] * 10
    gens = [2, 12, 3, 11, 2, 10, 4, 12, 2, 9]    # heavy raggedness
    ecfg = _ecfg(max_slots=2, prefill_chunk=6)
    eng = ServeEngine(cfg, params, ecfg)
    cont_out = eng.run(_requests(cfg, lens, gens))
    wave_out, wave_m = serve_waves(cfg, params, ecfg,
                                   _requests(cfg, lens, gens))
    assert cont_out == wave_out
    assert eng.metrics.occupancy > wave_m.occupancy
    assert eng.metrics.decode_steps < wave_m.decode_steps


# ---------------------------------------------------------------------------
# RNG discipline: fold_in(fold_in(key, req), token) — deterministic serving
# ---------------------------------------------------------------------------


def test_first_token_follows_fold_in_discipline(cfg, params):
    """Regression for the wave-era bug (first token sampled from the
    UNSPLIT top-level key): the engine's first token for request r must be
    exactly categorical(fold_in(fold_in(key(seed), r), 0), logits/T)."""
    temp, seed = 0.8, 11
    reqs = _requests(cfg, [6], [1], seed=6)
    eng = ServeEngine(cfg, params,
                      _ecfg(max_slots=1, temperature=temp, seed=seed,
                            prefill_chunk=6))
    out = eng.run(reqs)

    cache = T.init_cache(cfg, 1, 24)
    logits, _, _ = T.prefill(
        params, cfg, jnp.asarray([reqs[0].prompt], jnp.int32), cache, None)
    k = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 0), 0)
    want = int(jax.random.categorical(k, logits[0, -1] / temp))
    assert out[0] == [want]


def test_same_seed_same_tokens(cfg, params):
    reqs = lambda: _requests(cfg, [5, 8, 6], [6, 4, 7], seed=7)  # noqa: E731
    a = ServeEngine(cfg, params, _ecfg(temperature=0.7, seed=3)).run(reqs())
    b = ServeEngine(cfg, params, _ecfg(temperature=0.7, seed=3)).run(reqs())
    assert a == b


def test_different_seed_different_tokens(cfg, params):
    reqs = lambda: _requests(cfg, [5, 8, 6], [8, 8, 8], seed=7)  # noqa: E731
    a = ServeEngine(cfg, params, _ecfg(temperature=0.9, seed=3)).run(reqs())
    b = ServeEngine(cfg, params, _ecfg(temperature=0.9, seed=4)).run(reqs())
    assert a != b


def test_sampling_independent_of_admission_order(cfg, params):
    """Same pool size, different arrival pattern → slot assignment and
    admission interleaving differ, but per-request tokens must not."""
    lens, gens = [5, 6, 7, 4], [5, 3, 6, 4]
    a = ServeEngine(cfg, params, _ecfg(temperature=0.7)).run(
        _requests(cfg, lens, gens, seed=8))
    staggered = _requests(cfg, lens, gens, seed=8,
                          arrivals=[0.0, 0.0, 0.05, 0.1])
    b = ServeEngine(cfg, params, _ecfg(temperature=0.7)).run(staggered)
    assert a == b


def test_wave_and_continuous_token_identical_greedy(cfg, params):
    lens, gens = [6] * 5, [3, 6, 2, 5, 4]
    ecfg = _ecfg(max_slots=2, prefill_chunk=6)
    cont = ServeEngine(cfg, params, ecfg).run(_requests(cfg, lens, gens))
    wave, _ = serve_waves(cfg, params, ecfg, _requests(cfg, lens, gens))
    assert cont == wave


# ---------------------------------------------------------------------------
# prefill wait: TTFT = queue wait + prefill wait + the request's own chunks
# ---------------------------------------------------------------------------


def _ttft_parts_hold(eng, lens):
    """On the step clock, with one chunk a step, every step from a
    request's admission to its first token either runs one of its chunks
    or passes it over; the first token comes in its last chunk's step."""
    C, step = eng.ecfg.prefill_chunk, eng.ecfg.step_s
    for i, n in enumerate(lens):
        r = eng.metrics.requests[i]
        own = -(-n // C)
        assert r.ttft_s == pytest.approx(
            (r.admitted_s - r.arrival_s) + r.prefill_wait_s
            + (own - 1) * step), i


@pytest.mark.parametrize("kv_mode", ["contiguous", "paged"])
def test_prefill_wait_is_the_neighbours_chunks(cfg, params, kv_mode):
    """Two requests admitted in the same step, one chunk a step: the
    later slot waits exactly its neighbour's chunk count times
    ``step_s``; the earlier one never waits."""
    lens = [12, 8]                                   # 3 and 2 chunks of 4
    eng = ServeEngine(cfg, params, _ecfg(chunks_per_step=1, kv_mode=kv_mode,
                                         block_size=4))
    eng.run(_requests(cfg, lens, [2, 2]))
    recs = eng.metrics.requests
    assert recs[0].admitted_s == recs[1].admitted_s == 0.0
    assert recs[0].prefill_wait_s == 0.0
    assert recs[1].prefill_wait_s == pytest.approx(3 * eng.ecfg.step_s)
    _ttft_parts_hold(eng, lens)


def test_ttft_splits_into_queue_wait_prefill_wait_and_own_chunks(cfg,
                                                                 params):
    lens, gens = [12, 8, 5, 16, 9], [3, 2, 4, 2, 3]
    reqs = _requests(cfg, lens, gens, seed=2,
                     arrivals=[0.0, 0.0, 0.01, 0.02, 0.05])
    eng = ServeEngine(cfg, params, _ecfg(chunks_per_step=1))
    eng.run(reqs)
    assert any(r.prefill_wait_s > 0 for r in eng.metrics.requests.values())
    assert any(r.admitted_s > r.arrival_s
               for r in eng.metrics.requests.values())
    _ttft_parts_hold(eng, lens)


def test_preemption_resets_prefill_wait():
    from repro.serve.metrics import ServeMetrics
    m = ServeMetrics(clock="step", step_s=0.5)
    m.on_submit(7, 0.0, 10)
    m.on_admit(7)
    m.on_prefill_passed_over([7], m.now())
    m.tick()
    assert m.requests[7].prefill_wait_s == 0.5
    m.on_preempt(7)
    assert m.requests[7].prefill_wait_s == 0.0
    # passed over, then preempted later in the same step: the step that
    # ends after the preemption is not booked to the requeued request
    m.on_admit(7)
    m.on_prefill_passed_over([7], m.now())
    m.on_preempt(7)
    m.tick()
    assert m.requests[7].prefill_wait_s == 0.0

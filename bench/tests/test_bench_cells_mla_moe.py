"""The DeepSeek-V3 serving cell's driver on the CPU at a tiny size: a sound
run is correct; the control (the reference in fp8, the next precision
below the configuration's bfloat16, put in the program's place) is not,
and neither is a run whose produced tokens are altered.  The harness's
look for a chip is skipped; everything after it runs as in a benchmark
run (weights from the seed, set-up, window, reference)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench.harness import model as M  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.tests import cells_tiny as CT  # noqa: E402

SEED = 2**31 + 7

# every width cut, the shape kept: 1 dense layer then 2 MoE layers, a
# group-limited router over 16 experts in 4 groups (2 searched, 4 chosen)
# of which 4 are held here, from the 5th on
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, router_experts=16, n_routed_experts=4,
            experts_held_from=4, n_group=4, topk_group=2,
            num_experts_per_tok=4, num_hidden_layers=3,
            first_k_dense_replace=1, vocab_size=512)
# tiny readings on the CPU over six seeds, with the published YaRN: program
# mean gap 0.0004-0.0122, widest (tie-judged) 0.0035-0.195 (a group-score
# near-tie moves a token to other experts: with 4 of 16 experts held, a
# moved token often changes the held part); fp8 control mean 0.149-0.275,
# widest 1.18-2.10; before YaRN the altered token read mean 3.1, widest
# 5.5.  The mean separates the control, the widest a gross fault.
LIMITS = {"sample_requests": 3, "served_logit_gap": 3.0,
          "served_logit_gap_mean": 0.04}


def mla_moe_cell() -> spec.Cell:
    cell = spec.resolve("serve.dsv3.reason")
    c = M.replace_sizes(cell.config, **TINY,
                        engine={"max_slots": 4, "max_len": 256,
                                "prefill_chunk": 32, "kv_blocks": 64,
                                "block_size": 16})
    c["correct"] = dict(c["correct"], **LIMITS)
    mix = dict(cell.traffic, warmup_s=1.0,
               arrival={"process": "poisson", "rate_per_s": 4.0},
               prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.6,
                           "min": 8, "max": 120},
               output_len={"dist": "lognormal", "median": 24, "sigma": 0.5,
                           "min": 8, "max": 100})
    return spec.Cell(cell.name, 1, c, mix, cell.end_to_end, cell.per_layer)


@pytest.fixture(scope="module")
def sound_run():
    return CT.drive(mla_moe_cell(), SEED, seconds=3.0)


def test_mla_moe_sound_run_is_correct(sound_run):
    assert sound_run.correct, sound_run.checks
    assert sound_run.failed == 0 and sound_run.attempted > 5
    m = sound_run.metrics
    assert m["setup_s"] > 0 and m["serve_tokens_per_s"] > 0
    assert m["ttft_mean_ms"] > 0 and m["itl_p95_ms"] > 0
    assert sound_run.extra["window_compiles"] == 0
    # the routing counter: [MoE layers, held experts], tokens were routed
    routed = sound_run.extra["routed_per_expert"]
    assert len(routed) == 2 and all(len(r) == 4 for r in routed)
    assert sum(map(sum, routed)) > 0


def test_mla_moe_control_fails(sound_run):
    from bench.drivers import serve_mla_moe as D
    cell = mla_moe_cell()
    checks = D.check_outputs(cell.config, SEED,
                             sound_run.outputs["finished"],
                             sound_run.outputs["prompts"],
                             cell.config["correct"]["control_precision"])
    assert not checks["served_logit_gap_mean"]["ok"], checks


def test_mla_moe_altered_tokens_fail():
    from bench.harness import faults
    run = CT.drive(mla_moe_cell(), SEED, seconds=2.0,
                   fault=faults.token_altered)
    assert not run.correct
    assert not run.checks["served_logit_gap"]["ok"]
    assert not run.checks["served_logit_gap_mean"]["ok"]


def test_engine_paged_logits_match_reference():
    """The engine's paged prefill (chunks of 16) and its decode through the
    MLA kernel (interpret mode) with the held experts dropless, at
    float32 weights from the seed, give the logits of the plain float32
    reference's full forward over each prompt and its served tokens."""
    import jax
    import numpy as np
    from bench.drivers import serve_mla_moe as D
    from bench.harness import reference_mla_moe as R
    from bench.harness import weights as W
    from repro.models import transformer as T
    from repro.serve.engine import EngineConfig, ServeEngine
    from repro.serve.queue import Request

    c = dict(mla_moe_cell().config, precision={"params": "float32"})
    cfg = D.arch_config(c)
    seed = 2**33 + 1
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    engine = ServeEngine(cfg, W.make_params(shapes, seed), EngineConfig(
        max_slots=3, max_len=64, prefill_chunk=16, kv_mode="paged",
        block_size=8, paged_kernel="pallas"))
    seen = {}                       # (req_id, token index) -> logits row
    admit, decode = engine._admit, engine._decode
    current = {}

    def prefill_chunk(slot):
        # a final chunk's last real row: the prompt's end, right-aligned
        current["req"] = slot.req_id
        current["last"] = min(len(slot.request.prompt), 16) - 1
        return real_prefill(slot)

    def admit_logged(*a):
        logits, cache = admit(*a)
        seen[(current["req"], 0)] = np.asarray(logits)[0, current["last"]]
        return logits, cache

    def decode_logged(*a):
        logits, cache, routed = decode(*a)
        for s in engine.table.active():
            seen[(s.req_id, s.generated)] = np.asarray(logits)[s.index, 0]
        return logits, cache, routed

    real_prefill = engine._prefill_chunk
    engine._prefill_chunk = prefill_chunk
    engine._admit, engine._decode = admit_logged, decode_logged
    rng = np.random.default_rng(0)
    lens, gens = [5, 21, 13, 30], [6, 4, 7, 5]
    prompts = [rng.integers(0, 512, n) for n in lens]
    out = engine.run([Request(req_id=i, prompt=prompts[i].tolist(),
                              max_new_tokens=g, arrival_s=0.0)
                      for i, g in enumerate(gens)])
    seqs = [(prompts[i], np.asarray(out[i], np.int32)) for i in range(4)]
    ref = {}

    def reduce(i, r, n, logits):
        for j in range(n):
            ref[(i, r + j)] = np.asarray(logits[j])

    R.served_logits(c, seed, seqs, reduce, "f32", row_block=32)
    assert set(ref) == set(seen)
    for key in ref:
        np.testing.assert_allclose(seen[key], ref[key], rtol=1e-4,
                                   atol=1e-4, err_msg=str(key))


def _synthetic_ctx(c, with_kernel=True):
    """A decode tick of 64 rows over 160,000 live tokens: the decode
    program ran 1 ms, two MLA kernel ops in it 0.3 ms; a prefill program's
    custom call outside the decode program must not count."""
    import numpy as np
    from bench.harness import flops as F
    from bench.harness import trace as TR

    ev = TR.Event
    dec, pre = "jit_serve_decode(7)", "jit_serve_prefill(8)"
    kern = "%paged_mla_decode_attention.{} = bf16[64,128,512]{{2,1,0}} " \
        "custom-call(%p.1)"
    ops = [ev("%fusion.2 = bf16[64,7168]{1,0} fusion(%p.1)", 1e5, 2e5,
              module=dec)]
    if with_kernel:
        ops += [ev(kern.format(3), 2e5, 3.5e5, module=dec),
                ev(kern.format(4), 4e5, 5.5e5, module=dec),
                ev(kern.format(5), 2.1e6, 2.2e6, module=pre)]
    tr = TR.Trace(ops={0: ops},
                  modules={0: [ev(dec, 1e5, 1.1e6), ev(pre, 2e6, 3e6)]},
                  spans=[ev("bench.decode_tick", 5e4, 1.2e6),
                         ev("bench.prefill_tick", 1.9e6, 3.1e6)])
    routed = np.zeros((4, 8), np.int32)
    routed[:, :3] = 2                      # 3 held experts hit a layer
    return {"trace": tr, "devices": [0], "config": c,
            "decode_ticks": [(64, 160_000)], "decode_routed": [routed],
            "peak": F.PEAKS["TPU v5 lite"]}


def test_mla_moe_readers_on_a_synthetic_trace():
    """Both readers from their published shapes: the kernel's live latent
    (1,152 B a token a layer) + q_eff, q_rope, out over its ops' 0.3 ms;
    the step's fixed weights + the 12 held experts hit + latent over the
    decode program's 1 ms."""
    from bench.harness import flops_mla_moe as FM
    from bench.harness import spec as SP

    c = SP.resolve("serve.dsv3.reason").config
    ctx = _synthetic_ctx(c)
    L, H = 5, 128
    k_bytes = L * (160_000 * 1152 + 64 * H * (512 + 512 + 64) * 2)
    k_flops = L * 2 * H * (2 * 512 + 64) * 160_000
    want_k = 100 * max(k_bytes / 819e9, k_flops / 197e12) / 0.3e-3
    got_k = SP.metric_reader("kernel.paged_mla_decode_roofline")(ctx)
    assert got_k == pytest.approx(want_k, rel=1e-9)

    m = FM.MlaMoe.of(c)
    assert m.params == 3_156_434_944      # what the program holds
    step = m.decode_step(64, 160_000, ctx["decode_routed"][0])
    expert = 3 * 7168 * 2048
    assert step["bytes"] == pytest.approx(
        m.fixed_bytes() + 12 * expert * 2 + L * (160_000 + 64) * 1152)
    assert step["flops"] == pytest.approx(
        2 * 64 * m.fixed_matmul_params() + 2 * 24 * expert + k_flops)
    want_m = 100 * max(step["bytes"] / 819e9,
                       step["flops"] / 197e12) / 1e-3
    got_m = SP.metric_reader("mfu.moe_decode_step")(ctx)
    assert got_m == pytest.approx(want_m, rel=1e-9)


def test_mla_moe_kernel_reader_silent_without_the_kernel():
    """A trace with no MLA kernel (a program without it) reads nothing,
    and raises nothing."""
    from bench.harness import spec as SP

    c = SP.resolve("serve.dsv3.reason").config
    ctx = _synthetic_ctx(c, with_kernel=False)
    assert SP.metric_reader("kernel.paged_mla_decode_roofline")(ctx) is None
    ctx["decode_routed"] = [None]          # a program with no counter
    assert SP.metric_reader("mfu.moe_decode_step")(ctx) is None


def test_reference_router_margins_and_flip():
    """``select`` on hand-set scores: 4 groups of 4, the best 2 groups by
    the sum of their best two, then the top 4 in them; each margin is the
    last kept against the best left out, and a flip takes the other side
    of that near-tie at its row alone."""
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import reference_mla_moe as R

    c = dict(router_experts=16, n_group=4, topk_group=2,
             num_experts_per_tok=4)
    row = np.array([.9, .8, 0, 0,   .7, .6, .5, 0,
                    .65, .6, .55, .1,  0, 0, 0, .3], np.float32)
    # group sums of the best two: 1.7, 1.3, 1.25, 0.3 -> groups 0 and 1;
    # candidates .9 .8 .7 .6 .5 -> the first four, margin .6 - .5
    sb = jnp.asarray(np.stack([row, row]))
    chosen, gm, em = R.select(sb, c)
    want = np.zeros(16, bool)
    want[[0, 1, 4, 5]] = True
    assert (np.asarray(chosen) == want).all()
    np.testing.assert_allclose(gm, [0.05, 0.05], atol=1e-6)
    np.testing.assert_allclose(em, [0.1, 0.1], atol=1e-6)

    chosen, _, em = R.select(sb, c, flip=jnp.asarray([1, 0], jnp.int32))
    want_g = np.zeros(16, bool)              # groups 0 and 2 searched
    want_g[[0, 1, 8, 9]] = True
    assert (np.asarray(chosen[0]) == want_g).all()
    assert (np.asarray(chosen[1]) == want).all()
    np.testing.assert_allclose(em[0], 0.6 - 0.55, atol=1e-6)

    chosen, _, _ = R.select(sb, c, flip=jnp.asarray([0, 2], jnp.int32))
    want_e = np.zeros(16, bool)              # .6 swapped for .5
    want_e[[0, 1, 4, 6]] = True
    assert (np.asarray(chosen[0]) == want).all()
    assert (np.asarray(chosen[1]) == want_e).all()


def test_tie_judged_gaps_flip_the_narrowest_tie(sound_run):
    """The widest served tokens are judged again with their narrowest
    router tie at that position taken the other way: each keeps the
    smaller of its two gaps, every other token its gap; the flipped gap
    is the full reference's at that token, across that tie alone."""
    import numpy as np
    from bench.drivers import serve as S
    from bench.harness import reference_mla_moe as R

    c = mla_moe_cell().config
    o = sound_run.outputs
    _, seqs = S._seqs(c, SEED, o["finished"], o["prompts"])
    tokens = [s for _, s in seqs]
    gaps, judged, picks = R.tie_judged_gaps(c, SEED, seqs, tokens, n=3)
    assert 0 < len(picks) <= 3
    widest = sorted(np.concatenate(gaps))[::-1][:len(picks)]
    assert [pk["gap"] for pk in picks] == pytest.approx(widest)
    moved = set()
    for pk in picks:
        i, j = pk["seq"], pk["token"]
        assert pk["layer"] in (1, 2) and pk["tie"] in ("group", "expert")
        assert pk["position"] == len(seqs[i][0]) - 1 + j
        assert judged[i][j] == min(pk["gap"], pk["flipped_gap"])
        moved.add((i, j))
        # the same flip over the whole sequence reads the same gap there
        g, _ = R.logit_gaps(c, SEED, seqs[i:i + 1], tokens[i:i + 1],
                            flips=[(pk["layer"], pk["position"],
                                    1 if pk["tie"] == "group" else 2)])
        assert g[0][j] == pytest.approx(pk["flipped_gap"], abs=1e-4)
    for i, (g, jg) in enumerate(zip(gaps, judged)):
        for j in range(len(g)):
            if (i, j) not in moved:
                assert jg[j] == g[j]

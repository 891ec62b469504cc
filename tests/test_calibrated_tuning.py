"""Measured-cost autotuning: calibration fit, DP bucket search, codec policy.

Host-side tests (single device) for the PR-5 tuning pipeline:

  * ``calibrate.fit_from_samples`` recovers known LinkParams exactly from
    synthetic timings (the model is linear in (α, hop, β) by construction);
  * the DP bucket partition is OPTIMAL — equal to brute-force enumeration
    of every boundary set for ≤10 random leaves, and never worse than the
    greedy packer, under the same ``overlap_step_cost``-shaped objective;
  * the per-bucket codec policy skips compression on latency-bound buckets
    and compresses bandwidth-bound ones;
  * payload-band memoization returns consistent rankings and actually
    caches;
  * the measured-refinement budget is respected and measured timings
    override the analytic picks.
"""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import autotune, calibrate, cost_model as CM
from repro.core import schedule_ir as IR, superstep as SS
from repro.core.bsp import BSPConfig
from repro.core.cost_model import LinkParams


# ---------------------------------------------------------------------------
# LinkParams hop term + banded pricing
# ---------------------------------------------------------------------------


def test_hop_default_reproduces_hops_times_alpha():
    prog = IR.build_program("fractal", (4, 4))
    legacy = LinkParams(alpha_s=1e-6, bw_Bps=50e9, name="l")
    explicit = LinkParams(alpha_s=1e-6, bw_Bps=50e9, name="e", hop_s=1e-6)
    a = CM.program_cost(prog, 1e6, legacy, mesh_contention=True)
    b = CM.program_cost(prog, 1e6, explicit, mesh_contention=True)
    assert a == pytest.approx(b)


def test_cheaper_hops_cut_mesh_cost_only():
    prog = IR.build_program("fractal", (4, 4))   # multi-hop butterfly steps
    base = LinkParams(alpha_s=1e-6, bw_Bps=50e9, name="b")
    fast_hop = LinkParams(alpha_s=1e-6, bw_Bps=50e9, name="f", hop_s=1e-8)
    assert CM.program_cost(prog, 1e4, fast_hop, mesh_contention=True) < \
        CM.program_cost(prog, 1e4, base, mesh_contention=True)
    # without mesh routing there are no hops to price
    assert CM.program_cost(prog, 1e4, fast_hop) == \
        pytest.approx(CM.program_cost(prog, 1e4, base))


def test_program_cost_banded_matches_band_center():
    prog = IR.build_program("ring", (4, 4))
    link = CM.TPU_V5E_ICI
    vol = 123_456.0
    band = CM.payload_band(vol)
    want = CM.program_cost(prog, CM.band_payload(band), link,
                           mesh_contention=True)
    got = CM.program_cost_banded(prog, vol, link, mesh_contention=True)
    assert got == pytest.approx(want)
    # band centers are within a quarter octave of the true payload
    assert CM.band_payload(band) / vol == pytest.approx(1.0, abs=0.1)


def test_rank_schedules_memoized_per_band():
    autotune._rank_banded.cache_clear()
    r1 = autotune.rank_schedules((4, 4), 1.00e6)
    r2 = autotune.rank_schedules((4, 4), 1.02e6)   # same quarter-octave band
    assert r1 == r2
    info = autotune._rank_banded.cache_info()
    assert info.hits >= 1 and info.misses == 1


def test_step_features_linearize_program_cost():
    link = LinkParams(alpha_s=3e-6, bw_Bps=40e9, name="x", hop_s=7e-7)
    for name in ("fractal", "ring", "tree", "naive"):
        prog = IR.build_program(name, (8,))
        n_steps, hops, load = CM.step_features(prog, mesh_contention=True)
        vol = 2e5
        want = CM.program_cost(prog, vol, link, mesh_contention=True)
        got = (n_steps * link.alpha_s + hops * link.hop
               + load * vol / link.bw_Bps)
        assert got == pytest.approx(want), name


# ---------------------------------------------------------------------------
# calibration: least-squares recovery of known link parameters
# ---------------------------------------------------------------------------


def _synthetic_samples(link, shape=(8,), mesh_contention=True):
    out = []
    for schedule in calibrate.FIT_SCHEDULES:
        for elems in (1 << 10, 1 << 14, 1 << 18):
            prog = IR.build_program(schedule, shape)
            vol = elems * 4.0
            secs = CM.program_cost(prog, vol, link,
                                   mesh_contention=mesh_contention)
            out.append(calibrate.LinkSample(schedule=schedule, shape=shape,
                                            payload_bytes=vol, seconds=secs))
    return out


def test_fit_recovers_synthetic_link_params():
    true = LinkParams(alpha_s=2e-6, bw_Bps=80e9, name="true", hop_s=5e-7)
    fit = calibrate.fit_from_samples(_synthetic_samples(true))
    assert fit.link.alpha_s == pytest.approx(true.alpha_s, rel=1e-3)
    assert fit.link.bw_Bps == pytest.approx(true.bw_Bps, rel=1e-3)
    assert fit.link.hop == pytest.approx(true.hop, rel=1e-3)
    assert fit.residual < 1e-6


def test_fit_feeds_the_tuner():
    # a fitted fat-pipe link must flip large-payload picks toward the
    # latency-optimal butterfly relative to a thin-pipe fit
    fat = calibrate.fit_from_samples(_synthetic_samples(
        LinkParams(alpha_s=1e-5, bw_Bps=1e13, name="fat"))).link
    thin = calibrate.fit_from_samples(_synthetic_samples(
        LinkParams(alpha_s=1e-9, bw_Bps=1e8, name="thin"))).link
    vol = 4e7
    assert autotune.pick_schedule((8,), vol, link=fat) == "fractal"
    assert autotune.pick_schedule((8,), vol, link=thin) == "ring"


def test_fit_link_params_guards_device_count():
    with pytest.raises(ValueError):
        calibrate.fit_link_params(min_devices=8)   # 1 host device only


def test_fit_from_samples_rejects_empty():
    with pytest.raises(ValueError):
        calibrate.fit_from_samples([])


# ---------------------------------------------------------------------------
# DP bucket-boundary search: optimality vs brute force and greedy
# ---------------------------------------------------------------------------


def _buckets_from_groups(groups, leaf_sizes, pad_unit):
    buckets, offset = [], 0
    for bi, ids in enumerate(groups):
        raw = sum(leaf_sizes[i] for i in ids)
        length = ((raw + pad_unit - 1) // pad_unit) * pad_unit
        buckets.append(SS.Bucket(index=bi, leaf_ids=tuple(ids), raw=raw,
                                 offset=offset, length=length))
        offset += length
    return tuple(buckets)


def _brute_force_objective(leaf_sizes, order, pad_unit, itemsize, cost_fn,
                           backward_s):
    """Minimum objective over ALL 2^(n-1) contiguous boundary sets."""
    n = len(order)
    best = math.inf
    for mask in range(1 << (n - 1)):
        groups, cur = [], [order[0]]
        for k in range(1, n):
            if (mask >> (k - 1)) & 1:
                groups.append(cur)
                cur = []
            cur.append(order[k])
        groups.append(cur)
        buckets = _buckets_from_groups(groups, leaf_sizes, pad_unit)
        obj = SS.partition_objective(buckets, cost_fn, itemsize, backward_s)
        best = min(best, obj)
    return best


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 40_000), min_size=1, max_size=10),
       st.floats(1e-7, 1e-4), st.floats(1e8, 1e11), st.floats(0.0, 2.0))
def test_dp_partition_matches_brute_force(sizes, alpha, bw, bwd_scale):
    order = tuple(reversed(range(len(sizes))))
    pad_unit, itemsize = 512, 4

    def cost_fn(payload_bytes):
        return alpha + payload_bytes / bw

    total_b = sum(sizes) * itemsize
    backward_s = bwd_scale * cost_fn(total_b)
    dp = SS.dp_partition(sizes, order, pad_unit, itemsize, cost_fn,
                         backward_s)
    dp_obj = SS.partition_objective(dp, cost_fn, itemsize, backward_s)
    brute = _brute_force_objective(sizes, order, pad_unit, itemsize,
                                   cost_fn, backward_s)
    assert dp_obj == pytest.approx(brute, rel=1e-9), \
        "DP must equal exhaustive boundary enumeration"
    # every leaf exactly once, reverse order, contiguous segments
    seen = [i for b in dp for i in b.leaf_ids]
    assert seen == list(order)
    for a, b in zip(dp, dp[1:]):
        assert b.offset == a.offset + a.length


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 500_000), min_size=1, max_size=24),
       st.floats(1e-7, 1e-4), st.floats(1e8, 1e11),
       st.sampled_from([0.0005, 0.01, 0.5, 64.0]))
def test_dp_never_worse_than_greedy(sizes, alpha, bw, greedy_mb):
    order = tuple(reversed(range(len(sizes))))
    pad_unit, itemsize = 128, 4

    def cost_fn(payload_bytes):
        return alpha + payload_bytes / bw

    backward_s = cost_fn(sum(sizes) * itemsize)
    elems = max(1, int(greedy_mb * 1e6 / itemsize))
    greedy = SS.partition_buckets(sizes, order, elems, pad_unit)
    greedy_obj = SS.partition_objective(greedy, cost_fn, itemsize,
                                        backward_s)
    dp = SS.dp_partition(sizes, order, pad_unit, itemsize, cost_fn,
                         backward_s, upper_bound=greedy_obj)
    dp_obj = SS.partition_objective(dp, cost_fn, itemsize, backward_s)
    assert dp_obj <= greedy_obj * (1 + 1e-12)


def test_search_bucket_partition_prefers_dp_and_reports_source():
    sizes = [60_000] * 12
    order = tuple(reversed(range(len(sizes))))

    def cost_fn(payload_bytes):
        return 1e-5 + payload_bytes / 1e9

    plan = SS.search_bucket_partition(sizes, order, 128, 4, cost_fn)
    assert plan.source == "dp"
    for mb in SS.GREEDY_FALLBACK_MBS:
        elems = max(1, int(mb * 1e6 / 4))
        g = SS.partition_buckets(sizes, order, elems, 128)
        g_obj = SS.partition_objective(g, cost_fn, 4, plan.backward_s)
        assert plan.objective_s <= g_obj * (1 + 1e-12)


# ---------------------------------------------------------------------------
# engine integration: bucket_mb="auto", per-bucket codecs, refinement
# ---------------------------------------------------------------------------


def _specs(sizes):
    return tuple(SS.LeafSpec(shape=(s,), dtype="float32") for s in sizes)


def test_engine_auto_buckets_cover_leaves():
    specs = _specs([40_000, 3, 70_000, 128, 9_999, 5_000_000, 17])
    cfg = BSPConfig(schedule="auto", bucket_mb="auto")
    eng = SS.SuperstepEngine(specs, cfg, (4,))
    seen = sorted(i for b in eng.buckets for i in b.leaf_ids)
    assert seen == list(range(len(specs)))
    assert eng.plan is not None
    assert "[" + eng.plan.source + "]" in eng.describe()
    assert eng.total_padded == sum(b.length for b in eng.buckets)


def test_engine_auto_respects_overlap_switch():
    specs = _specs([10_000] * 8)
    cfg = BSPConfig(schedule="fractal", bucket_mb="auto", overlap=False)
    eng = SS.SuperstepEngine(specs, cfg, (4,))
    assert eng.n_buckets == 1 and eng.plan is None


def test_bsp_config_validates_new_fields():
    BSPConfig(bucket_mb="auto")
    BSPConfig(bucket_codec="auto")
    BSPConfig(bucket_codec="bf16", link=CM.TPU_V5E_ICI)
    with pytest.raises(ValueError):
        BSPConfig(bucket_mb="autos")
    with pytest.raises(ValueError):
        BSPConfig(bucket_codec="zstd")


def test_codec_policy_small_skips_large_compresses():
    pols = autotune.pick_bucket_policies((4, 4), [256.0, 4e8])
    assert pols[0].codec == "none", "latency-bound bucket must not compress"
    assert pols[0].schedule == "fractal"
    assert pols[1].schedule == "fractal" and pols[1].codec in ("bf16", "int8")
    # same shape under the zero1 pricing: policy survives the publish term
    z = autotune.pick_bucket_policies((4, 4), [256.0, 4e8],
                                      zero1_publish=True)
    assert z[0].codec == "none" and z[1].codec != "none"


def test_rank_policies_sorted_and_codecs_fractal_only():
    pols = autotune.rank_policies((4, 4), 1e7)
    costs = [p.predicted_s for p in pols]
    assert costs == sorted(costs)
    for p in pols:
        if p.codec != "none":
            assert p.schedule == "fractal"


def test_engine_auto_codec_tags_bucket_meta():
    specs = _specs([100_000_000, 64])
    cfg = BSPConfig(schedule="auto", bucket_mb=1.0, bucket_codec="auto")
    eng = SS.SuperstepEngine(specs, cfg, (4, 4))
    assert eng.n_buckets == 2
    assert eng.codec_names[0] == "none"      # tiny reverse-order head
    assert eng.codec_names[1] != "none"      # the 400MB leaf compresses
    progs = eng.programs()
    assert progs[0].bucket.codec is None
    assert progs[1].bucket.codec == eng.codec_names[1]


def test_engine_uniform_codec_when_bucket_codec_unset():
    specs = _specs([100_000_000, 64])
    cfg = BSPConfig(schedule="fractal", bucket_mb=1.0, compression="bf16")
    eng = SS.SuperstepEngine(specs, cfg, (4, 4))
    assert all(c == "bf16" for c in eng.codec_names)


def test_pick_bucket_schedules_measured_budget():
    shape = (4, 4)
    buckets = [1e3, 1e8]
    analytic = autotune.pick_bucket_schedules(shape, buckets)
    calls = []

    def measure(name, payload):
        calls.append((name, payload))
        return 1e-9 if name == analytic[1] else 1.0

    # budget 0 → no measurement at all
    assert autotune.pick_bucket_schedules(
        shape, buckets, measure=measure, measure_budget=0) == analytic
    assert calls == []
    # budget 2 → only the priciest bucket (the 1e8 one) gets refined
    got = autotune.pick_bucket_schedules(shape, buckets, measure=measure,
                                         measure_budget=2, measure_top_k=2)
    assert len(calls) == 2
    assert all(p == buckets[1] for _, p in calls)
    assert got[1] == analytic[1]


def test_budget_exhaustion_cannot_drop_untimed_incumbent():
    shape = (4, 4)
    buckets = [4e8]
    ranking = [n for n, _ in autotune.rank_schedules(shape, buckets[0])]
    incumbent = ranking[1]               # baseline = analytic runner-up
    calls = []

    def measure(name, payload):
        calls.append(name)
        return 1e-9                      # every challenger "measures fast"

    got = autotune.pick_bucket_schedules(shape, buckets, measure=measure,
                                         measure_budget=1, measure_top_k=2,
                                         baseline=[incumbent])
    assert calls == [incumbent], \
        "the incumbent must be timed before any challenger"
    assert got[0] == incumbent


def test_zero1_codec_overhead_halved():
    # the publish all-gather half is uncompressed, so the quant launches
    # charge only the reduce-scatter half: a payload whose saving beats
    # L·alpha but not 2L·alpha must still compress under zero1 pricing
    link = LinkParams(alpha_s=1e-6, bw_Bps=50e9, name="l")
    prog = IR.build_program("fractal", (4, 4))
    pols = {p.codec: p.predicted_s
            for p in autotune.rank_policies((4, 4), 1e7, link=link,
                                            zero1_publish=True)
            if p.schedule == "fractal"}
    full = CM.program_cost_banded(prog, 1e7, link, mesh_contention=True)
    wire = CM.program_cost_banded(prog, 1e7 * 0.5, link,
                                  mesh_contention=True)
    want = 0.5 * full + 0.5 * wire + \
        0.5 * autotune.CODEC_STEP_ALPHAS["bf16"] * link.alpha_s \
        * prog.num_steps
    assert pols["bf16"] == pytest.approx(want)


def test_measured_refinement_overrides_analytic_pick():
    shape = (4, 4)
    buckets = [4e8]
    analytic = autotune.pick_bucket_schedules(shape, buckets)
    runner_up = [n for n, _ in autotune.rank_schedules(shape, buckets[0])
                 if n != analytic[0]][0]

    def measure(name, payload):
        return 1e-9 if name == runner_up else 1.0

    got = autotune.pick_bucket_schedules(shape, buckets, measure=measure,
                                         measure_budget=4, measure_top_k=3)
    assert got[0] == runner_up


def test_engine_refined_applies_measured_picks_and_drops_codecs():
    specs = _specs([100_000_000])
    cfg = BSPConfig(schedule="auto", bucket_mb=None, bucket_codec="auto")
    eng = SS.SuperstepEngine(specs, cfg, (4, 4))
    assert eng.codec_names[0] != "none"
    ref = eng.refined(lambda s, b: 1e-9 if s == "naive" else 1.0,
                      measure_budget=8, measure_top_k=6)
    assert ref.schedules == ("naive",)
    assert ref.codec_names == ("none",)      # codecs ride fractal only
    # the original engine is untouched (refined returns a copy)
    assert eng.schedules != ("naive",)


def test_engine_refined_keeps_policy_picks_unless_outmeasured():
    specs = _specs([100_000_000] * 4)
    cfg = BSPConfig(schedule="auto", bucket_mb=64.0, bucket_codec="auto")
    eng = SS.SuperstepEngine(specs, cfg, (4, 4))
    assert all(c != "none" for c in eng.codec_names)
    # a single measurement that CONFIRMS the incumbent must change nothing
    # — least of all the codec-aware picks of the unmeasured buckets
    ref = eng.refined(lambda s, b: 1e-9 if s == eng.schedules[0] else 1.0,
                      measure_budget=1, measure_top_k=1)
    assert ref.schedules == eng.schedules
    assert ref.codec_names == eng.codec_names


def test_forced_bucket_codec_normalized_to_fractal_buckets():
    specs = _specs([100_000_000])
    eng = SS.SuperstepEngine(
        specs, BSPConfig(schedule="ring", bucket_codec="bf16"), (4, 4))
    assert eng.codec_names == ("none",), \
        "no wire-codec path outside fractal — a forced codec must not " \
        "silently pretend otherwise"
    # the legacy uniform `compression` keeps its historical EF semantics
    leg = SS.SuperstepEngine(
        specs, BSPConfig(schedule="ring", compression="bf16"), (4, 4))
    assert leg.codec_names == ("bf16",)


def test_engine_refined_respects_forced_schedule():
    specs = _specs([100_000_000])
    eng = SS.SuperstepEngine(
        specs, BSPConfig(schedule="fractal", bucket_mb=None), (4, 4))
    ref = eng.refined(lambda s, b: 1e-9 if s == "naive" else 1.0,
                      measure_budget=8, measure_top_k=6)
    assert ref.schedules == ("fractal",), \
        "refinement must not override an explicitly forced schedule"
    xla = SS.SuperstepEngine(
        specs, BSPConfig(schedule="xla", bucket_mb=None), (4, 4))
    assert xla.refined(lambda s, b: 0.0, measure_budget=8).schedules == \
        ("xla",)


def test_timeline_charges_codec_launch_overhead():
    # tiny payload: the β saving is negligible, the quant/dequant launches
    # are not — a forced codec must predict strictly slower than none
    specs = _specs([400])
    plain = SS.SuperstepEngine(
        specs, BSPConfig(schedule="fractal"), (4, 4))
    coded = SS.SuperstepEngine(
        specs, BSPConfig(schedule="fractal", bucket_codec="bf16"), (4, 4))
    assert coded.timeline(0.0).overlapped_s > \
        plain.timeline(0.0).overlapped_s


def test_engine_for_caches_calibrated_configs():
    import jax.numpy as jnp
    link = LinkParams(alpha_s=1e-6, bw_Bps=42e9, name="fit")
    cfg = BSPConfig(schedule="auto", bucket_mb="auto", link=link)
    tree = {"w": jnp.zeros((2048,))}
    e1 = SS.engine_for(tree, cfg, (4,))
    e2 = SS.engine_for(tree, cfg, (4,))
    assert e1 is e2 and e1.link is link


def test_timeline_prices_with_engine_link():
    specs = _specs([1_000_000] * 4)
    slow = LinkParams(alpha_s=1e-6, bw_Bps=1e9, name="slow")
    fast = LinkParams(alpha_s=1e-6, bw_Bps=1e12, name="fast")
    e_slow = SS.SuperstepEngine(
        specs, BSPConfig(schedule="fractal", bucket_mb=1.0, link=slow), (4,))
    e_fast = SS.SuperstepEngine(
        specs, BSPConfig(schedule="fractal", bucket_mb=1.0, link=fast), (4,))
    assert e_slow.timeline(1e-3).overlapped_s > \
        e_fast.timeline(1e-3).overlapped_s

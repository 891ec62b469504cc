"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on a TPU v5e chip
(``bench/tests/data/trace_small.xplane.pb``, made by
``bench/tools/record_trace.py``: three steps of a jitted program with a
Pallas paged-attention kernel, each under a ``bench.engine_step`` host
span, with a ``bench.host_gap`` span of host work between them)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench.harness import readers  # noqa: E402
from bench.harness import trace as TR  # noqa: E402

SMALL = BENCH / "tests" / "data" / "trace_small.xplane.pb"


def ev(name, s, e, module=""):
    return TR.Event(name, float(s), float(e), module)


def test_union_length_clip_subtract():
    u = TR.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert TR.length(u) == 6
    assert TR.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert TR.subtract([(0, 10)], [(2, 3), (5, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert TR.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert TR.gaps([(1, 2)], 0, 4) == [(0, 1), (2, 4)]


def hand_trace():
    ops = {0: [ev("fusion.1", 0, 10, "jit_step"),
               ev("custom-call.3", 10, 30, "jit_step"),
               ev("all-gather-start.2", 30, 35),
               ev("fusion.1", 32, 40),
               ev("all-gather-done.2", 40, 50),
               ev("fusion.7", 80, 100)],
           1: [ev("fusion.1", 0, 50)]}
    mods = {0: [ev("jit_step", 0, 30)], 1: []}
    spans = [ev("bench.engine_step", 0, 60), ev("bench.decode_tick", 5, 55),
             ev("bench.feed", 60, 100)]
    return TR.Trace(ops=ops, modules=mods, spans=spans)


def test_busy_union_and_op_time_by_name():
    tr = hand_trace()
    assert TR.busy(tr, 0) == [(0, 50), (80, 100)]
    assert TR.busy_seconds(tr, [0, 1]) == pytest.approx((70 + 50) / 2 / 1e9)
    by_name = dict(TR.op_time_by_name(tr, [0]))
    assert by_name["fusion"] == pytest.approx((10 + 8 + 20) / 1e9)
    assert by_name["custom-call"] == pytest.approx(20 / 1e9)
    # averaged over the devices named
    assert dict(TR.op_time_by_name(tr, [0, 1]))["fusion"] == \
        pytest.approx((38 + 50) / 2 / 1e9)


def test_idle_gaps_labelled_by_innermost_host_span():
    tr = hand_trace()
    gaps = TR.idle_gaps(tr, 0)
    assert gaps[0] == ("bench.engine_step", pytest.approx(10 / 1e9)) or \
        gaps[0][1] == pytest.approx(30 / 1e9)
    labels = dict((round(s * 1e9), k) for k, s in gaps)
    assert labels[30] in ("bench.engine_step", "bench.feed")
    assert TR.span_at(tr.spans, 20) == "bench.decode_tick"
    assert TR.span_at(tr.spans, 200) == "host: no bench span"


def test_exposed_collective_time():
    tr = hand_trace()
    # collectives cover [30, 50); compute covers [32, 40): exposed 12
    assert TR.exposed_collective_ns(tr, 0) == 12
    assert TR.is_collective("collective-permute-done.4")
    assert not TR.is_collective("fusion.12")


def test_op_names_and_containers():
    assert TR.op_key("%fusion.12 = bf16[8]{0} fusion(%custom-call.3)") \
        == "fusion"
    assert TR.op_key("%custom-call.3 = bf16[8]{0} custom-call()") \
        == "custom-call"
    assert not TR.is_kernel("%fusion.1 = f32[] fusion(%custom-call.3)")
    assert not TR.is_collective("%add.1 = f32[] add(%all-gather-done.2)")
    ops = [ev("%while.5 = (s32[]) while()", 0, 100), ev("fusion.1", 10, 20),
           ev("custom-call.2", 20, 50), ev("fusion.3", 120, 130)]
    TR.mark_containers(ops)
    assert [o.container for o in ops] == [True, False, False, False]
    tr = TR.Trace(ops={0: ops}, modules={0: []}, spans=[])
    assert dict(TR.op_time_by_name(tr, [0])) == pytest.approx(
        {"fusion": 20e-9, "custom-call": 30e-9})
    assert TR.length(TR.busy(tr, 0)) == 110


def test_modules_assigned_to_ops():
    ops = [ev("a", 1, 2), ev("b", 5, 6), ev("c", 12, 13)]
    TR.assign_modules(ops, [ev("m1", 0, 4), ev("m2", 4, 10)])
    assert [o.module for o in ops] == ["m1", "m2", ""]


def test_decode_share_matches_hand_count():
    """One traced decode step: the roofline time of its work over the
    decode program's device time."""
    from bench.harness import flops as F
    c = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10, "tie_word_embeddings": True}
    tr = hand_trace()
    tr.spans = [ev("bench.decode_tick", 0, 60),
                ev("bench.decode_tick", 70, 75)]   # ran no decode
    peak = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"trace": tr, "devices": [0], "config": c, "peak": peak,
           "decode_ticks": [(3, 20), (0, 0)]}
    m = F.Dense.of(c)
    want = F.roofline_s(m.decode_step(3, 20), peak) / 30e-9
    assert readers.decode_share(ctx, kernel=False) == \
        pytest.approx(100 * want)
    want_k = F.roofline_s(m.paged_decode_kernel(3, 20), peak) / 20e-9
    assert readers.decode_share(ctx, kernel=True) == \
        pytest.approx(100 * want_k)


def test_recorded_chip_trace():
    tr = TR.load(str(SMALL))
    assert tr.devices == [0]
    steps = [s for s in tr.spans if s.name == "bench.engine_step"]
    assert len(steps) == 3
    busy = TR.busy(tr, 0)
    assert busy and TR.length(busy) > 0
    lo, hi = tr.window()
    assert TR.length(TR.clip(busy, lo, hi)) <= hi - lo
    # the Pallas kernel runs in each step, inside the step's program
    kern = TR.kernel_events(tr, 0)
    assert len(kern) >= 3 and all(e.module for e in kern)
    assert len(readers.decode_modules(tr, 0)) == 1
    # every step's device work falls inside its host span
    for s in steps:
        assert TR.length(TR.clip(busy, s.start, s.end)) > 0
    names = dict(TR.op_time_by_name(tr, [0]))
    assert sum(names.values()) == pytest.approx(
        sum(e.dur for e in tr.ops[0] if not e.container) / 1e9)
    gaps = TR.idle_gaps(tr, 0)
    assert gaps and gaps[0][1] > 0
    assert any(label == "bench.host_gap" for label, _ in gaps)


def test_clock_offset_puts_programs_inside_their_spans():
    # programs run 700 ns before their host spans on the device's clock
    mods = [ev("jit_step", 100, 150), ev("jit_step", 1100, 1180)]
    spans = [ev("bench.engine_step", 790, 900),
             ev("bench.engine_step", 1790, 1900)]
    shift = TR.clock_offset(mods, spans)
    assert all(s.start <= m.start + shift and m.end + shift <= s.end
               for m, s in zip(mods, spans))
    assert TR.clock_offset(mods, []) == 0.0


def test_recorded_chip_trace_is_aligned():
    tr = TR.load(str(SMALL))
    steps = [s for s in tr.spans if s.name == "bench.engine_step"]
    assert tr.shift_ns != 0.0
    for m in tr.modules[0]:
        assert any(s.start <= m.start and m.end <= s.end for s in steps)

"""The serving program's own names, end to end on the CPU, and the
readers that use them.

A tiny paged engine is traced with ``jax.profiler`` while driven as the
benchmark drives it (a ``bench.engine_step`` span around each step, the
driver's wrappers on its phases): its ``serve.*`` spans nest as the
engine opens them, carry their stats, and lie inside the benchmark's
steps.  The per-layer readers that find the engine's programs by name
are checked on a hand-built trace against a hand count."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench.harness import program as PG  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.harness import trace as TR  # noqa: E402

PARENT = {
    "serve.admit": "serve.step",
    "serve.prefill": "serve.step",
    "serve.prefill.prepare": "serve.prefill",
    "serve.prefill.dispatch": "serve.prefill",
    "serve.prefill.first_token": "serve.prefill",
    "serve.prefill.publish": "serve.prefill",
    "serve.decode.prepare": "serve.step",
    "serve.decode.dispatch": "serve.step",
    "serve.decode.readback": "serve.step",
    "serve.decode.commit": "serve.step",
}
PROMPTS = {0: 70, 1: 20, 2: 45}       # 3, 1 and 2 chunks of 32


def ev(name, s, e, module=""):
    return TR.Event(name, float(s), float(e), module)


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """(bench spans' trace, program spans, engine, trace directory) of a
    tiny paged engine serving three requests from t = 0, traced step by
    step."""
    import jax
    import numpy as np
    from repro.models import transformer as T
    from repro.serve.queue import Request

    from bench.drivers import serve as S
    from bench.harness import common as H
    from bench.harness import model as M
    from bench.tests import cells_tiny as CT

    c = CT.serve_cell().config
    cfg = M.arch_config(c)
    engine = S._engine(cfg, T.init_params(cfg, jax.random.key(0)), c)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, prompt=rng.integers(1, 500, n).tolist(),
                    max_new_tokens=3, arrival_s=0.0)
            for i, n in PROMPTS.items()]
    engine.run([Request(req_id=99, prompt=[1] * 40, max_new_tokens=2,
                        arrival_s=0.0)])           # compile outside
    for name in ("_admit_ready", "_prefill_tick", "_decode_tick"):
        S._wrap(engine, name)
    engine.submit(reqs)
    with H.profiled(True, tmp_path_factory.mktemp("t"), "engine") as d:
        while len(engine.queue) or engine.table.busy():
            with H.span(True, "bench.engine_step"):
                engine.step()
    return TR.load(str(d)), PG.program_spans(str(d)), engine, d


def enclosing(spans, s):
    """Name of the innermost other span that holds ``s``."""
    outer = [o for o in spans if o is not s and o.start <= s.start
             and s.end <= o.end and o.dur > s.dur]
    return min(outer, key=lambda o: o.dur).name if outer else None


def test_engine_spans_nest_as_opened(engine_trace):
    _, spans, _, _ = engine_trace
    names = {s.name for s in spans}
    assert names == set(PARENT) | {"serve.step"}
    for s in spans:
        want = PARENT.get(s.name)
        assert enclosing(spans, s) == want, (s.name, s.start)
    leaf_names = {s.name for s in PG.leaves(spans)}
    assert "serve.step" not in leaf_names and "serve.prefill" not in \
        leaf_names
    assert {"serve.admit", "serve.decode.prepare"} <= leaf_names


def test_prefill_spans_carry_request_and_chunk(engine_trace):
    _, spans, engine, _ = engine_trace
    chunks = {}
    for s in spans:
        if s.name == "serve.prefill":
            assert "req_id" in s.stats and "chunk" in s.stats, s.stats
            chunks.setdefault(int(s.stats["req_id"]), []).append(
                int(s.stats["chunk"]))
    C = engine.ecfg.prefill_chunk
    assert chunks == {r: list(range(-(-n // C))) for r, n in PROMPTS.items()}
    steps = [int(s.stats["step_num"]) for s in spans if s.name == "serve.step"]
    assert steps == list(range(steps[0], steps[0] + len(steps)))


def test_program_spans_lie_inside_benchmark_steps(engine_trace):
    tr, spans, _, _ = engine_trace
    steps = [s for s in tr.spans if s.name == "bench.engine_step"]
    assert len(steps) == sum(1 for s in spans if s.name == "serve.step")
    for s in spans:
        assert any(b.start <= s.start and s.end <= b.end for b in steps), \
            s.name
    # the driver's wrappers sit between the step and the engine's phases
    wraps = [s for s in tr.spans if s.name == "bench.decode_tick"]
    for s in spans:
        if s.name.startswith("serve.decode."):
            assert any(w.start <= s.start and s.end <= w.end for w in wraps)


def test_programs_run_under_stable_names(engine_trace):
    import jax.numpy as jnp
    _, _, engine, _ = engine_trace
    text = engine._sample.lower(jnp.zeros((1, 8)), jnp.zeros((1,), jnp.int32),
                                jnp.zeros((1,), jnp.int32)).as_text()
    assert "jit_serve_sample" in text
    assert {f.__name__ for f in (engine._decode, engine._admit,
                                 engine._admit_quiet, engine._reset,
                                 engine._copy)} == {
        "serve_decode", "serve_prefill", "serve_prefill_quiet",
        "serve_reset", "serve_copy_block"}


def test_program_trace_tool_counts_the_spans(engine_trace):
    sys.path.insert(0, str(BENCH / "tools"))
    import program_trace
    _, spans, _, d = engine_trace
    got = program_trace.summarize(str(d))["spans"]
    assert {k: v["count"] for k, v in got.items()} == {
        n: sum(1 for s in spans if s.name == n) for n in got}
    assert got["serve.prefill"]["count"] == 6


def test_no_bench_names_in_the_program():
    src = BENCH.parent / "src"
    hits = [p for p in src.rglob("*.py") if '"bench.' in p.read_text()
            or "'bench." in p.read_text()]
    assert not hits


# -- readers on a hand-built trace -------------------------------------------

def hand_ctx():
    """Two steps on one device (times in ns).  Step 1 [0, 100): a prefill
    tick [0, 40) whose chunk runs [10, 30), then a decode tick [40, 100)
    whose program runs [55, 90) after a copy op [45, 50) of the same step.
    Step 2 [100, 200): no prefill tick holds a chunk ([100, 105)); a decode
    tick [105, 200) whose program runs [150, 190), with a quiet prefill
    chunk of another request dispatched earlier running [110, 130)."""
    pre, quiet, dec = ("jit_serve_prefill(1)", "jit_serve_prefill_quiet(2)",
                       "jit_serve_decode(3)")
    ops = {0: [ev("fusion.1", 10, 30, pre), ev("copy.1", 45, 50, dec),
               ev("custom-call.2", 55, 90, dec),
               ev("fusion.3", 110, 130, quiet),
               ev("custom-call.2", 150, 190, dec)]}
    mods = {0: [ev(pre, 10, 30), ev(dec, 45, 90), ev(quiet, 110, 130),
                ev(dec, 150, 190)]}
    spans = [ev("bench.engine_step", 0, 100), ev("bench.prefill_tick", 0, 40),
             ev("bench.decode_tick", 40, 100),
             ev("bench.engine_step", 100, 200),
             ev("bench.prefill_tick", 100, 105),
             ev("bench.decode_tick", 105, 200)]
    tr = TR.Trace(ops=ops, modules=mods, spans=spans)
    return {"trace": tr, "devices": [0]}


def reader(name):
    return spec.metric_reader(name)


def test_prefill_busy_share_by_hand():
    # prefill ops: [10,30) + [110,130) = 40 of busy 20+5+35+20+40 = 120
    assert reader("sched.prefill_busy_share")(hand_ctx()) == \
        pytest.approx(100 * 40 / 120)


def test_prefill_idle_per_chunk_by_hand():
    # idle in [0,40): 40 - 20 = 20; in [100,105): 5; two chunks ran
    assert reader("sched.prefill_idle_ms_per_chunk")(hand_ctx()) == \
        pytest.approx((20 + 5) / 2 / 1e6)


def test_decode_prepare_idle_by_hand():
    # step 1: [40, 45) idle before the program's start at 45 → 5;
    # step 2: [105, 150) less the quiet chunk [110, 130) → 25
    assert reader("sched.decode_prepare_idle_ms")(hand_ctx()) == \
        pytest.approx((5 + 25) / 2 / 1e6)


@pytest.mark.parametrize("name", ["sched.prefill_busy_share",
                                  "sched.prefill_idle_ms_per_chunk",
                                  "sched.decode_prepare_idle_ms"])
def test_readers_give_none_for_a_program_without_names(name):
    ctx = hand_ctx()
    tr = ctx["trace"]
    for e in tr.ops[0] + tr.modules[0]:
        e.module = "jit_fn(7)" if e.module else ""
        if e.name.startswith("jit_"):
            e.name = "jit_fn(7)"
    assert reader(name)(ctx) is None


def test_idle_by_leaf_and_leaf_share_by_hand():
    ctx = hand_ctx()
    tr = ctx["trace"]
    S = PG.Span
    spans = [S("serve.step", 0, 100), S("serve.prefill", 2, 38),
             S("serve.prefill.prepare", 2, 10),
             S("serve.prefill.dispatch", 10, 12),
             S("serve.decode.prepare", 40, 44),
             S("serve.decode.commit", 92, 99)]
    leaf = {s.name for s in PG.leaves(spans)}
    assert leaf == {"serve.prefill.prepare", "serve.prefill.dispatch",
                    "serve.decode.prepare", "serve.decode.commit"}
    steps = [s for s in tr.spans if s.name == "bench.engine_step"][:1]
    by = PG.idle_by_leaf(tr, 0, steps, spans)
    # idle in [0,100): [0,10) [30,45) [50,55) [90,100) = 40
    assert by == {"serve.prefill.prepare": 8, "serve.prefill.dispatch": 0,
                  "serve.decode.prepare": 4, "serve.decode.commit": 7,
                  PG.NO_SPAN: 40 - 19}
    assert PG.leaf_share(by) == pytest.approx(100 * 19 / 40)
    assert PG.span_at(spans, 3) == "serve.prefill.prepare"
    assert PG.span_at(spans, 39) == "serve.step"
    assert PG.span_at(spans, 150) == PG.NO_SPAN

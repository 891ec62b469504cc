"""The serving cell's driver on the CPU at a tiny size: a sound run is
correct; the control (the reference in fp8, the next precision below the
configuration's bfloat16, put in the program's place) is not, and
neither is a run whose produced tokens are altered.  The harness's look
for a chip is skipped; everything after it runs as in a benchmark run
(weights from the seed, set-up, window, reference)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench.tests import cells_tiny as CT  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def serve_run():
    return CT.drive(CT.serve_cell(), SEED, seconds=3.0)


def test_serve_sound_run_is_correct(serve_run):
    assert serve_run.correct, serve_run.checks
    assert serve_run.failed == 0 and serve_run.attempted > 5
    m = serve_run.metrics
    assert m["setup_s"] > 0 and m["serve_tokens_per_s"] > 0
    assert m["ttft_mean_ms"] > 0 and m["itl_p95_ms"] > 0
    assert serve_run.extra["window_compiles"] == 0


def test_serve_control_fails(serve_run):
    from bench.drivers import serve as S
    cell = CT.serve_cell()
    checks = S.check_outputs(cell.config, SEED,
                             serve_run.outputs["finished"],
                             serve_run.outputs["prompts"],
                             cell.config["correct"]["control_precision"])
    assert not checks["served_logit_gap"]["ok"], checks


def test_serve_altered_tokens_fail():
    from bench.harness import faults
    run = CT.drive(CT.serve_cell(), SEED, seconds=2.0,
                   fault=faults.token_altered)
    assert not run.correct
    assert not run.checks["served_logit_gap"]["ok"]

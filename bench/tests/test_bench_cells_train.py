"""The one-chip training cell's driver on the CPU at a tiny size: a sound
run is correct; the control (the reference in the configuration's
control precision, bf16, put in the program's place) is not, and neither
is a run whose step hands back its parameters unchanged or leaves out
half of the batch.  The harness's
look for a chip is skipped; everything after it runs as in a benchmark
run (weights from the seed, set-up, window, reference)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench.tests import cells_tiny as CT  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def train_run():
    return CT.drive(CT.train_cell(1), SEED)


def test_train_sound_run_is_correct(train_run):
    assert train_run.correct, train_run.checks
    assert train_run.metrics["train_tokens_per_s"] > 0
    assert train_run.extra["window_compiles"] == 0


def test_train_control_fails(train_run):
    from bench.drivers import train as TD
    from bench.harness import reference as R
    cell = CT.train_cell(1)
    o = train_run.outputs
    ctrl = R.train(cell.config, SEED, o["batches"],
                   cell.config["correct"]["control_precision"])
    checks = TD.compare(cell.config, ctrl["losses"], ctrl["grad_norms"],
                        ctrl["delta_norms"], o["reference"])
    assert not all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults_fail(fault):
    from bench.harness import faults
    run = CT.drive(CT.train_cell(1), SEED, fault=faults.TRAIN[fault])
    assert not run.correct, run.checks

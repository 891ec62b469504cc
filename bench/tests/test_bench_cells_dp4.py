"""The four-chip training cell's driver on four virtual CPU devices, in a
child process (the device count is fixed when JAX starts): a sound run
is correct, and runs with the gradient's reduce-scatter left out or half
of the batch left out are not."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**31 + 5


CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from bench.tests import cells_tiny as CT
from bench.harness import faults
out = {{}}
for name in [None, "exchange_left_out", "half_batch"]:
    run = CT.drive(CT.train_cell(4), {seed},
                   fault=faults.TRAIN[name] if name else None)
    out[name or "sound"] = run.correct
print(json.dumps(out))
"""


def test_train_four_chips_sound_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c",
                        CHILD.format(root=str(ROOT), seed=SEED)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "exchange_left_out": False,
                   "half_batch": False}

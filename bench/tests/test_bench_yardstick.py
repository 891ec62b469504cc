"""CPU tests of the benchmark's yardstick: the traffic generator, the
operation and byte counts, the harness's refusal to run without a TPU,
and the lookup of configurations, mixes and metric readers by name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench.harness import flops as F  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.harness import traffic as TF  # noqa: E402

CHAT = {"kind": "open_loop",
        "arrival": {"process": "poisson", "rate_per_s": 2.0},
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                       "min": 128, "max": 3584},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 16, "max": 512},
        "warmup_s": 20.0}


# -- traffic -----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_requests_deterministic_per_seed(seed):
    a = TF.requests(CHAT, 30.0, seed, 1000)
    b = TF.requests(CHAT, 30.0, seed, 1000)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def _window(reqs):
    return [r for r in reqs if r.arrival_s >= 0]


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = _window(TF.requests(CHAT, 30.0, 1, 1000))
    b = _window(TF.requests(CHAT, 30.0, 2, 1000))
    assert len(a) == len(b) == TF.window_count(CHAT, 30.0) == 60
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    ga = np.sort(np.diff([0.0] + [r.arrival_s for r in a]))
    gb = np.sort(np.diff([0.0] + [r.arrival_s for r in b]))
    assert np.allclose(ga, gb)


def test_schedule_spans_warmup_and_window():
    reqs = TF.requests(CHAT, 30.0, 3, 1000)
    t = [r.arrival_s for r in reqs]
    assert t == sorted(t)
    assert len(reqs) == 2.0 * 20.0 + 2.0 * 30.0
    assert -20.0 < t[0] < -19.0 and t[39] < 0.0 <= t[40]
    assert 28.0 < t[-1] < 30.0     # arrivals reach the end of the window
    assert [r.req_id for r in reqs] == list(range(len(reqs)))
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 128 and max(lens) <= 3584
    assert 900 < np.median(lens) < 1150
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_lognormal_strata_are_quantiles():
    spec_ = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
             "max": 10_000}
    v = np.sort(TF.lengths(spec_, 101, 0, 2))
    assert v[50] == 100                      # the middle stratum is the median
    assert v[0] < 100 < v[-1]


def test_poisson_and_burst_arrivals():
    mix = {"arrival": {"process": "poisson", "rate_per_s": 4.0}}
    t = TF.arrivals(mix, 1000, 5, 1)
    assert t[0] > 0.0 and np.all(np.diff(t) >= 0)
    assert abs(t[-1] / 1000 - 0.25) < 0.01   # mean gap 1/rate
    burst = {"arrival": {"process": "burst", "rate_per_s": 4.0,
                         "duty": 0.25, "period_s": 2.0}}
    b = TF.arrivals(burst, 1000, 5, 1)
    phase = np.mod(b, 2.0)
    assert np.all(phase < 0.5 + 1e-9)        # only in the on-quarter
    assert abs(b[-1] / 1000 - 0.25) < 0.02   # same average rate


# -- operations and bytes ----------------------------------------------------

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 10, "tie_word_embeddings": True, "qkv_bias": True}


def test_param_counts_by_hand():
    m = F.Dense.of(TINY)
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16
    assert m.layer_matmul_params == 64 + 32 + 32 + 64 + 384
    # + biases 8 + 4 + 4, + two norms of 8
    assert m.layer_params == 576 + 16 + 16
    # two layers, tied embedding 10x8, final norm 8
    assert m.params == 2 * 608 + 80 + 8


def test_decode_step_work_by_hand():
    m = F.Dense.of(TINY)
    w = m.decode_step(rows=3, kv_tokens=20)
    # 3 rows x 2 flops x (2 layers x 576 + head 80) + attention
    # 4 x layers 2 x heads 2 x dh 4 x 20 keys
    assert w["flops"] == 3 * 2 * (2 * 576 + 80) + 4 * 2 * 2 * 4 * 20
    kv_tok = 2 * 2 * 1 * 4 * 2             # k+v, layers, kv heads, dh, bf16
    assert w["bytes"] == m.params * 2 + 20 * kv_tok + 3 * kv_tok


def test_kernel_work_by_hand():
    m = F.Dense.of(TINY)
    w = m.paged_decode_kernel(rows=3, kv_tokens=20)
    per_layer = 20 * 2 * 1 * 4 * 2 + 3 * 2 * 2 * 4 * 2
    assert w["bytes"] == 2 * per_layer
    assert w["flops"] == 4 * 2 * 2 * 4 * 20


def test_train_flops_by_hand():
    m = F.Dense.of(TINY)
    fwd = 2 * (2 * 576 + 80) + 4 * 2 * 2 * 4 * (64 + 1) / 2
    assert m.train_flops_per_token(64) == pytest.approx(3 * fwd)


def test_roofline_takes_the_larger_bound():
    pk = F.peaks("TPU v5 lite")
    assert pk == {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert F.roofline_s({"flops": 197e12, "bytes": 0}, pk) == 1.0
    assert F.roofline_s({"flops": 0, "bytes": 2 * 819e9}, pk) == 2.0
    with pytest.raises(KeyError):
        F.peaks("cpu")


# -- the harness ---------------------------------------------------------------

def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_a_machine_without_tpu():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "serve.phi4.chat", "--seed", str(2**31 + 9), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve.phi4.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "src/repro" in p.stderr


def test_check_devices_refuses_cpu_and_too_few_chips():
    sys.path.insert(0, str(BENCH))
    from bench import run as R

    class Dev:
        def __init__(self, platform, kind="TPU v5 lite"):
            self.platform, self.device_kind = platform, kind

    with pytest.raises(R.NoDevice):
        R.check_devices(1, [Dev("cpu", "cpu")])
    with pytest.raises(R.NoDevice):
        R.check_devices(4, [Dev("tpu")])
    with pytest.raises(KeyError):
        R.check_devices(1, [Dev("tpu", "TPU v9 imaginary")])
    assert len(R.check_devices(1, [Dev("tpu"), Dev("tpu")])) == 1


def test_every_cell_resolves_and_every_reader_loads():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench)
        assert spec.driver(cell.config).run
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell by adding files only."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "qwen2.5-3b-bsp.json").read_text())
    cfg["name"] = "new-model"
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(CHAT, warmup_s=1.0)))
    (tmp_path / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.cell", "config": "new-model",
                               "traffic": "new_mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["new.cell"]})
    cell = spec.resolve("new.cell", bench, root=tmp_path)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["warmup_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    read = spec.metric_reader("new.metric", tmp_path / "bench")
    assert read({"x": 3.0}) == 3.0 and read({}) is None
    assert spec.driver(cell.config, tmp_path / "bench").__name__ \
        == "bench_driver_train"


@pytest.mark.parametrize("case", ["values", "chat_mix"])
def test_rank_group_swaps_only_neighbouring_ranks(case):
    """Every seed keeps one order of the sorted values and only permutes
    ranks within each group of ``RANK_GROUP``; in the chat mix, two seeds
    give the window's prompts in another order with no long prompt in a
    short one's place."""
    if case == "values":
        vals = np.arange(40) * 10
        a = TF.seeded_order(vals, 1, 7)
        b = TF.seeded_order(vals, 2, 7)
        assert sorted(a) == sorted(b) == list(vals)
        assert list(a) != list(b)
        # same place, same pair of neighbouring ranks {2j, 2j+1}
        assert TF.RANK_GROUP == 2 and np.all(a // 20 == b // 20)
        return
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    wa = _window(TF.requests(mix, 51.0, 11, 1000))
    wb = _window(TF.requests(mix, 51.0, 12, 1000))
    assert len(wa) == len(wb) == TF.window_count(mix, 51.0)
    la = np.array([len(r.prompt) for r in wa])
    lb = np.array([len(r.prompt) for r in wb])
    assert list(la) != list(lb)
    assert np.abs(la - lb).max() < 0.5 * la.max()

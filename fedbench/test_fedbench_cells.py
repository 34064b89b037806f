"""Whole runs of the cells at a size a CPU holds: the result line, and
``correct`` coming out false under the control and under each fault.

The configurations are the committed ones with the fleet cut to 64 agents,
2 envs each and short periods (widths as published); the limits are the
committed ones.
"""
import json
import os
import shutil
from unittest import mock

import pytest

from fedbench import faults, harness

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_CELLS = ("fleet1k-vpa.train", "fleet10k-cirl.train")
SERVE_CELL = "fleet10k-cirl.serve-p99"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def make_tiny(root):
    """A copy of the benchmark under ``root`` with every fleet cut down."""
    base = os.path.join(root, "fedbench")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(base, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "peaks.json"), base)
    bench = harness.load_benchmark()
    if SERVE_CELL not in [w["name"] for w in bench["workloads"]]:
        # The serving cell is built and checked but not yet a benchmark cell
        # (PERF.md): it runs here under the name it will have.
        bench["workloads"].append({"name": SERVE_CELL, "config": "fleet10k-cirl",
                                   "traffic": "poisson_0.8knee", "chips": 1,
                                   "why": "test"})
        bench["end_to_end"].append({"name": "decision_p90_ms", "unit": "ms",
                                    "better": "lower", "bound": 0.25,
                                    "source": "host_clock",
                                    "workloads": [SERVE_CELL]})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    for name in os.listdir(os.path.join(base, "configs")):
        path = os.path.join(base, "configs", name)
        cfg = harness.load_json(path)
        cfg.update(agents=64, envs_per_agent=2, epoch_len=20, minibatch=10,
                   tau=2, checked_calls=1)
        if "taus" in cfg["strategy"]:
            cfg["strategy"]["taus"] = {"lo": 1, "hi": 2, "seed": 0}
        if "serve" in cfg:
            cfg["serve"]["clients"] = 100
        _dump(path, cfg)
    for name in os.listdir(os.path.join(base, "traffic")):
        path = os.path.join(base, "traffic", name)
        if name.endswith(".json"):
            t = harness.load_json(path)
            if t["kind"] == "open_loop":
                t.update(rate_per_s=2000, warmup_s=0.5, close_wait_s=2)
            _dump(path, t)
    return root, base


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def run(tiny, cell, seed=2 ** 33 + 5, seconds=1):
    root, base = tiny
    with mock.patch.object(harness, "enable_compile_cache", lambda r: ""):
        return harness.run_cell(cell, seed, seconds, False, root=root,
                                base=base, require_accelerator=False)


@pytest.mark.parametrize("cell", TRAIN_CELLS + (SERVE_CELL,))
def test_sound_run_is_correct_with_the_five_keys(tiny, cell):
    line = run(tiny, cell)
    assert list(line) == list(harness.RESULT_KEYS) + ["compared"]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) == 2
    assert line["device"]["count"] == 1
    if cell == SERVE_CELL:   # due times are the window's own schedule
        assert 0 < line["metrics"]["decision_p90_ms"]["value"] < 2000


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_control_is_incorrect(tiny, cell):
    with faults.bf16_buffers():
        assert run(tiny, cell)["correct"] is False


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_fault_is_incorrect(tiny, cell, fault):
    with faults.TRAIN[fault]():
        assert run(tiny, cell)["correct"] is False


def test_serving_control_is_incorrect(tiny):
    with faults.fp8_decisions():
        assert run(tiny, SERVE_CELL)["correct"] is False


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
def test_serving_fault_is_incorrect(tiny, fault):
    with faults.SERVE[fault]():
        line = run(tiny, SERVE_CELL)
    assert line["correct"] is False


def test_cli_without_an_accelerator_prints_no_result(tmp_path):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd in (harness.ROOT, _bare_copy(tmp_path)):
        p = subprocess.run(
            [sys.executable, "fedbench/run.py", "--workload",
             "fleet1k-vpa.train", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=cwd, env=env, capture_output=True,
            text=True, timeout=120)
        assert p.returncode != 0
        assert p.stdout.strip() == ""


def _bare_copy(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    dst = tmp_path / "bare"
    shutil.copytree(HERE, dst / "fedbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), dst)
    return str(dst)

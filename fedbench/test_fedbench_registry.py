"""A configuration, a traffic mix, a generator and a per-layer metric written
as new files are found by name, with no edit to the harness."""
import json
import os
import textwrap

import pytest

from fedbench import harness

TOY_GENERATOR = """
    import jax.numpy as jnp

    from fedbench.harness import Check, Outcome


    def run(ctx):
        with ctx.window():
            with ctx.span("work"):
                float(jnp.sum(jnp.arange(8.0)))
        return Outcome(
            attempted=3, failed=0, setup_s=0.5,
            end_to_end={"toy_rate": float(ctx.cell.config["value"])},
            checks=[Check("toy_gap", 0.0, ctx.cell.traffic["limit"])],
            memory_peak_bytes=0, layer={"x": 2.0})
"""

TOY_METRIC = """
    def read(out, reduced, cell, peaks):
        return out.layer["x"] * peaks["scale"]
"""

SILENT_METRIC = """
    def read(out, reduced, cell, peaks):
        return None
"""


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    # Leave JAX's compile cache settings of the test process alone.
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")
    root = str(tmp_path)
    base = os.path.join(root, "fedbench")
    bench = {
        "workloads": [{"name": "toy.cell", "config": "toy", "traffic": "toy_mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "toy_rate", "unit": "x/s", "better": "higher",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            {"name": "elsewhere", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": ["other.cell"]}],
        "per_layer": [
            {"name": "toy.metric", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "toy", "moves": "toy_rate"},
            {"name": "silent", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "toy", "moves": "toy_rate",
             "workloads": ["toy.cell"]},
            {"name": "not_here", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "toy", "moves": "elsewhere"}],
    }
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(bench))
    _write(os.path.join(base, "configs", "toy.json"), json.dumps({"value": 7}))
    _write(os.path.join(base, "traffic", "toy_mix.json"),
           json.dumps({"kind": "toy", "limit": 0.1}))
    _write(os.path.join(base, "traffic", "toy.py"), TOY_GENERATOR)
    _write(os.path.join(base, "metrics", "toy.metric.py"), TOY_METRIC)
    _write(os.path.join(base, "metrics", "silent.py"), SILENT_METRIC)
    _write(os.path.join(base, "peaks.json"), json.dumps(
        {"source": "test", "devices": {"cpu": {"scale": 10.0}}}))
    return root, base


def test_metrics_of_follows_workloads_and_moves(toy_root):
    bench = harness.load_benchmark(toy_root[0])
    e2e, layer = harness.metrics_of(bench, "toy.cell")
    assert [e["name"] for e in e2e] == ["toy_rate", "setup_s"]
    assert [p["name"] for p in layer] == ["toy.metric", "silent"]


def test_untraced_line_has_the_five_keys_then_compared(toy_root):
    root, base = toy_root
    line = harness.run_cell("toy.cell", 5, 1, False, root=root, base=base,
                            require_accelerator=False)
    assert list(line) == list(harness.RESULT_KEYS) + ["compared"]
    assert line["correct"] is True
    assert line["metrics"] == {"toy_rate": {"value": 7.0, "unit": "x/s"},
                               "setup_s": {"value": 0.5, "unit": "s"}}
    assert line["device"]["count"] == 1
    assert line["compared"] == {"toy_gap": {"value": 0.0, "limit": 0.1}}
    json.dumps(line)


def test_traced_line_reports_per_layer_metrics_and_breakdown(toy_root):
    root, base = toy_root
    line = harness.run_cell("toy.cell", 5, 1, True, root=root, base=base,
                            require_accelerator=False)
    assert list(line) == list(harness.RESULT_KEYS) + ["breakdown", "compared"]
    # The silent metric found nothing to read and is left out.
    assert line["metrics"] == {"toy.metric": {"value": 20.0, "unit": "%"}}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_failed_check_makes_the_run_incorrect(toy_root):
    root, base = toy_root
    path = os.path.join(base, "traffic", "toy_mix.json")
    _write(path, json.dumps({"kind": "toy", "limit": -1.0}))
    line = harness.run_cell("toy.cell", 5, 1, False, root=root, base=base,
                            require_accelerator=False)
    assert line["correct"] is False


def test_unknown_device_kind_is_an_error(toy_root):
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99", toy_root[1])


def test_seed_key_uses_all_64_bits():
    import jax

    a = jax.random.key_data(harness.seed_key(5))
    b = jax.random.key_data(harness.seed_key(5 + 2 ** 32))
    assert (a != b).any()
    assert (jax.random.key_data(harness.seed_key(5)) == a).all()


CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")
FAMILIES = ("strategies", "topologies", "optimizers", "comm")


def _kinds(cfg):
    """The (family, kind) pairs a configuration names."""
    s = cfg["strategy"]
    out = [("strategies", s["kind"]), ("optimizers", cfg["optimizer"]["kind"])]
    if "topology" in s:
        out.append(("topologies", s["topology"]["kind"]))
    if cfg.get("comm"):
        out.append(("comm", cfg["comm"]["kind"]))
    return out


@pytest.mark.parametrize("name", sorted(os.listdir(CONFIGS)))
def test_every_kind_a_configuration_names_is_found_by_name(name):
    import importlib

    from fedbench.reference import fedrl as ref

    cfg = harness.load_json(os.path.join(CONFIGS, name))
    for family, kind in _kinds(cfg):
        assert ref.kind_module(family, kind) is not None
    assert callable(importlib.import_module(
        f"fedbench.strategies.{cfg['strategy']['kind']}").program)


@pytest.mark.parametrize("family", FAMILIES)
def test_an_unknown_kind_names_the_file_it_wants(family):
    from fedbench.reference import fedrl as ref

    with pytest.raises(SystemExit, match=f"{family}/no_such_kind.py"):
        ref.kind_module(family, "no_such_kind")


def test_a_new_topology_is_a_new_file(tmp_path, monkeypatch):
    import numpy as np

    import fedbench.reference.topologies as topologies
    from fedbench.reference.strategies import consensus

    _write(str(tmp_path / "toy_star.py"), """
        import numpy as np


        def neighbours(spec, m):
            idx = np.zeros((m, 2), int)
            idx[:, 0] = np.arange(m)
            valid = np.ones((m, 2), bool)
            valid[0, 1] = False     # the hub listens to no one
            return idx, valid
    """)
    monkeypatch.setattr(topologies, "__path__",
                        list(topologies.__path__) + [str(tmp_path)])
    cfg = {"agents": 4, "tau": 2, "comm": None,
           "strategy": {"kind": "consensus", "rounds": 1,
                        "eps_times_max_degree": 0.5,
                        "topology": {"kind": "toy_star"}}}
    s = consensus.Strategy(cfg)
    assert s.receives_per_period() == 3 * 2
    x = np.arange(4.0)[:, None]
    mixed, _ = s.direction(x, {}, 0)
    # Each leaf hears the hub with eps = 0.5 / (1 + 1); the hub keeps its own.
    np.testing.assert_allclose(np.asarray(mixed)[:, 0],
                               [0.0, 0.75, 1.5, 2.25])

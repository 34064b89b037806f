"""The training program's layer scopes, from the compiled program to the
per-layer metrics that read them in a trace.

* the program: ``run_fedrl_core`` compiled at a tiny size carries each scope
  in its ops' ``op_name``, the payload-width sorts under the encode, and the
  evaluation's ops under the evaluation alone;
* the reader: ``xplane_meta`` on a recorded v5e trace and on hand-made
  protobuf bytes; ``scopes`` on hand-made events and on a recorded training
  call (``fixtures/train_window.xplane.pb``, one call of
  ``fleet10k-cirl.train``).
"""
import os
import re
import shutil
import types

import numpy as np
import pytest

from fedbench import scopes as fs
from fedbench import trace as tr
from fedbench import xplane_meta
from fedbench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = os.path.join(HERE, "fixtures", "serve_window.xplane.pb")
TRAIN = os.path.join(HERE, "fixtures", "train_window.xplane.pb")
TRAIN_N = 9347                          # payload width of the recorded cell
METRICS = ("rollout_ms.train", "ppo_grad_ms.train", "local_step_ms.train",
           "sync_ms.train", "eval_ms.train", "payload_encode_ms.train",
           "unscoped_share.train")
MS_SCOPES = dict(zip(METRICS, fs.TOP + (fs.NESTED,)))


# --- the program -----------------------------------------------------------

def _program(case: str, m: int = 16):
    import jax

    import repro.comm
    from repro.core import make_strategy
    from repro.core.topology import knn_ring
    from repro.optim.flat import FlatOptimizer
    from repro.rl import FedRLConfig, make_fleet
    from repro.rl.scenarios import get_scenario

    if case == "periodic_adam":
        strat = make_strategy("periodic", tau=2, m=m, backend="jnp")
        opt = FlatOptimizer(kind="adam")
    else:
        topo = knn_ring(m, 4)
        strat = make_strategy("consensus", tau=2, m=m, topo=topo,
                              eps=0.5 / topo.max_degree, backend="jnp",
                              comm=repro.comm.topk(32))
        opt = None
    return FedRLConfig(
        env=get_scenario("figure_eight").cfg, strategy=strat, n_epochs=2,
        epoch_len=4, minibatch=2, optimizer=opt, num_envs=2,
        env_params=make_fleet("figure_eight", m, jax.random.key(1))[1])


def _compiled_ops(cfg):
    """``(result shape, op kind, op_name)`` of every instruction of the
    compiled program that carries an ``op_name``."""
    import jax

    from repro.rl.fedrl import run_fedrl_core

    text = jax.jit(lambda k: run_fedrl_core(cfg, k)).lower(
        jax.random.key(0)).compile().as_text()
    line = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\(.*"
                      r"op_name=\"([^\"]*)\"")
    return [m.groups() for m in map(line.match, text.splitlines()) if m]


@pytest.mark.parametrize("case,encodes", [("periodic_adam", False),
                                          ("consensus_topk", True)])
def test_scopes_reach_the_compiled_program(case, encodes):
    from repro.rl.fedrl import policy_payload_elems
    from repro.utils import scopes

    ops = _compiled_ops(_program(case))
    words = [set(re.findall(r"\w+", op_name)) for _, _, op_name in ops]
    top = {scopes.ROLLOUT, scopes.PPO_GRAD, scopes.LOCAL_STEP, scopes.SYNC,
           scopes.EVALUATE}
    found = set().union(*words) & (top | {scopes.PAYLOAD_ENCODE})
    # Dense payloads never encode: the periodic cell has the other five.
    assert found == top | ({scopes.PAYLOAD_ENCODE} if encodes else set())
    # Every sort over rows of the payload width is the encode's select.
    n = policy_payload_elems()
    sorts = [op_name for shape, kind, op_name in ops
             if kind == "sort" and f",{n}]" in shape]
    assert bool(sorts) == encodes
    assert all(scopes.PAYLOAD_ENCODE in s for s in sorts)
    # The outermost scope wins: the evaluation's rollout and gradient
    # count as evaluation, never as the training rollout.
    for w, (_, _, op_name) in zip(words, ops):
        if scopes.EVALUATE in w:
            assert fs.op_scopes(op_name)[0] == scopes.EVALUATE


def test_program_scopes_are_the_ones_the_reader_matches():
    from repro.utils import scopes

    assert (scopes.ROLLOUT, scopes.PPO_GRAD, scopes.LOCAL_STEP, scopes.SYNC,
            scopes.EVALUATE) == fs.TOP
    assert scopes.PAYLOAD_ENCODE == fs.NESTED


# --- the metadata reader -----------------------------------------------------

def test_recorded_trace_tf_ops():
    planes = xplane_meta.tf_ops(SERVE)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"]
    kernel = next(t for t in ops if tr.short_name(t) == "%policy_infer_pallas.1")
    assert ops[kernel] == "jit(step)/jit(policy_infer_pallas)/pallas_call:"
    done = next(t for t in ops if tr.short_name(t) == "%copy-done.1")
    assert ops[done] is None
    # Every op the trace reduction names has its metadata.
    r = tr.reduce_file(SERVE, [0])
    assert set(r.devices[0].texts) <= set(ops)


def _pb(num: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited for bytes."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def _meta(key: int, name: str, *stats) -> bytes:
    return _pb(4, _pb(1, key) + _pb(2, _pb(1, key) + _pb(2, name)
                                     + b"".join(_pb(5, s) for s in stats)))


def _stat_meta(key: int, name: str) -> bytes:
    return _pb(5, _pb(1, key) + _pb(2, _pb(1, key) + _pb(2, name)))


def test_hand_made_planes_str_ref_and_ambiguity(tmp_path):
    tf_op, other = 7, 8
    by_str = lambda s: _pb(1, tf_op) + _pb(5, s)
    by_ref = lambda ref: _pb(1, tf_op) + _pb(7, ref)
    plane = (_pb(1, 1) + _pb(2, "/device:TPU:0") + _pb(3, _pb(2, "XLA Ops"))
             + _meta(1, "%a = f32[2] add()", by_str("jit(f)/fed_sync/add:"))
             + _meta(2, "%b = f32[2] mul()", by_ref(9))
             + _meta(3, "%c = f32[2] neg()", by_str("jit(f)/x:"))
             + _meta(4, "%c = f32[2] neg()", by_str("jit(g)/y:"))
             + _meta(5, "%d = f32[2] copy()", _pb(1, other) + _pb(4, 3))
             + _stat_meta(tf_op, "tf_op") + _stat_meta(other, "flops")
             + _stat_meta(9, "jit(f)/fed_rollout/mul:"))
    host = _pb(2, "/host:CPU") + _pb(3, _pb(2, "python3"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, plane) + _pb(1, host) + _pb(4, "host0"))
    planes = xplane_meta.tf_ops(str(path))
    assert planes == {"/device:TPU:0": {
        "%a = f32[2] add()": "jit(f)/fed_sync/add:",
        "%b = f32[2] mul()": "jit(f)/fed_rollout/mul:",
        "%c = f32[2] neg()": None,           # two programs disagree
        "%d = f32[2] copy()": None}}         # no tf_op stat


# --- scope seconds -------------------------------------------------------------

def test_op_scopes_outermost_wins_and_encode_nests():
    assert fs.op_scopes("jit(job)/while/body/fed_rollout/add:") == (
        "fed_rollout", False)
    assert fs.op_scopes("jit(job)/fed_evaluate/closed_call/fed_rollout/x:") == (
        "fed_evaluate", False)
    assert fs.op_scopes("jit(job)/transpose(jvp(fed_ppo_grad))/dot:") == (
        "fed_ppo_grad", False)
    assert fs.op_scopes("jit(job)/fed_sync/cond/branch_1_fun/"
                        "fed_payload_encode/top_k:") == ("fed_sync", True)
    assert fs.op_scopes("jit(job)/fed_syncx/add:") == (None, False)
    assert fs.op_scopes(None) == (None, False)


def _hand_made():
    texts = ["%fusion.1 = f32[8] fusion()", "%fusion.2 = f32[8] fusion()",
             "%sort.3 = f32[4,9347] sort()", "%sort.4 = f32[4,9347] sort()",
             "%copy.5 = f32[8] copy()", "%while.6 = (f32[8]) while()",
             "%fusion.7 = f32[8] fusion()"]
    tf_ops = {texts[0]: "jit(job)/while/body/fed_rollout/add:",
              texts[1]: "jit(job)/fed_evaluate/fed_rollout/mul:",
              texts[2]: "jit(job)/fed_local_step/fed_payload_encode/top_k:",
              texts[3]: "jit(job)/fed_sync/cond/fed_payload_encode/top_k:",
              texts[4]: None,
              texts[5]: "jit(job)/fed_rollout/while:",
              texts[6]: "jit(job)/transpose(jvp(fed_ppo_grad))/dot:"}
    # ns, window [0, 1000): the while loop [100, 400) holds fusion.1 and
    # sort.3; fusion.2 starts before the window, copy.5 runs past its end.
    index = np.array([1, 5, 0, 2, 3, 6, 4])
    start = np.array([-40, 100, 100, 250, 500, 700, 900])
    dur = np.array([100, 300, 100, 150, 100, 50, 300])
    dev = tr.DeviceOps(texts, index, start, dur)
    return tr.Reduced(window=(0, 1000), devices=[dev], host=[]), tf_ops


def test_scope_seconds_on_hand_made_events():
    r, tf_ops = _hand_made()
    # Each op's own time: the while loop keeps the 50 ns between its body's
    # ops, in its own scope.
    assert list(fs.own_ns(r, r.devices[0])) == [60, 50, 100, 150, 100, 50, 100]
    secs = fs.scope_seconds(r, tf_ops)
    assert secs == pytest.approx({
        "fed_rollout": 150e-9, "fed_ppo_grad": 50e-9,
        "fed_local_step": 150e-9, "fed_sync": 100e-9, "fed_evaluate": 60e-9,
        "fed_payload_encode": 250e-9, "unscoped": 100e-9})
    # The top-level scopes and unscoped partition the busy time.
    top = sum(secs[k] for k in fs.TOP) + secs[fs.UNSCOPED]
    assert top == pytest.approx(r.busy_s, rel=1e-12)


def test_an_op_sharing_its_start_with_a_shorter_one_keeps_its_time():
    # A 1,000 ns fusion and a 10 ns event that starts with it: a leaf rule
    # takes the fusion for a parent and drops it; its own time is 990 ns.
    texts = ["%fusion.1 = f32[8] fusion()", "%copy-start.2 = f32[8] copy()"]
    dev = tr.DeviceOps(texts, np.array([0, 1]), np.array([100, 100]),
                       np.array([1000, 10]))
    r = tr.Reduced(window=(0, 2000), devices=[dev], host=[])
    secs = fs.scope_seconds(r, {texts[0]: "jit(job)/fed_ppo_grad/dot:"})
    assert secs["fed_ppo_grad"] == pytest.approx(990e-9)
    assert secs["unscoped"] == pytest.approx(10e-9)


def _in_trace_dir(tmp_path, monkeypatch, fixture):
    """The fixture where the harness leaves a run's trace."""
    d = tmp_path / "fedbench-trace-x" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(fixture, d / "host.xplane.pb")
    monkeypatch.setattr(fs.tempfile, "tempdir", str(tmp_path))


def _read(metric, reduced, periods=1):
    mod = load_module(os.path.join(HERE, "metrics", metric + ".py"),
                      "test_scopes_metric_" + metric.replace(".", "_"))
    out = types.SimpleNamespace(layer={"periods": periods})
    return mod.read(out, reduced, None, None)


def test_metrics_read_nothing_from_a_program_without_scopes(
        tmp_path, monkeypatch):
    _in_trace_dir(tmp_path, monkeypatch, SERVE)
    r = tr.reduce_file(SERVE, [0])
    assert fs.find_tf_ops(r) is not None
    assert [_read(m, r) for m in METRICS] == [None] * len(METRICS)


def test_trace_of_another_program_is_not_taken(tmp_path, monkeypatch):
    _in_trace_dir(tmp_path, monkeypatch, SERVE)
    r, _ = _hand_made()
    assert fs.find_tf_ops(r) is None


def test_recorded_training_call(tmp_path, monkeypatch):
    _in_trace_dir(tmp_path, monkeypatch, TRAIN)
    r = tr.reduce_file(TRAIN, [0])
    ops = fs.merged_tf_ops(TRAIN)
    secs = fs.scope_seconds(r, ops)
    assert all(secs[k] > 0 for k in fs.TOP + (fs.NESTED,))
    busy = sum(secs[k] for k in fs.TOP) + secs[fs.UNSCOPED]
    assert busy == pytest.approx(r.busy_s, rel=1e-9)
    # Every top-k select of the payload (a sort over rows of width n) is
    # under the encode.
    sorts = [t for t in r.devices[0].texts
             if tr.op_kind(t) == "sort" and f",{TRAIN_N}]" in t]
    assert sorts and all(fs.NESTED in ops[t] for t in sorts)
    # The metrics read the same numbers, per period (one call, one period).
    for metric, scope in MS_SCOPES.items():
        assert _read(metric, r) == pytest.approx(1e3 * secs[scope])
    assert _read("unscoped_share.train", r) == pytest.approx(
        100 * secs[fs.UNSCOPED] / busy, rel=1e-9)

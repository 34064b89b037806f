"""Each per-layer metric's FLOP and byte arithmetic against a hand count."""
import os
import types

import numpy as np
import pytest

from fedbench import roofline, trace as tr
from fedbench.harness import load_json, load_module

HERE = os.path.dirname(__file__)
PEAKS = load_json(os.path.join(HERE, "peaks.json"))["devices"]["TPU v5 lite"]


def metric(name):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "test_metric_" + name.replace(".", "_"))


def test_flat_adam_bytes_and_flops_by_hand():
    m = metric("flat_adam_roofline")
    # m=2, n=3: read p, g, mu, nu and write p, mu, nu: 7 buffers x 6 x 4 B.
    assert m.bytes_per_call(2, 3) == 7 * 6 * 4 == 168
    assert m.flops_per_call(2, 3) == 12 * 6


def test_consensus_gather_bytes_and_flops_by_hand():
    m = metric("consensus_gather_roofline")
    # m=16 rows of n=5, k_max=3 rows read per output row, one written.
    assert m.bytes_per_call(16, 5, 3) == (3 * 16 * 5 + 16 * 5) * 4 == 1280
    assert m.flops_per_call(16, 5, 3) == 2 * 3 * 16 * 5


def test_mfu_flops_per_env_step_by_hand():
    m = metric("mfu.train")
    policy = {"obs_dim": 2, "hidden": 3, "act_dim": 1}
    # actor 2*3 + 3*3 + 3*1 = 18 MACs, critic the same: 36 MACs = 72 FLOPs.
    assert m.forward_flops(policy) == 72
    cfg = {"policy": policy, "env": {"rl_indices": [0, 5]}}
    # rollout forward + update forward and backward (2x) = 4 forwards, x 2.
    assert m.flops_per_env_step(cfg) == 4 * 72 * 2
    full = {"policy": {"obs_dim": 6, "hidden": 64, "act_dim": 1},
            "env": {"rl_indices": list(range(0, 14, 2))}}
    assert m.flops_per_env_step(full) == 4 * 2 * 9088 * 7


def _reduced(texts, durs_ns):
    dev = tr.DeviceOps(list(texts), np.arange(len(texts)),
                       np.arange(len(texts)) * 10_000, np.asarray(durs_ns))
    return tr.Reduced(window=(0, 10 ** 9), devices=[dev], host=[])


def test_share_is_least_time_over_kernel_time():
    m_, n_ = 1024, 9347
    text = (f"%adam_update_pallas.3 = (f32[{m_},{n_}]) custom-call("
            f"f32[{m_},{n_}] %p), custom_call_target=\"tpu_custom_call\"")
    other = f"%fusion.9 = f32[{m_},{n_}] fusion(f32[{m_},{n_}] %x)"
    nbytes = 7 * m_ * n_ * 4
    least = nbytes / PEAKS["hbm_bytes_per_s"]
    r = _reduced([text, other, text], [int(2 * least * 1e9)] * 3)
    got = roofline.share(r, "adam_update_pallas", (m_, n_), 12 * m_ * n_,
                         nbytes, PEAKS)
    assert got == pytest.approx(50.0, rel=1e-5)  # durations are whole ns
    # A kernel that did not run reads nothing, never 0.
    assert roofline.share(r, "consensus_gather_pallas", (m_, n_), 1, 1,
                          PEAKS) is None


def test_topk_select_reads_only_payload_width_sorts():
    m = metric("topk_select_ms")
    n = 9347
    texts = [f"%sort.58 = (f32[10000,{n}]) sort(f32[10000,{n}] %a)",
             "%sort.3 = f32[80000,14] sort(f32[80000,14] %x)",
             "%fusion.1 = f32[10] fusion(...)"]
    r = _reduced(texts, [3_000_000, 500_000, 1_000])
    cell = types.SimpleNamespace(config={"policy": {"params": n}})
    out = types.SimpleNamespace(layer={"periods": 2})
    assert m.read(out, r, cell, PEAKS) == pytest.approx(1.5)


def test_collectives_per_period():
    m = metric("collective_ms.train4")
    texts = ["%all-reduce.1 = f32[9347] all-reduce(...)",
             "%all-gather-start.2 = f32[9347] all-gather-start(...)",
             "%fusion.1 = f32[10] fusion(...)"]
    r = _reduced(texts, [2_000_000, 1_000_000, 5_000_000])
    out = types.SimpleNamespace(layer={"periods": 3})
    assert m.read(out, r, None, PEAKS) == pytest.approx(1.0)


def test_serving_readers():
    out = types.SimpleNamespace(layer={"decide_ms": [1.0, 3.0, 2.0],
                                       "decisions": 300, "padded": 100})
    assert metric("decide_ms.serve").read(out, None, None, PEAKS) == 2.0
    assert metric("batch_fill.serve").read(out, None, None, PEAKS) == 75.0
    empty = types.SimpleNamespace(layer={"decide_ms": [], "decisions": 0,
                                         "padded": 0})
    assert metric("decide_ms.serve").read(empty, None, None, PEAKS) is None
    assert metric("batch_fill.serve").read(empty, None, None, PEAKS) is None

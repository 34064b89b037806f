"""The trace reduction, on a recorded TPU trace and on hand-made events."""
import os

import numpy as np
import pytest

from fedbench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "serve_window.xplane.pb")


def _raw_events():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(FIXTURE)
    dev, host = [], []
    for p in pd.planes:
        for line in p.lines:
            for e in line.events:
                row = (e.name, int(e.start_ns), int(e.duration_ns))
                if p.name == "/device:TPU:0" and line.name == "XLA Ops":
                    dev.append(row)
                elif p.name == "/host:CPU":
                    host.append(row)
    return dev, host


def _naive_busy(intervals, lo, hi):
    """Union length of [s, e) intervals clipped to [lo, hi), one by one."""
    total, reach = 0, lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= reach:
            continue
        total += e - max(s, reach)
        reach = e
    return total


def test_recorded_trace_window_busy_and_ops():
    dev, host = _raw_events()
    r = tr.reduce_file(FIXTURE, [0])
    (w0, w1), = [(s, s + d) for n, s, d in host if n == "window"]
    assert r.window == (w0, w1)
    assert r.window_s == pytest.approx(3.003997032, abs=0)
    assert len(dev) == 46 and len(r.devices) == 1
    busy_ns = _naive_busy([(s, s + d) for _, s, d in dev], w0, w1)
    assert r.busy_s == pytest.approx(busy_ns * 1e-9, rel=1e-12)
    assert r.idle_share() == pytest.approx(
        100 * (1 - busy_ns / (w1 - w0)), rel=1e-12)
    # Per-op time: both runs of the serving kernel, by short name.
    ops = r.op_seconds()
    assert ops["%policy_infer_pallas.1"] == pytest.approx((337 + 461) * 1e-9)
    stats = r.op_stats(lambda t: "policy_infer_pallas" in t.split(" = ")[0])
    assert sorted(c for _, c, _ in stats) == [1, 1]
    assert sum(s for _, _, s in stats) == pytest.approx(798e-9)


def test_no_device_of_the_cell_means_no_ops():
    r = tr.reduce_file(FIXTURE, [3])
    assert r.devices == [] and r.busy_s == 0.0


def _synthetic():
    texts = ["%fusion.1 = f32[8,128] fusion(...)",
             "%sort.2 = f32[4,9347] sort(...)",
             "%all-reduce.3 = f32[9347] all-reduce(...)",
             "%while.4 = (f32[8,128]) while(...)"]
    # ns, window [0, 1000): one op starts before the window, one runs past
    # its end, and a while loop [100, 300) holds a fusion and a sort.
    index = np.array([0, 3, 0, 1, 2, 1])
    start = np.array([-50, 100, 100, 200, 600, 950])
    dur = np.array([100, 200, 80, 100, 100, 200])
    dev = tr.DeviceOps(texts, index, start, dur)
    host = [("window", 0, 1000), ("dispatch", 0, 40), ("block", 320, 590),
            ("decide", 700, 900)]
    return tr.Reduced(window=(0, 1000), devices=[dev], host=host)


def test_synthetic_busy_gaps_and_attribution():
    r = _synthetic()
    # busy: [0,50) + [100,300) + [600,700) + [950,1000) = 50+200+100+50
    assert r.busy_s == pytest.approx(400e-9)
    assert r.idle_share() == pytest.approx(60.0)
    # Leaf ops only, clipped to the window: the while loop is left out.
    assert r.op_seconds() == pytest.approx(
        {"%fusion.1": 130e-9, "%sort.2": 150e-9, "%all-reduce.3": 100e-9})
    # Gaps: [50,100) mid 75 none; [300,600) mid 450 block;
    # [700,950) mid 825 decide.
    assert r.idle_gaps() == pytest.approx(
        {"none": 50e-9, "block": 300e-9, "decide": 250e-9})
    # Events starting inside the window, full durations.
    stats = {tr.short_name(t): (c, s) for t, c, s in
             r.op_stats(lambda t: True)}
    assert stats["%sort.2"] == (2, pytest.approx(300e-9))
    assert stats["%fusion.1"] == (1, pytest.approx(80e-9))
    assert stats["%while.4"] == (1, pytest.approx(200e-9))
    b = r.breakdown()
    assert b["device_ops"][0] == ["%sort.2", pytest.approx(150e-9)]
    assert b["idle_gaps"][0][0] == "block"


def test_short_name_and_kind():
    text = "%sort.58 = (f32[10000,9347]) sort(f32[10000,9347] %x)"
    assert tr.short_name(text) == "%sort.58"
    assert tr.op_kind(text) == "sort"

"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device planes ``/device:TPU:<id>`` of the devices the cell used, their
  ``XLA Ops`` line: one event per operation run, named by its HLO text
  (``%fusion.12 = f32[...] fusion(...)``); the op's short name is the text
  before `` = ``;
* the host plane ``/host:CPU``: the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), on the line of the thread that holds
  the ``window`` span.

What comes out (all times in seconds, device times averaged over the
devices used):

* ``window_s``: the length of the ``window`` span;
* ``busy_s``: the union of the device's op intervals inside the window;
* per-op device time, and the device time of any set of ops;
* idle gaps inside the window, each named by the innermost host span of
  the window's thread that covers its midpoint (``none`` where none does).

Host and device events share the trace's clock (nanoseconds).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


def short_name(text: str) -> str:
    """``%sort.58`` from ``%sort.58 = (f32[...]) sort(...)``."""
    return text.split(" = ", 1)[0].strip()


def op_kind(text: str) -> str:
    """The op's name without its numeric suffix: ``%sort.58`` -> ``sort``."""
    return re.sub(r"\.\d+$", "", short_name(text)).lstrip("%")


@dataclasses.dataclass
class DeviceOps:
    """One device's ops: HLO texts (deduplicated) and, per event, the text's
    index, start and duration (ns)."""

    texts: list
    index: np.ndarray
    start: np.ndarray
    dur: np.ndarray


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start_ns, end_ns)
    devices: list                 # [DeviceOps]
    host: list                    # [(name, start_ns, end_ns)] window's thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, d: DeviceOps):
        s = np.clip(d.start, *self.window)
        e = np.clip(d.start + d.dur, *self.window)
        return s, e

    def busy_intervals(self, d: DeviceOps) -> np.ndarray:
        """Union of the device's op intervals inside the window, (k, 2) ns."""
        s, e = self._clipped(d)
        keep = e > s
        s, e = s[keep], e[keep]
        if s.size == 0:
            return np.zeros((0, 2), np.int64)
        order = np.argsort(s, kind="stable")
        s, e = s[order], e[order]
        reach = np.maximum.accumulate(e)
        new = np.r_[True, s[1:] > reach[:-1]]
        starts = s[new]
        ends = np.r_[reach[np.flatnonzero(new)[1:] - 1], reach[-1]]
        return np.stack([starts, ends], axis=1)

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        total = sum(float(np.sum(b[:, 1] - b[:, 0]))
                    for b in map(self.busy_intervals, self.devices))
        return total * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        """Percent of the window in which the device ran no op."""
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self, select=None) -> dict:
        """Device seconds inside the window per op short name (averaged over
        devices), for the ops whose HLO text ``select(text)`` accepts. Only
        leaf ops count: an op whose interval holds others (a ``while`` loop
        around its body's ops) is left out, so no time counts twice."""
        out = collections.Counter()
        for d in self.devices:
            s, e = self._clipped(d)
            leaf = _leaves(d)
            per_text = np.bincount(d.index[leaf],
                                   weights=(e - s)[leaf].astype(np.float64),
                                   minlength=len(d.texts))
            for i in np.flatnonzero(per_text):
                text = d.texts[i]
                if select is None or select(text):
                    out[short_name(text)] += per_text[i] * 1e-9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def op_stats(self, select) -> list:
        """``(text, count, seconds)`` per HLO text that ``select`` accepts,
        over the events that start inside the window, on all devices."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for d in self.devices:
            inside = (d.start >= self.window[0]) & (d.start < self.window[1])
            count = np.bincount(d.index[inside], minlength=len(d.texts))
            secs = np.bincount(d.index[inside], weights=d.dur[inside] * 1e-9,
                               minlength=len(d.texts))
            for i in np.flatnonzero(count):
                if select(d.texts[i]):
                    out[d.texts[i]][0] += int(count[i])
                    out[d.texts[i]][1] += float(secs[i])
        return [(t, c, s) for t, (c, s) in out.items()]

    def idle_gaps(self) -> dict:
        """Idle seconds inside the window by the host span covering each gap
        (averaged over devices)."""
        out = collections.Counter()
        starts = np.array([h[1] for h in self.host], np.int64)
        for d in self.devices:
            b = self.busy_intervals(d)
            edges = np.r_[self.window[0], b.ravel(), self.window[1]]
            gaps = edges.reshape(-1, 2)
            gaps = gaps[gaps[:, 1] > gaps[:, 0]]
            for g0, g1 in gaps:
                out[self._host_at((g0 + g1) // 2, starts)] += (g1 - g0) * 1e-9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def _host_at(self, t: int, starts: np.ndarray, look_back: int = 64) -> str:
        """The innermost host span of the window's thread covering ``t``: the
        latest-starting one, among the ``look_back`` spans that start last
        before ``t`` (spans of one thread nest)."""
        i = int(np.searchsorted(starts, t, side="right")) - 1
        for j in range(i, max(i - look_back, -1), -1):
            name, s, e = self.host[j]
            if s <= t < e and name != WINDOW_SPAN:
                return name
        return "none"

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": [[k, float(v)] for k, v in gaps]}


def _leaves(d: DeviceOps) -> np.ndarray:
    """Mask of the events that hold no other event of the same line."""
    order = np.lexsort((-d.dur, d.start))
    s, e = d.start[order], d.start[order] + d.dur[order]
    parent = np.r_[s[1:] < e[:-1], False]
    leaf = np.empty(len(order), bool)
    leaf[order] = ~parent
    return leaf


def reduce_file(path: str, device_ids) -> Reduced:
    """Reduce one ``.xplane.pb`` for the devices with ``device_ids``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    wanted = {int(i) for i in device_ids}
    devices, host_lines = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in wanted:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = _device_ops(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_lines.append([(e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                   for e in line.events])
    host = next((l for l in host_lines
                 if any(n == WINDOW_SPAN for n, _, _ in l)), [])
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if spans:
        window = spans[0]
    else:
        ends = [(d.start.min(), (d.start + d.dur).max())
                for d in devices.values() if d.start.size]
        window = (min(a for a, _ in ends), max(b for _, b in ends))
    host.sort(key=lambda h: h[1])
    return Reduced(window=(int(window[0]), int(window[1])),
                   devices=[devices[i] for i in sorted(devices)], host=host)


def _device_ops(line) -> DeviceOps:
    ids, texts, index, start, dur = {}, [], [], [], []
    for e in line.events:
        name = e.name
        i = ids.get(name)
        if i is None:
            i = ids[name] = len(texts)
            texts.append(name)
        index.append(i)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    return DeviceOps(texts, np.asarray(index, np.int64),
                     np.asarray(start, np.float64).astype(np.int64),
                     np.asarray(dur, np.float64).astype(np.int64))


def reduce_dir(trace_dir: str, device_ids) -> Reduced:
    """Reduce the one trace that ``jax.profiler`` wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise SystemExit(f"fedbench: expected one trace under {trace_dir}, "
                         f"found {len(found)}")
    return reduce_file(found[0], device_ids)

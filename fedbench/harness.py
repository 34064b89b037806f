"""The benchmark's harness: finds a cell's files by name and assembles a run.

Everything a cell is made of is found by the names in ``BENCHMARK.json``,
so a new configuration, traffic mix or per-layer metric is a new file and
no edit:

    configs/<config>.json      the deployment: sizes, strategy, precision
    traffic/<traffic>.json     the mix's parameters; its ``kind`` names ...
    traffic/<kind>.py          ... the generator that drives the system
    metrics/<metric>.py        one per-layer metric: ``read(ctx)``
    peaks.json                 the chip's published peaks, by device kind

A generator's ``run(ctx)`` returns an :class:`Outcome`. The harness turns
it into the one JSON line the driver reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


# --- finding a cell's files ---------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"fedbench: no workload {name!r} in BENCHMARK.json "
                     f"(have {[c['name'] for c in bench['workloads']]})")


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise SystemExit(f"fedbench: cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell_name: str):
    """``(end_to_end, per_layer)`` entries that this cell reports.

    A metric with a ``workloads`` key is reported where it lists the cell.
    A per-layer metric without one is reported wherever the end-to-end
    metric it moves is; an end-to-end metric without one, everywhere.
    """
    def listed(entry):
        return "workloads" not in entry or cell_name in entry["workloads"]

    e2e = [e for e in bench["end_to_end"] if listed(e)]
    names = {e["name"] for e in e2e}
    per_layer = [p for p in bench["per_layer"]
                 if (listed(p) if "workloads" in p else p["moves"] in names)]
    return e2e, per_layer


@dataclasses.dataclass
class Cell:
    """A cell with its configuration and traffic read from their files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    base: str = HERE

    @classmethod
    def load(cls, name: str, root: str = ROOT, base: str = HERE) -> "Cell":
        bench = load_benchmark(root)
        entry = find_cell(bench, name)
        e2e, per_layer = metrics_of(bench, name)
        return cls(
            name=name, chips=int(entry["chips"]),
            config=load_json(os.path.join(base, "configs",
                                          entry["config"] + ".json")),
            traffic=load_json(os.path.join(base, "traffic",
                                           entry["traffic"] + ".json")),
            end_to_end=e2e, per_layer=per_layer, base=base)

    def generator(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(self.base, "traffic", kind + ".py"),
                           f"fedbench_traffic_{kind}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.base, "metrics", metric + ".py"),
                           "fedbench_metric_" + metric.replace(".", "_"))


def peaks_for(device_kind: str, base: str = HERE) -> dict:
    table = load_json(os.path.join(base, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"fedbench: no peaks for device kind {device_kind!r} "
                         f"in peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


# --- what a generator hands back ----------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, with its limit (value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict                     # name -> value, host clock
    checks: list                         # [Check]
    memory_peak_bytes: int
    layer: dict = dataclasses.field(default_factory=dict)  # readers' inputs


@dataclasses.dataclass
class Context:
    """What a generator is given: the cell, the seed and the devices."""

    cell: Cell
    seed: int
    seconds: int
    trace: bool
    devices: list
    t_start: float = 0.0
    trace_dir: Optional[str] = None

    def setup_s(self) -> float:
        """Seconds from the process's start (or the run's) until now."""
        return time.perf_counter() - self.t_start

    def note(self, stage: str, **fields) -> None:
        """A diagnostic line on standard error: ``[stage] key=value ...``."""
        print(f"[{stage}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
              file=sys.stderr, flush=True)

    def span(self, name: str):
        """A host span in the trace (``--trace 1``); otherwise a no-op."""
        import jax

        return (jax.profiler.TraceAnnotation(name) if self.trace
                else contextlib.nullcontext())

    @contextlib.contextmanager
    def window(self):
        """The measured window; profiled when ``--trace 1``. The host span
        ``window`` marks it in the trace."""
        import jax

        if not self.trace:
            yield
            return
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # a span per Python call stalls a
        options.host_tracer_level = 2     # per-request host loop
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield
        finally:
            jax.profiler.stop_trace()


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits: the low word seeds the key
    and the high word is folded in, so seeds that differ only above bit 32
    still draw different inputs."""
    import jax

    if not 0 <= seed < 2 ** 64:
        raise SystemExit(f"fedbench: --seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


# --- the compile cache and the device -----------------------------------------

def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (``.jax_cache/`` at its root), or where ``JAX_COMPILATION_CACHE_DIR``
    says. Every program is cached, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def accelerator_devices(chips: int, require: bool = True) -> list:
    """The first ``chips`` accelerator devices; exits 1 (no result) where
    JAX finds no accelerator or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if require and devices[0].platform not in ("tpu", "gpu"):
        raise SystemExit(f"fedbench: no accelerator (JAX's first device is "
                         f"{devices[0].platform!r}); nothing is measured")
    if len(devices) < chips:
        raise SystemExit(f"fedbench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    """Peak device memory of the fullest of ``devices``: the allocator's
    peak of buffers in use plus its peak reserved for compiled programs'
    temporaries (on the TPU these are counted apart). 0 where the backend
    keeps no such count."""
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))


class CompileCounter:
    """Counts XLA backend compiles while active (JAX monitoring events)."""

    _installed = False
    _active: list = []

    def __enter__(self):
        import jax.monitoring

        if not CompileCounter._installed:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_event)
            CompileCounter._installed = True
        self.count = 0
        CompileCounter._active.append(self)
        return self

    def __exit__(self, *exc):
        CompileCounter._active.remove(self)

    @staticmethod
    def _on_event(event: str, duration: float, **kw) -> None:
        if "backend_compile" in event:
            for c in CompileCounter._active:
                c.count += 1


# --- one run ------------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: int, trace: bool, *,
             root: str = ROOT, base: str = HERE,
             require_accelerator: bool = True,
             t_start: Optional[float] = None) -> dict:
    """Run one cell once and return the result line as a dict. Set-up is
    timed from ``t_start`` (``time.perf_counter``), by default this call."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell.load(name, root=root, base=base)
    enable_compile_cache(root)
    devices = accelerator_devices(cell.chips, require=require_accelerator)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  devices=devices, t_start=t_start)
    with tempfile.TemporaryDirectory(prefix="fedbench-trace-") as tdir:
        ctx.trace_dir = tdir
        ctx.note("setup", cell=name, seed=seed, seconds=seconds,
                 trace=int(trace), devices=len(devices),
                 kind=devices[0].device_kind,
                 import_s=f"{time.perf_counter() - t_start:.3f}")
        out = cell.generator().run(ctx)
        layer_values, device_extra, breakdown = {}, {}, None
        if trace:
            from fedbench import trace as tr

            reduced = tr.reduce_dir(tdir, [d.id for d in devices])
            device_extra = {"busy_s": reduced.busy_s,
                            "window_s": reduced.window_s}
            breakdown = reduced.breakdown()
            peaks = peaks_for(devices[0].device_kind, base)
            for entry in cell.per_layer:
                value = cell.reader(entry["name"]).read(
                    out, reduced, cell, peaks)
                if value is not None:
                    layer_values[entry["name"]] = (value, entry["unit"])
    line = result_line(cell, out, devices, layer_values if trace else None,
                       device_extra, breakdown)
    for c in out.checks:
        print(f"compared {c.name} = {c.value!r} (limit {c.limit!r})",
              file=sys.stderr, flush=True)
    return line


def result_line(cell: Cell, out: Outcome, devices, layer_values,
                device_extra: dict, breakdown) -> dict:
    """The driver's line: end-to-end metrics with ``--trace 0``, per-layer
    ones with ``--trace 1``; the numbers compared come last."""
    if layer_values is None:
        units = {e["name"]: e["unit"] for e in cell.end_to_end}
        values = dict(out.end_to_end, setup_s=out.setup_s)
        metrics = {k: {"value": values[k], "unit": units[k]} for k in units}
    else:
        metrics = {k: {"value": v, "unit": u}
                   for k, (v, u) in layer_values.items()}
    d0 = devices[0]
    line = {
        "correct": bool(out.failed == 0 and all(c.ok for c in out.checks)),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": metrics,
        "device": dict({"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": int(out.memory_peak_bytes)},
                       **device_extra),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return line

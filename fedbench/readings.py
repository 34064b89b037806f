"""Read the numbers that ``correct`` compares, on many seeds, without a window.

    python fedbench/readings.py --workload fleet1k-vpa.train --seed 100 \\
        --sound 12 --control 3 --faults 3

Training cells: for each seed the job is built and driven through its
checked calls as in a run, then compared with the reference:

* ``sound``    the program as the configuration states it;
* ``control``  the program's own lower-precision path, ``buffer_dtype =
               "bfloat16"`` (``fedbench.faults.bf16_buffers``);
* ``half_batch``, on a gossip topology ``gossip_weight``, and on several
               chips ``no_exchange``: the program with that fault planted
               (``fedbench.faults``); ``frozen`` needs no run, it reads 1 on
               ``change_gap`` by construction.

``--also highest`` compares the sound and ``gossip_weight`` runs with the
reference at that precision too, beside the configured one.

Serving cells: for each seed, the decisions that the float8-operand
reference (``decide_fp8``, the control) and the program's engine give for
the window's observations, each against the float32 reference.

Prints one JSON line per reading: variant, seed and the numbers. The limits
in ``configs/<config>.json`` were set from such readings (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train_reading(cell, seed: int, precisions=()) -> dict:
    """Every number that can be compared for one seed; and the same against
    the reference at each of ``precisions`` besides the configured one,
    under keys suffixed ``@<precision>``."""
    from fedbench.traffic import train_periods as tp

    ctx = types.SimpleNamespace(cell=cell, seed=seed)
    trainer = tp.Trainer.build(ctx)
    outs = trainer.run_checked()
    bytes_prog = trainer.period_bytes()
    del trainer.call, trainer.env_params
    out = {}
    for prec in (None,) + tuple(precisions):
        cfg = dict(cell.config)
        if prec is not None:
            cfg["matmul_precision"] = prec
        at = "" if prec is None else "@" + prec
        got = tp.numbers(cfg, cell.traffic, trainer, outs, bytes_prog)
        out.update({k + at: v for k, v in got.items()})
    return out


def serve_reading(cell, seed: int, seconds: int) -> dict:
    import jax

    from fedbench.harness import seed_key
    from fedbench.reference import serve as ref
    from fedbench.traffic import open_loop
    from repro.serve import ServeEngine

    cfg = cell.config
    times, _, obs = open_loop.schedule(cfg, cell.traffic, seed, seconds)
    lo = int(np.searchsorted(times, cell.traffic["warmup_s"]))
    obs = obs[lo:]
    pi = jax.jit(lambda k: ref.make_pi(k, cfg["policy"]))(seed_key(seed))
    expect = ref.decisions(pi, obs)
    control = ref.decisions(pi, obs, fn=ref.decide_fp8)
    engine = ServeEngine({"pi": pi}, buckets=tuple(cfg["serve"]["buckets"]),
                         mode=cfg["serve"]["mode"], backend=cfg["backend"])
    top = engine.max_batch()
    served = np.concatenate([engine.decide(obs[i:i + top])
                             for i in range(0, obs.shape[0], top)])
    return {"control_gap": float(np.max(np.abs(control - expect))),
            "engine_gap": float(np.max(np.abs(served - expect))),
            "decisions": int(obs.shape[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True, help="first seed")
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--also", default="",
                    help="comma-separated matmul precisions to compare the "
                         "sound and gossip_weight runs against besides "
                         "the configured one")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from fedbench import faults
    from fedbench.harness import Cell, accelerator_devices, enable_compile_cache

    cell = Cell.load(args.workload)
    enable_compile_cache(ROOT)
    accelerator_devices(cell.chips)
    seeds = iter(range(args.seed, args.seed + 10_000))

    def emit(variant, seed, numbers):
        print(json.dumps({"variant": variant, "seed": seed, **numbers}),
              flush=True)

    if cell.traffic["kind"] == "open_loop":
        for _ in range(args.control):
            s = next(seeds)
            emit("control", s, serve_reading(cell, s, args.seconds))
        return 0
    also = tuple(p for p in args.also.split(",") if p)
    for _ in range(args.sound):
        s = next(seeds)
        emit("sound", s, train_reading(cell, s, also))
    for _ in range(args.control):
        s = next(seeds)
        with faults.bf16_buffers():
            emit("control", s, train_reading(cell, s))
    planted = dict(faults.TRAIN)
    if cell.config["strategy"]["kind"] == "consensus":
        planted.update(faults.GOSSIP)
    if cell.chips > 1:
        planted.update(faults.SHARDED)
    planted.pop("frozen")   # reads 1 on change_gap by construction
    for name, fault in planted.items():
        for _ in range(args.faults):
            s = next(seeds)
            with fault():
                emit(name, s, train_reading(
                    cell, s, also if name == "gossip_weight" else ()))
    return 0


if __name__ == "__main__":
    sys.exit(main())

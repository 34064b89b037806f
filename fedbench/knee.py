"""Find the serving cell's knee: the highest rate the server sustains.

    python fedbench/knee.py --config fleet10k-cirl --rates 100000 140000 \\
        --warmup 5 --seconds 8 --seed 1

For each offered rate, in one process and on one engine: a fresh queue and
schedule (the ``open_loop`` generator's), ``--warmup`` seconds served
unmeasured, then ``--seconds`` measured in slices of ``--slice`` seconds.
Per slice it prints the decisions answered per second, p50/p99/max of the
requests due in the slice that were answered, the backlog at the slice's
end, the longest host gap of the serving loop and the garbage collections
(with generation 2 separately). A rate is sustained when the backlog stays
flat and the answered rate equals the offered one. Used once, when the
serving cell's rate was fixed; the cell's rate is 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="fleet10k-cirl")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--warmup", type=float, default=5.0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--slice", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from fedbench.harness import (accelerator_devices, enable_compile_cache,
                                  load_json, seed_key)
    from fedbench.reference import serve as ref
    from fedbench.traffic import open_loop
    from repro.serve import MicroBatchQueue, ServeEngine

    enable_compile_cache(ROOT)
    accelerator_devices(1)
    cfg = load_json(os.path.join(ROOT, "fedbench", "configs",
                                 args.config + ".json"))
    serve = cfg["serve"]
    pi = jax.jit(lambda k: ref.make_pi(k, cfg["policy"]))(seed_key(args.seed))
    engine = ServeEngine({"pi": pi}, buckets=tuple(serve["buckets"]),
                         mode=serve["mode"], backend=cfg["backend"])
    for rate in args.rates:
        traffic = {"rate_per_s": rate, "warmup_s": args.warmup}
        times, ids, obs = open_loop.schedule(cfg, traffic, args.seed,
                                             args.seconds)
        queue = MicroBatchQueue(engine.max_batch(), engine.obs_dim)
        loop = open_loop.OpenLoop(engine, queue, times, ids, obs,
                                  lambda name: contextlib.nullcontext())
        gcs = []
        cb = lambda phase, info: gcs.append((info["generation"], phase,
                                             time.perf_counter()))
        loop.run(until=args.warmup)
        gc.callbacks.append(cb)
        t = args.warmup
        while t < args.warmup + args.seconds - 1e-9:
            loop.max_gap_s = 0.0
            n_gc = len(gcs)
            loop.run(until=t + args.slice)
            lo, hi = np.searchsorted(times, [t, t + args.slice])
            done = loop.done_at[lo:hi]
            ok = ~np.isnan(done)
            lat = 1e3 * (done[ok] - times[lo:hi][ok])
            answered = np.sum((loop.done_at >= t) & (loop.done_at < t + args.slice))
            print(json.dumps({
                "rate": rate, "slice_start_s": t,
                "answered_per_s": float(answered / args.slice),
                "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
                "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
                "max_ms": float(lat.max()) if lat.size else None,
                "backlog": int(loop.next - np.count_nonzero(~np.isnan(loop.done_at))),
                "max_loop_gap_ms": 1e3 * loop.max_gap_s,
                "gc": len(gcs) - n_gc,
                "gc_gen2": sum(1 for g, p, _ in gcs[n_gc:] if g == 2 and p == "start"),
            }), flush=True)
            t += args.slice
        gc.callbacks.remove(cb)
    return 0


if __name__ == "__main__":
    sys.exit(main())

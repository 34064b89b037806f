"""Run one benchmark cell once on the chips of this machine.

    python fedbench/run.py --workload fleet1k-vpa.train --seed 7 \\
        --seconds 10 --trace 0

Prints diagnostics on standard error and, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``: each
number compared for ``correct`` with its limit. Exits 1 without a result
where JAX finds no accelerator or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)

    from fedbench.harness import run_cell

    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

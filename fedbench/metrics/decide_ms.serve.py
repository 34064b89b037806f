"""``decide_ms.serve``: median host time of one ``ServeEngine.decide`` call
(pad, run the bucket's program, fetch the decisions) over the calls made in
the traced window."""
import statistics

LAYER = "serving engine"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "decision_p90_ms"


def read(out, reduced, cell, peaks):
    calls = out.layer["decide_ms"]
    return statistics.median(calls) if calls else None

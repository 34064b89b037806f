"""``eval_ms.train``: the per-epoch evaluation (server read, a P-step fleet
rollout and a gradient) and the job's final server read: device work
that ``env_steps_per_s`` does not count.

Device ms per federation period of the ops under the program's
``fed_evaluate`` scope (``fedbench.scopes``): each op's own time in the
traced window, averaged over the devices, over the periods the window
completed.
"""
from fedbench import scopes

LAYER = "driver"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_evaluate")

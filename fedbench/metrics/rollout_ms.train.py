"""``rollout_ms.train``: the fleet rollout: the per-epoch env resets and
each local update's P-step experience collection (env step, policy
sampling, values).

Device ms per federation period of the ops under the program's
``fed_rollout`` scope (``fedbench.scopes``): each op's own time in the
traced window, averaged over the devices, over the periods the window
completed.
"""
from fedbench import scopes

LAYER = "rollout"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_rollout")

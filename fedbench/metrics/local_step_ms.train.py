"""``local_step_ms.train``: the strategy's local step on the flat carry:
optimizer kernel, consensus gather and, inclusive, the gossip payload's
encode.

Device ms per federation period of the ops under the program's
``fed_local_step`` scope (``fedbench.scopes``): each op's own time in
the traced window, averaged over the devices, over the periods the
window completed.
"""
from fedbench import scopes

LAYER = "strategy"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_local_step")

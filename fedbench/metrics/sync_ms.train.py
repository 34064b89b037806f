"""``sync_ms.train``: the period-boundary server sync (the strategy's flat
sync and the optimizer state's average), inclusive of the uplink
payload's encode.

Device ms per federation period of the ops under the program's
``fed_sync`` scope (``fedbench.scopes``): each op's own time in the
traced window, averaged over the devices, over the periods the window
completed.
"""
from fedbench import scopes

LAYER = "sync"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_sync")

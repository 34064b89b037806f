"""``payload_encode_ms.train``: the payload compression (top-k threshold
select, keep mask, residual), nested inside the local step (gossip) and
the sync (uplink).

Device ms per federation period of the ops under the program's
``fed_payload_encode`` scope (``fedbench.scopes``): each op's own time
in the traced window, averaged over the devices, over the periods the
window completed.
"""
from fedbench import scopes

LAYER = "payload encode"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_payload_encode")

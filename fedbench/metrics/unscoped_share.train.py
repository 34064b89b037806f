"""``unscoped_share.train``: percent of the devices' busy time in the
traced window under none of the program's top-level scopes
(``fedbench.scopes``): XLA's own copies, ops outside every layer, ops of
other programs. A guard on the scopes' coverage; reads nothing where the
program names no scope.
"""
from fedbench import scopes

LAYER = "driver"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    secs = scopes.seconds_of(reduced)
    if not secs:
        return None
    total = sum(secs[k] for k in scopes.TOP) + secs[scopes.UNSCOPED]
    return 100.0 * secs[scopes.UNSCOPED] / total

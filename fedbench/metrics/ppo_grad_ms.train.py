"""``ppo_grad_ms.train``: each local update's per-agent PPO gradients, with
the closures' tree view of the flat carry and the ravel of the gradients
back onto it.

Device ms per federation period of the ops under the program's
``fed_ppo_grad`` scope (``fedbench.scopes``): each op's own time in the
traced window, averaged over the devices, over the periods the window
completed.
"""
from fedbench import scopes

LAYER = "local update"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    return scopes.ms_per_period(out, reduced, "fed_ppo_grad")

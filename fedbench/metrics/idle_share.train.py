"""``idle_share.train``: percent of the traced window in which the devices
ran no operation, averaged over the cell's devices."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def read(out, reduced, cell, peaks):
    if not reduced.devices:
        return None
    return reduced.idle_share()

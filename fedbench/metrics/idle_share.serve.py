"""``idle_share.serve``: percent of the traced window in which the device
ran no operation while the server answered the open loop."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "decision_p90_ms"


def read(out, reduced, cell, peaks):
    if not reduced.devices:
        return None
    return reduced.idle_share()

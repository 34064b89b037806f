"""``batch_fill.serve``: percent of the rows the engine ran that were real
requests, ``n_decisions / (n_decisions + n_padded)`` over the window, from
the engine's own counters."""
LAYER = "serving queue"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "decision_p90_ms"


def read(out, reduced, cell, peaks):
    rows = out.layer["decisions"] + out.layer["padded"]
    return 100.0 * out.layer["decisions"] / rows if rows else None

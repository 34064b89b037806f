"""``topk_select_ms``: device time per federation period of the top-k
threshold select of the payload encode (``comm/transforms.py``
``topk_threshold``: ``lax.top_k`` of ``|x|`` over each (n,) payload row).

On the TPU the select lowers to ``sort`` ops over rows of the payload
width n; they are found by that op name and width in the trace, until the
program names the scope.
"""
from fedbench.trace import op_kind

LAYER = "payload encode"
UNIT = "ms/period"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def is_select(text: str, n: int) -> bool:
    return op_kind(text) in ("sort", "top-k", "topk") and f",{n}]" in text


def read(out, reduced, cell, peaks):
    n = cell.config["policy"]["params"]
    secs = sum(s for _, _, s in reduced.op_stats(lambda t: is_select(t, n)))
    periods = out.layer["periods"]
    if not secs or not periods:
        return None
    return 1e3 * secs / len(reduced.devices) / periods

"""A kernel's share of its roofline, from the trace and from shapes.

The least time a call can take on the chip is the larger of its FLOPs over
the peak FLOP rate and its bytes over the peak HBM bandwidth (``peaks.json``
for the device kind). The share is that least time, times the calls, over
the kernel's device time, in percent. The kernel's events are the custom
calls whose op name starts with the kernel's name and whose HLO text holds
the shape ``[m,n]`` the FLOPs and bytes were computed for.
"""
from __future__ import annotations

from fedbench.trace import short_name


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_events(reduced, kernel: str, shape) -> tuple:
    """``(calls, seconds)`` of the kernel's events at ``shape`` in the window."""
    dims = "[" + ",".join(str(d) for d in shape) + "]"

    def select(text: str) -> bool:
        return (short_name(text).lstrip("%").startswith(kernel)
                and "custom-call" in text and dims in text)

    stats = reduced.op_stats(select)
    return sum(c for _, c, _ in stats), sum(s for _, _, s in stats)


def share(reduced, kernel: str, shape, flops: float, nbytes: float,
          peaks: dict):
    """Percent of the roofline, or None where the kernel did not run."""
    calls, secs = kernel_events(reduced, kernel, shape)
    if not calls or secs <= 0:
        return None
    return 100.0 * calls * least_seconds(flops, nbytes, peaks) / secs

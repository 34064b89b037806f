"""``flat_adam_roofline``: the fused Adam kernel's share of its roofline.

Per call on the (m, n) flat carry the kernel must read the parameters, the
gradient and both moments and write the parameters and both moments back:
7 * m * n * 4 bytes. Its FLOPs (about a dozen per element) lie far below
the chip's ridge point, so the bound is the bytes over the HBM bandwidth.
The share is that least time over the kernel's device time, summed over
its calls in the traced window.
"""
import fedbench.roofline as rl

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"
KERNEL = "adam_update_pallas"


def bytes_per_call(m: int, n: int) -> int:
    return 7 * m * n * 4


def flops_per_call(m: int, n: int) -> int:
    return 12 * m * n


def read(out, reduced, cell, peaks):
    cfg = cell.config
    m, n = cfg["agents"], cfg["policy"]["params"]
    return rl.share(reduced, KERNEL, (m, n), flops_per_call(m, n),
                    bytes_per_call(m, n), peaks)

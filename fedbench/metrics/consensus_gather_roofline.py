"""``consensus_gather_roofline``: the sparse gossip gather's share of its
roofline.

Per call on the (m, n) payload each output row needs the k_max rows of its
closed neighbourhood (k + 1 on the k-NN ring) and is written once:
(k_max + 1) * m * n * 4 bytes, and k_max multiply-adds per element. The
bound is the bytes over the HBM bandwidth; the share is that least time
over the kernel's device time in the traced window.
"""
import fedbench.roofline as rl

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"
KERNEL = "consensus_gather_pallas"


def bytes_per_call(m: int, n: int, k_max: int) -> int:
    return (k_max + 1) * m * n * 4


def flops_per_call(m: int, n: int, k_max: int) -> int:
    return 2 * k_max * m * n


def read(out, reduced, cell, peaks):
    cfg = cell.config
    m, n = cfg["agents"], cfg["policy"]["params"]
    k_max = cfg["strategy"]["topology"]["k"] + 1
    return rl.share(reduced, KERNEL, (m, n), flops_per_call(m, n, k_max),
                    bytes_per_call(m, n, k_max), peaks)

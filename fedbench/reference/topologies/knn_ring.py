"""Reference of topology ``knn_ring``: agents on a ring, each joined to the
k/2 nearest on either side."""
import numpy as np


def neighbours(spec: dict, m: int):
    """Each agent's closed neighbourhood: ``(idx, valid)``, both (m, d),
    the agent itself among its own ``idx``."""
    k = spec["k"]
    offs = np.r_[np.arange(-(k // 2), 0), 0, np.arange(1, k // 2 + 1)]
    idx = (np.arange(m)[:, None] + offs[None, :]) % m
    return idx, np.ones(idx.shape, bool)

"""Reference of strategy ``consensus`` (CIRL): every local update, each
agent broadcasts its gradient (encoded, with error feedback, where the
configuration has a ``comm``) and steps along the gossip mix ``P = I - eps
L`` of what its closed neighbourhood sent, ``rounds`` times over."""
import importlib

import jax.numpy as jnp
import numpy as np


class Strategy:
    def __init__(self, cfg: dict):
        s = cfg["strategy"]
        topo = s["topology"]
        idx, valid = importlib.import_module(
            f"fedbench.reference.topologies.{topo['kind']}").neighbours(
                topo, cfg["agents"])
        m = idx.shape[0]
        self_col = idx == np.arange(m)[:, None]
        deg = (valid & ~self_col).sum(axis=1)
        # eps is given times the paper's Delta = max |Omega_i| + 1.
        eps = s["eps_times_max_degree"] / (deg.max() + 1)
        w = np.where(self_col, 1.0 - eps * deg[:, None], eps) * valid
        self.idx = jnp.asarray(idx, jnp.int32)
        self.w = jnp.asarray(w, jnp.float32)
        self.rounds = s["rounds"]
        self.degrees = int(deg.sum())
        self.comm = cfg.get("comm")
        self.encode = self.comm and importlib.import_module(
            f"fedbench.reference.comm.{self.comm['kind']}").keep
        self.tau = cfg["tau"]

    def mix(self, x):
        """One gossip round: each row's weighted sum of its neighbourhood."""
        return sum(self.w[:, j, None] * x[self.idx[:, j]]
                   for j in range(self.idx.shape[1]))

    def init(self, flat) -> dict:
        if self.comm and self.comm["error_feedback"]:
            return {"err_gossip": jnp.zeros(flat.shape, jnp.float32)}
        return {}

    def direction(self, g, state: dict, offset):
        x = g + state["err_gossip"] if "err_gossip" in state else g
        sent = self.encode(x, self.comm) if self.comm else x
        if "err_gossip" in state:
            state = dict(state, err_gossip=x - sent)
        for _ in range(self.rounds):
            sent = self.mix(sent)
        return sent, state

    def receives_per_period(self) -> int:
        return self.degrees * self.rounds * self.tau

"""Reference of optimizer ``adam`` on each agent's row, bias-corrected; at
a server sync the moments are replaced by their mean over agents."""
import jax.numpy as jnp


def init(flat, spec: dict) -> dict:
    zeros = jnp.zeros(flat.shape, jnp.float32)
    return {"mu": zeros, "nu": zeros, "t": jnp.zeros((), jnp.int32)}


def step(flat, d, state: dict, eta: float, spec: dict):
    t = state["t"] + 1
    mu = spec["b1"] * state["mu"] + (1.0 - spec["b1"]) * d
    nu = spec["b2"] * state["nu"] + (1.0 - spec["b2"]) * d * d
    tf = t.astype(jnp.float32)
    upd = (mu / (1.0 - spec["b1"] ** tf)) / (
        jnp.sqrt(nu / (1.0 - spec["b2"] ** tf)) + spec["eps"])
    return flat - eta * upd, {"mu": mu, "nu": nu, "t": t}


def sync(state: dict) -> dict:
    mean = lambda a: jnp.broadcast_to(a.mean(axis=0), a.shape)
    return dict(state, mu=mean(state["mu"]), nu=mean(state["nu"]))

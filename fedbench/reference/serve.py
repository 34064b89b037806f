"""Plain jax.numpy reference of the served decision, and the served weights.

A decision is the mean of the Gaussian policy head, ``tanh`` of a
6 -> 64 -> 64 -> 1 tanh MLP, on the raw observation (the served
normalisation is the identity). The reference computes it in float32 with
matrix products at ``highest`` precision.

The configuration states what the system computes: float32 weights and
activations, matrix products with bfloat16 operands and float32
accumulation (the TPU's default for float32). ``decide_fp8`` is the control,
one step below: the same forward pass with the products' operands rounded
to float8 (e4m3), accumulation and activations in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_pi(key, policy: dict) -> dict:
    """Served weights at a trained policy's scale, drawn from ``key``:
    orthogonal matrices (the output layer unscaled), biases N(0, 0.1^2),
    log_std = -0.5."""
    o, h, a = policy["obs_dim"], policy["hidden"], policy["act_dim"]
    ks = jax.random.split(key, 6)
    g = jax.nn.initializers.orthogonal()
    return {"w1": g(ks[0], (o, h)), "b1": 0.1 * jax.random.normal(ks[1], (h,)),
            "w2": g(ks[2], (h, h)), "b2": 0.1 * jax.random.normal(ks[3], (h,)),
            "w3": g(ks[4], (h, a)), "b3": 0.1 * jax.random.normal(ks[5], (a,)),
            "log_std": jnp.full((a,), -0.5)}


def _mean(pi, obs, operand_dtype):
    """The policy mean, each product's operands rounded to ``operand_dtype``
    and accumulated in float32 at ``highest`` precision."""
    r = lambda x: x.astype(operand_dtype).astype(jnp.float32)
    dot = lambda a, w: jnp.dot(r(a), r(w), precision="highest")
    h = jnp.tanh(dot(obs, pi["w1"]) + pi["b1"])
    h = jnp.tanh(dot(h, pi["w2"]) + pi["b2"])
    return jnp.tanh(dot(h, pi["w3"]) + pi["b3"])


@jax.jit
def decide(pi, obs):
    return _mean(pi, obs, jnp.float32)


@jax.jit
def decide_fp8(pi, obs):
    return _mean(pi, obs, jnp.float8_e4m3fn)


def decisions(pi, obs: np.ndarray, fn=decide, block: int = 1 << 16) -> np.ndarray:
    """``fn`` over ``obs`` in blocks of ``block`` rows (the last one padded,
    so one program serves every block)."""
    n = obs.shape[0]
    out = np.empty((n, pi["w3"].shape[1]), np.float32)
    for i in range(0, n, block):
        part = obs[i:i + block]
        pad = np.zeros((block, obs.shape[1]), np.float32)
        pad[:part.shape[0]] = part
        out[i:i + part.shape[0]] = np.asarray(fn(pi, pad))[:part.shape[0]]
    return out

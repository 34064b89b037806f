"""Compile the hot-path Pallas kernels for a described TPU v5e, at real sizes.

Interpret-mode parity cannot see what the TPU compiler refuses: blocks not
aligned to the (8, 128) tiling, or working sets larger than the scoped VMEM.
These tests compile every dispatch primitive with ``backend="pallas"`` for a
v5e chip that is described, not attached — nothing runs — at the policy's
flat width (n = 9,347: the 6->64->64 actor plus critic) and at the fleet
sizes the chip is meant to hold.

The topology is described inside a module-scoped fixture and never while a
module is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.rl.env import OBS_DIM
from repro.rl.fedrl import policy_payload_elems
from repro.rl.policy import init_policy
from repro.serve.engine import DEFAULT_BUCKETS

N = 9347     # flat policy width
M = 1024     # agents per chip in the training cells
M_GOSSIP = 10_000
K_GOSSIP = 8
K_TOPK = 512  # entries a top-k payload row keeps in the 10k cell


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _primitive(name, m, sds):
    """(fn, abstract args) of one dispatch primitive on the pallas backend."""
    buf, col = sds((m, N)), sds((m,))
    if name == "decay_accum":
        return lambda a, g, d: dispatch.decay_accum(a, g, d, backend="pallas"), (
            buf, buf, col)
    if name == "scale_rows":
        return lambda g, w: dispatch.scale_rows(g, w, backend="pallas"), (buf, col)
    if name == "momentum":
        return (
            lambda p, g, w, s: dispatch.flat_opt_update(
                p, g, w, s, kind="momentum", lr=0.1, backend="pallas"),
            (buf, buf, col, {"mu": buf}),
        )
    if name == "adam":
        return (
            lambda p, g, w, s: dispatch.flat_opt_update(
                p, g, w, s, kind="adam", lr=0.1, backend="pallas"),
            (buf, buf, col,
             {"mu": buf, "nu": buf, "t": sds((), jnp.int32)}),
        )
    if name == "consensus_mix":
        return lambda g, p: dispatch.consensus_mix(g, p, backend="pallas"), (
            buf, sds((m, m)))
    if name == "consensus_gather":
        return (
            lambda g, i, w: dispatch.consensus_gather(g, i, w, backend="pallas"),
            (buf, sds((m, K_GOSSIP), jnp.int32), sds((m, K_GOSSIP))),
        )
    if name == "row_mean":
        return lambda g: dispatch.row_mean(g, backend="pallas"), (buf,)
    if name == "topk_threshold":
        return lambda x: dispatch.topk_threshold(x, K_TOPK, backend="pallas"), (
            buf,)
    if name == "topk_scatter":
        return lambda x, t: dispatch.topk_scatter(x, t, backend="pallas"), (
            buf, col)
    raise ValueError(name)


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_policy_width_is_the_compiled_width():
    assert policy_payload_elems() == N


@pytest.mark.parametrize("name,m", [
    ("decay_accum", M), ("scale_rows", M), ("momentum", M), ("adam", M),
    ("consensus_mix", M), ("consensus_gather", M), ("row_mean", M),
    ("topk_scatter", M), ("consensus_gather", M_GOSSIP),
    ("topk_threshold", M), ("topk_threshold", M_GOSSIP),
])
def test_primitive_compiles_for_v5e(one_chip, name, m):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert "tpu_custom_call" in _compile(*_primitive(name, m, sds))


@pytest.mark.parametrize("bucket", DEFAULT_BUCKETS)
def test_policy_infer_compiles_for_v5e(one_chip, bucket):
    pi = jax.eval_shape(lambda: init_policy(jax.random.key(0), OBS_DIM))["pi"]
    act_dim = pi["w3"].shape[1]

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    pi = jax.tree.map(lambda l: sds(l.shape), pi)
    fn = lambda obs, p, nm, ns, z: dispatch.policy_infer(
        obs, p, nm, ns, z, backend="pallas"
    )
    args = (sds((bucket, OBS_DIM)), pi, sds((OBS_DIM,)), sds((OBS_DIM,)),
            sds((bucket, act_dim)))
    assert "tpu_custom_call" in _compile(fn, args)

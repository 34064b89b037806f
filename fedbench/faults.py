"""Faults planted in the system under test, to show ``correct`` catches them.

Each is a context manager that patches one function of the program while it
is active; a program compiled inside it carries the fault. They serve the
tests under ``fedbench/`` (at a size a CPU holds) and ``readings.py`` (on
the chip, at the cell's size):

* ``frozen``      training: every local step and every sync returns the
                  state it was given;
* ``half_batch``  training: each agent's PPO gradient is taken over the
                  first half of its transitions only, the mean over those;
* ``gossip_weight`` training on a gossip topology: the mixing step eps is
                  8/9 of the configured one (the paper's 0.5 / Delta taken
                  with Delta one too large);
* ``no_exchange`` training across chips: the server mean is taken on each
                  chip over its own agents, with no exchange between chips;
* ``half_answers`` serving: ``decide`` answers only the first half of a
                  batch;
* ``altered_answer`` serving: ``decide`` shifts the first decision of each
                  batch by 0.05.

and the controls, the step to a lower precision that a sound comparison has
to fail:

* ``bf16_buffers``   training: the program's own bfloat16 storage of the
                     flat parameters and gradients (``buffer_dtype``);
* ``fp8_decisions``  serving: the reference's forward pass with float8
                     operands put in the engine's place.
"""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def frozen():
    from repro.core.strategies import AggregationStrategy, ConsensusStrategy

    def local(self, flat, g, offset, eta, opt, opt_state, comm_state, **kw):
        return flat, opt_state, comm_state

    def sync(self, flat, comm_state, **kw):
        return flat, comm_state

    with mock.patch.object(AggregationStrategy, "flat_local_step", local), \
            mock.patch.object(ConsensusStrategy, "flat_local_step", local), \
            mock.patch.object(AggregationStrategy, "flat_sync", sync):
        yield


@contextlib.contextmanager
def half_batch():
    import jax

    import repro.rl.fedrl as fedrl

    sound = fedrl.minibatch_epoch_grad

    def half(loss_fn, params, data, key, **kw):
        d = jax.tree.leaves(data)[0].shape[0]
        return sound(loss_fn, params,
                     jax.tree.map(lambda x: x[: d // 2], data), key, **kw)

    with mock.patch.object(fedrl, "minibatch_epoch_grad", half):
        yield


@contextlib.contextmanager
def gossip_weight():
    import repro.core as core

    sound = core.make_strategy

    def make(kind, **kw):
        if "eps" in kw:
            kw["eps"] = kw["eps"] * 8 / 9
        return sound(kind, **kw)

    with mock.patch.object(core, "make_strategy", make):
        yield


@contextlib.contextmanager
def no_exchange():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from repro.kernels import dispatch
    from repro.sharding import current_rules

    sound = dispatch.row_mean

    def local_mean(g, **kw):
        rules = current_rules()
        if rules is None:
            return sound(g, **kw)
        # Each chip keeps the mean of its own rows; the result claims to be
        # replicated without the all-reduce that would make it so.
        mean = jax.shard_map(lambda x: x.astype(jnp.float32).mean(axis=0),
                             mesh=rules.mesh,
                             in_specs=PartitionSpec("agents"),
                             out_specs=PartitionSpec(), check_vma=False)
        return mean(g).astype(g.dtype)

    with mock.patch.object(dispatch, "row_mean", local_mean):
        yield


@contextlib.contextmanager
def half_answers():
    from repro.serve import ServeEngine

    sound = ServeEngine.decide

    def decide(self, obs):
        return sound(self, obs)[: (len(obs) + 1) // 2]

    with mock.patch.object(ServeEngine, "decide", decide):
        yield


@contextlib.contextmanager
def altered_answer():
    from repro.serve import ServeEngine

    sound = ServeEngine.decide

    def decide(self, obs):
        act = sound(self, obs).copy()
        act[0] += 0.05
        return act

    with mock.patch.object(ServeEngine, "decide", decide):
        yield


@contextlib.contextmanager
def bf16_buffers():
    import repro.rl as rl

    sound = rl.FedRLConfig

    def config(*args, **kw):
        kw["buffer_dtype"] = "bfloat16"
        return sound(*args, **kw)

    with mock.patch.object(rl, "FedRLConfig", config):
        yield


@contextlib.contextmanager
def fp8_decisions():
    import numpy as np

    from fedbench.reference import serve as ref
    from repro.serve import ServeEngine

    sound = ServeEngine.decide

    def decide(self, obs):
        sound(self, obs)
        return ref.decisions(self._pi, np.asarray(obs), fn=ref.decide_fp8,
                             block=self.max_batch())

    with mock.patch.object(ServeEngine, "decide", decide):
        yield


TRAIN = {"frozen": frozen, "half_batch": half_batch}
GOSSIP = {"gossip_weight": gossip_weight}
SHARDED = {"no_exchange": no_exchange}
SERVE = {"half_answers": half_answers, "altered_answer": altered_answer}

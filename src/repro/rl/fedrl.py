"""Federated MARL driver: paper Algorithms 1 & 2 on the traffic envs.

Two rollout regimes share the same federated machinery:

* **Legacy shared env** (``num_envs=0``, the default): m federated agents =
  the RL-controlled vehicles of ONE environment. Every vehicle acts under its
  own current replica (agents interact through traffic while learning
  locally). This path is bit-identical to the original driver.
* **Heterogeneous fleet** (``num_envs >= 1`` or ``env_params`` set): agent i
  owns its *own* environment — an ``EnvParams`` row, possibly perturbed per
  agent (the paper's asynchronous/heterogeneous-MDP setting) — with B
  parallel rollout copies stepped by ``repro.rl.rollout``. Trajectory
  buffers come back shaped (m, B, P, ...), and each local update runs the
  PPO minibatch-epoch loop (``ppo_epochs`` x ``n_minibatches``) over the
  B*P*n_rl transitions, reported to the strategy as a pseudo-gradient.

Every P transitions each agent takes one local update on its own data; the
strategy applies variation masks / decay / consensus gossip; every tau local
updates the virtual agent averages the replicas (eq. 11). The whole run is
one jitted scan (epochs x updates x P env steps).

Carry layouts mirror ``repro.core.fmarl``: the jnp backend with plain SGD
keeps the original tree-space reference (bit-identical); kernel backends —
or any run with ``cfg.optimizer`` or ``cfg.buffer_dtype`` set — keep the
policy replicas as one flat ``(m, n)`` matrix across every scan. Each update
step unravels one cached tree view for the rollout/grad closures and ravels
only the gradients back; the local update, the periodic sync (``row_mean``),
and the optimizer accumulators all stay flat through the dispatch layer.
With ``buffer_dtype="bfloat16"`` the flat params/grad buffers are stored in
bf16 end to end (the dispatch primitives and optimizer moments still
accumulate in fp32; closures see an fp32 tree view).

Traced variation axis: both cores read the strategy's per-step weights
(variation mask x decay, mask-folded mixing) through ``jnp.asarray`` inside
the scan bodies, so a ``with_mask`` strategy copy whose mask is a tracer —
the sweep engine's ``taus`` axis — threads straight through as a scan-body
operand, and ``cfg.env_params`` built from a traced ``hetero_scale``
likewise. Under the sweep's vmap the mask batches to ``(S, m, tau)`` and
the env params to per-run pytrees; the period length ``tau`` stays static
(it is the inner scan length). See DESIGN.md §11.

Both cores name their layers on the device: each call site opens a
``jax.named_scope`` from ``repro.utils.scopes`` (rollout, PPO gradient,
local step, sync, evaluation), so a profiler trace attributes every op to
the outermost one; the payload transform nests its encode scope inside.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import CostLedger
from repro.core.fmarl import _use_flat_carry
from repro.core.strategies import AggregationStrategy
from repro.kernels import dispatch
from repro.optim.flat import FlatOptimizer, server_average_state
from repro.rl.env import (
    EnvConfig,
    EnvParams,
    broadcast_params,
    env_reset,
    env_step,
    get_obs,
)
from repro.rl.policy import init_policy, policy_value, sample_action
from repro.rl.ppo import LOSSES, gae, minibatch_epoch_grad
from repro.rl.env import OBS_DIM
from repro.rl.rollout import (
    fleet_flatten,
    fleet_gae,
    fleet_last_values,
    fleet_reset,
    fleet_rollout,
)
from repro.sharding import shard_agents
from repro.utils import scopes
from repro.utils.pytree import tree_l2_norm


@dataclasses.dataclass(frozen=True)
class FedRLConfig:
    env: EnvConfig
    strategy: AggregationStrategy
    eta: float = 1e-3
    n_epochs: int = 100          # U
    epoch_len: int = 200         # T (env steps per epoch)
    minibatch: int = 25          # P (transitions per local update)
    algo: str = "ppo"            # ppo | trpo | tac
    gamma: float = 0.99
    lam: float = 0.95
    eval_seed: int = 1234
    optimizer: Optional[FlatOptimizer] = None  # None = plain SGD (reference)
    # --- heterogeneous fleet (repro.rl.rollout) ---
    num_envs: int = 0            # B parallel envs per agent; 0 = legacy shared env
    env_params: Optional[EnvParams] = None  # (m,)-stacked per-agent MDPs
    ppo_epochs: int = 1          # PPO epochs per local update (fleet path)
    n_minibatches: int = 1       # PPO minibatches per epoch (fleet path)
    # --- flat-carry storage dtype (None = fp32); e.g. "bfloat16" ---
    buffer_dtype: Optional[str] = None

    @property
    def fleet(self) -> bool:
        return self.num_envs > 0 or self.env_params is not None

    @property
    def B(self) -> int:
        return max(self.num_envs, 1)

    def __post_init__(self):
        if self.epoch_len % self.minibatch:
            raise ValueError("T must divide into P-sized steps")
        if self.fleet:
            if self.env_params is not None:
                m_p = jax.tree.leaves(self.env_params)[0].shape[0]
                if m_p != self.strategy.m:
                    raise ValueError(
                        f"env_params carries {m_p} agents, strategy m="
                        f"{self.strategy.m}"
                    )
            d = self.B * self.minibatch * self.env.n_rl
            if d % self.n_minibatches:
                raise ValueError(
                    f"{d} fleet transitions per update do not split into "
                    f"{self.n_minibatches} minibatches"
                )
        elif self.env.n_rl != self.strategy.m:
            raise ValueError(
                f"strategy m={self.strategy.m} must equal n_rl={self.env.n_rl}"
            )
        if self.buffer_dtype is not None:
            jnp.dtype(self.buffer_dtype)  # fail fast on typos


def _fleet_params(cfg: FedRLConfig) -> EnvParams:
    """The (m,)-stacked per-agent EnvParams (homogeneous broadcast if unset)."""
    if cfg.env_params is not None:
        return cfg.env_params
    return broadcast_params(cfg.env.default_params(), (cfg.strategy.m,))


def _rollout(cfg: FedRLConfig, params_m, env_state, key, n_steps: int):
    """Steps the shared env; every RL vehicle acts via its own replica.

    Returns (env_state, traj) with traj leaves shaped (m, n_steps, ...).
    """
    m = cfg.env.n_rl

    def step(carry, _):
        env_state, key = carry
        key, sub = jax.random.split(key)
        obs = get_obs(cfg.env, env_state)                     # (m, obs)
        keys = jax.random.split(sub, m)
        acts, logps = jax.vmap(sample_action)(params_m, obs, keys)
        vals = jax.vmap(policy_value)(params_m, obs)
        env_state, reward, _ = env_step(cfg.env, env_state, acts[:, 0])
        out = {
            "obs": obs, "act": acts, "logp_old": logps,
            "val": vals, "rew": jnp.broadcast_to(reward, (m,)),
        }
        return (env_state, key), out

    (env_state, _), traj = jax.lax.scan(step, (env_state, key), None, length=n_steps)
    traj = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), traj)  # (m, P, ...)
    return env_state, traj


def _agent_grads(cfg: FedRLConfig, params_m, traj, env_state):
    """Per-agent PPO/TRPO/TAC gradient from its own P transitions."""
    loss_fn = LOSSES[cfg.algo]
    last_obs = get_obs(cfg.env, env_state)
    last_val = jax.vmap(policy_value)(params_m, last_obs)     # (m,)

    def one(params_i, traj_i, last_v):
        adv, ret = gae(traj_i["rew"], traj_i["val"], last_v,
                       gamma=cfg.gamma, lam=cfg.lam)
        t = dict(traj_i, adv=adv, ret=ret)
        loss, g = jax.value_and_grad(loss_fn)(params_i, t)
        return g, loss

    grads, losses = jax.vmap(one)(params_m, traj, last_val)
    return grads, losses


def _fleet_grads(cfg: FedRLConfig, params_m, env_params, traj, env_state, key,
                 *, epochs: int, n_minibatches: int):
    """Per-agent pseudo-gradients from the (m, B, P, ...) fleet trajectories.

    GAE runs per (env, vehicle) stream, the streams flatten to one
    B*P*n_rl transition batch per agent, and each agent's gradient is the
    PPO minibatch-epoch pseudo-gradient (plain gradient when 1x1).
    """
    loss_fn = LOSSES[cfg.algo]
    last_val = fleet_last_values(cfg.env, env_params, params_m, env_state)
    adv, ret = fleet_gae(traj["rew"], traj["val"], last_val,
                         gamma=cfg.gamma, lam=cfg.lam)
    data = fleet_flatten({
        "obs": traj["obs"], "act": traj["act"],
        "logp_old": traj["logp_old"], "adv": adv, "ret": ret,
    })
    keys = jax.random.split(key, cfg.strategy.m)

    def one(params_i, data_i, k):
        return minibatch_epoch_grad(
            loss_fn, params_i, data_i, k,
            epochs=epochs, n_minibatches=n_minibatches, lr=cfg.eta,
        )

    grads, losses = jax.vmap(one)(params_m, data, keys)
    return grads, losses


def _collect(cfg: FedRLConfig, env_params, params_m, env_state, key):
    """One local-update batch of experience + per-agent gradients.

    Returns ``(env_state, grads_m, losses, nas)``. The legacy shared-env
    branch reproduces the original key discipline exactly (one rollout key);
    the fleet branch additionally splits a minibatch-shuffle key. The
    rollout runs under the ``fed_rollout`` scope, the gradients under
    ``fed_ppo_grad`` (the evaluation calls the same helpers under its own).
    """
    if cfg.fleet:
        rk, gk = jax.random.split(key)
        with jax.named_scope(scopes.ROLLOUT):
            env_state, traj = fleet_rollout(
                cfg.env, env_params, params_m, env_state, rk, cfg.minibatch
            )
            nas = jnp.mean(traj["rew"])
        with jax.named_scope(scopes.PPO_GRAD):
            grads, losses = _fleet_grads(
                cfg, params_m, env_params, traj, env_state, gk,
                epochs=cfg.ppo_epochs, n_minibatches=cfg.n_minibatches,
            )
    else:
        with jax.named_scope(scopes.ROLLOUT):
            env_state, traj = _rollout(cfg, params_m, env_state, key,
                                       cfg.minibatch)
            nas = jnp.mean(traj["rew"])
        with jax.named_scope(scopes.PPO_GRAD):
            grads, losses = _agent_grads(cfg, params_m, traj, env_state)
    return env_state, grads, losses, nas


def _reset(cfg: FedRLConfig, env_params, key):
    with jax.named_scope(scopes.ROLLOUT):
        if cfg.fleet:
            return fleet_reset(cfg.env, env_params, key, cfg.B)
        return env_reset(cfg.env, key)


def _eval_grad_norm(cfg: FedRLConfig, server_params, env_params=None):
    """Expected gradient norm ||grad F(theta_bar)||^2 on a fixed eval stream
    (Table II metric: fixed sample distribution, deterministic seed).

    The reset and rollout streams are decorrelated: reusing one key for both
    made the eval trajectory's action noise a deterministic function of the
    initial env state, biasing the fixed-sample estimate. On the fleet path
    the metric is the *plain* gradient over each agent's batch (no inner
    minibatch epochs — the metric estimates grad F, not a PPO displacement).
    """
    k_reset, k_roll = jax.random.split(jax.random.key(cfg.eval_seed))
    m = cfg.strategy.m if cfg.fleet else cfg.env.n_rl
    params_m = jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape),
                            server_params)
    if cfg.fleet:
        env_state = fleet_reset(cfg.env, env_params, k_reset, cfg.B)
        k_roll, gk = jax.random.split(k_roll)
        env_state, traj = fleet_rollout(
            cfg.env, env_params, params_m, env_state, k_roll, cfg.minibatch
        )
        grads, _ = _fleet_grads(cfg, params_m, env_params, traj, env_state,
                                gk, epochs=1, n_minibatches=1)
    else:
        env_state = env_reset(cfg.env, k_reset)
        env_state, traj = _rollout(cfg, params_m, env_state, k_roll,
                                   cfg.minibatch)
        grads, _ = _agent_grads(cfg, params_m, traj, env_state)
    g_mean = jax.tree.map(lambda x: jnp.mean(x, axis=0), grads)
    return tree_l2_norm(g_mean) ** 2


@functools.lru_cache(maxsize=1)
def policy_payload_elems() -> int:
    """Parameter count of one policy — the per-event payload size in elements.

    Shape-only (``jax.eval_shape``), so no device work; cached because every
    ledger call needs it and the policy architecture is fixed by ``OBS_DIM``.
    """
    shapes = jax.eval_shape(lambda: init_policy(jax.random.key(0), OBS_DIM))
    return int(sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)))


def _finish_ledger(strat, n_updates: int,
                   payload_elems: Optional[int] = None) -> CostLedger:
    """Bill full periods plus any trailing partial one (the old
    ``n_updates // tau`` silently dropped the remainder's local updates)."""
    full, rem = divmod(n_updates, strat.tau)
    ledger = CostLedger()
    ledger.add_periods(strat, full, payload_elems)
    ledger.add_partial_period(strat, rem, payload_elems)
    return ledger


def fedrl_ledger(cfg: FedRLConfig) -> CostLedger:
    """The run's communication-cost ledger (host-side, config-only — the
    same for every seed, so sweep callers compute it once per config)."""
    return _finish_ledger(
        cfg.strategy, cfg.n_epochs * (cfg.epoch_len // cfg.minibatch),
        policy_payload_elems(),
    )


def fedrl_bytes_curve(cfg: FedRLConfig) -> np.ndarray:
    """Cumulative wire bytes after each epoch — the figures' bytes x-axis.

    Host-side and config-only like :func:`fedrl_ledger`: entry ``e`` is
    ``total_bytes()`` of a ledger billed for the first ``e + 1`` epochs
    (partial trailing periods included), so plotting a per-epoch metric
    against this axis reads "utility bought per byte on the wire".
    """
    upd = cfg.epoch_len // cfg.minibatch
    n = policy_payload_elems()
    return np.asarray(
        [
            _finish_ledger(cfg.strategy, (e + 1) * upd, n).total_bytes()
            for e in range(cfg.n_epochs)
        ],
        np.float64,
    )


def run_fedrl(cfg: FedRLConfig, key) -> tuple[Any, dict, CostLedger]:
    server, metrics = run_fedrl_core(cfg, key)
    metrics = jax.tree.map(np.asarray, jax.device_get(metrics))
    return server, metrics, fedrl_ledger(cfg)


def run_fedrl_core(cfg: FedRLConfig, key) -> tuple[Any, dict]:
    """Traced core of :func:`run_fedrl`: ``(server_params, metrics)`` only.

    Pure function of ``(cfg, key)`` with no host transfers — safe to wrap in
    ``jax.jit`` / ``jax.vmap`` (the sweep engine maps it over a seed axis and
    over traced hyperparameter overrides). The communication-cost ledger is
    host-side accounting and lives in the :func:`run_fedrl` wrapper.
    """
    if _use_flat_carry(cfg):  # the one carry-selection predicate, shared
        return _run_fedrl_flat(cfg, key)
    return _run_fedrl_tree(cfg, key)


def _run_fedrl_tree(cfg: FedRLConfig, key) -> tuple[Any, dict]:
    """Tree-space reference path (bit-identical to the original jnp driver)."""
    strat = cfg.strategy
    m, tau = strat.m, strat.tau
    updates_per_epoch = cfg.epoch_len // cfg.minibatch
    env_params = _fleet_params(cfg) if cfg.fleet else None

    key, pk = jax.random.split(key)
    init = init_policy(pk, OBS_DIM)
    params_m = jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape), init)

    def update(carry, _):
        params_m, env_state, k, key = carry
        key, rk = jax.random.split(key)
        env_state, grads, losses, nas = _collect(
            cfg, env_params, params_m, env_state, rk
        )
        offset = jnp.mod(k, tau)
        with jax.named_scope(scopes.LOCAL_STEP):
            params_m = strat.local_update(params_m, grads, offset, cfg.eta)
        k = k + 1

        def do_sync(p):
            avg = strat.server_average(p)
            return jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape), avg)

        synced = jnp.equal(jnp.mod(k, tau), 0)
        with jax.named_scope(scopes.SYNC):
            params_m = jax.lax.cond(synced, do_sync, lambda p: p, params_m)
        return (params_m, env_state, k, key), {"nas": nas, "loss": losses.mean(),
                                               "synced": synced}

    def epoch(carry, _):
        params_m, k, key = carry
        key, ek = jax.random.split(key)
        env_state = _reset(cfg, env_params, ek)
        (params_m, _, k, key), ms = jax.lax.scan(
            update, (params_m, env_state, k, key), None, length=updates_per_epoch
        )
        with jax.named_scope(scopes.EVALUATE):
            server = strat.server_average(params_m)
            grad_sq = _eval_grad_norm(cfg, server, env_params)
        out = {
            "nas": ms["nas"].mean(),
            "loss": ms["loss"].mean(),
            "server_grad_sq_norm": grad_sq,
        }
        return (params_m, k, key), out

    carry = (params_m, jnp.zeros((), jnp.int32), key)
    (params_m, k, key), metrics = jax.lax.scan(
        epoch, carry, None, length=cfg.n_epochs
    )
    with jax.named_scope(scopes.EVALUATE):
        server = strat.server_average(params_m)
    return server, metrics


def _run_fedrl_flat(cfg: FedRLConfig, key) -> tuple[Any, dict]:
    """Flat-carry path: replicas live as one (m, n) matrix across all scans.

    ``cfg.buffer_dtype`` selects the storage dtype of the flat params/grad
    buffers (bf16 mode); the per-agent tree views handed to the rollout/grad
    closures are always fp32, and the dispatch primitives + optimizer moments
    accumulate in fp32 regardless.
    """
    strat = cfg.strategy
    m, tau = strat.m, strat.tau
    opt = cfg.optimizer
    dtype = jnp.dtype(cfg.buffer_dtype) if cfg.buffer_dtype is not None else None
    updates_per_epoch = cfg.epoch_len // cfg.minibatch
    if strat.is_async:
        strat.validate_horizon((cfg.n_epochs * updates_per_epoch) // tau)
    env_params = _fleet_params(cfg) if cfg.fleet else None

    key, pk = jax.random.split(key)
    init = init_policy(pk, OBS_DIM)
    flat, spec = dispatch.stacked_ravel_spec(
        jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape), init)
    )
    if dtype is not None:
        flat = flat.astype(dtype)
    opt_state = opt.init(flat) if opt is not None else {}
    comm_state = strat.init_comm_state(flat)

    def tree_view(f):
        """The closures' fp32 per-agent tree view of the flat carry."""
        return spec.unravel(dispatch.compute_view(f, dtype))

    def update(carry, _):
        flat, opt_state, comm_state, env_state, k, key = carry
        flat = shard_agents(flat)
        key, rk = jax.random.split(key)
        with jax.named_scope(scopes.PPO_GRAD):
            params_m = tree_view(flat)
        env_state, grads, losses, nas = _collect(
            cfg, env_params, params_m, env_state, rk
        )
        with jax.named_scope(scopes.PPO_GRAD):
            g_flat = jax.vmap(spec.ravel_one)(grads)
            if dtype is not None:
                g_flat = g_flat.astype(dtype)
        offset = jnp.mod(k, tau)
        with jax.named_scope(scopes.LOCAL_STEP):
            flat, opt_state, comm_state = strat.flat_local_step(
                flat, g_flat, offset, cfg.eta, opt, opt_state, comm_state
            )
        k = k + 1

        # Boundary index of the sync `k` just completed (k is
        # post-increment, so update tau-1 closes period 0, etc.); only the
        # async schedule lookup consumes it.
        period = jnp.floor_divide(k, tau) - 1

        def do_sync(args):
            f, s, cs = args
            f, cs = strat.flat_sync(f, cs, period=period)
            if not strat.is_async:
                # Async boundaries sync only the arrived subset; optimizer
                # moments stay local (FedBuff keeps no server momentum).
                s = server_average_state(strat, s)
            return f, s, cs

        synced = jnp.equal(jnp.mod(k, tau), 0)
        with jax.named_scope(scopes.SYNC):
            flat, opt_state, comm_state = jax.lax.cond(
                synced, do_sync, lambda args: args, (flat, opt_state, comm_state)
            )
        return (flat, opt_state, comm_state, env_state, k, key), {
            "nas": nas, "loss": losses.mean(), "synced": synced,
        }

    def server_view(f):
        # Epoch evals land mid-period, where replicas are divergent even on
        # the synchronous path — the metric has always been the all-replica
        # poll (row_mean). Async keeps the same poll so utilities stay
        # comparable and the zero-delay run stays bitwise-identical.
        row = strat.flat_server_average(f)
        return spec.unravel_one(dispatch.compute_view(row, dtype))

    def epoch(carry, _):
        flat, opt_state, comm_state, k, key = carry
        key, ek = jax.random.split(key)
        env_state = _reset(cfg, env_params, ek)
        (flat, opt_state, comm_state, _, k, key), ms = jax.lax.scan(
            update, (flat, opt_state, comm_state, env_state, k, key), None,
            length=updates_per_epoch,
        )
        with jax.named_scope(scopes.EVALUATE):
            grad_sq = _eval_grad_norm(cfg, server_view(flat), env_params)
        out = {
            "nas": ms["nas"].mean(),
            "loss": ms["loss"].mean(),
            "server_grad_sq_norm": grad_sq,
        }
        return (flat, opt_state, comm_state, k, key), out

    carry = (flat, opt_state, comm_state, jnp.zeros((), jnp.int32), key)
    (flat, opt_state, comm_state, k, key), metrics = jax.lax.scan(
        epoch, carry, None, length=cfg.n_epochs
    )
    with jax.named_scope(scopes.EVALUATE):
        return server_view(flat), metrics


def expected_gradient_norm(metrics) -> float:
    """Table II metric: average ||grad F||^2 over the training run."""
    return float(np.mean(metrics["server_grad_sq_norm"]))


# --- trace-safety audit registration (repro.analysis.jaxpr_audit) -------------

def _audit_hot_path() -> dispatch.HotPathEntry:
    """Tiny-but-faithful ``run_fedrl_core`` entry for the jaxpr audit.

    FIGURE_EIGHT with a 2-step decay period and 2 local updates per epoch:
    every scan body, dispatch call, PRNG split, and eval branch of the
    production driver appears in the jaxpr — only the trip counts shrink,
    and trip counts do not change which equations the audit sees.
    """
    from repro.core import make_strategy
    from repro.rl.env import FIGURE_EIGHT

    cfg = FedRLConfig(
        env=FIGURE_EIGHT,
        strategy=make_strategy("decay", tau=2, m=7, backend="jnp"),
        n_epochs=1,
        epoch_len=4,
        minibatch=2,
    )
    return dispatch.HotPathEntry(
        fn=lambda seed: run_fedrl_core(cfg, jax.random.key(seed))[1],
        args=(jax.ShapeDtypeStruct((), jnp.int32),),
    )


dispatch.register_hot_path("rl.run_fedrl_core", _audit_hot_path)

"""Plain jax.numpy reference of one federated training job.

It follows the configuration file alone (``fedbench/configs/<config>.json``)
and imports nothing of the system under test. Everything runs in float32
with matrix products at the precision the configuration states
(``matmul_precision``), one federation update at a time from a Python
loop, in the order of the paper's Algorithms 1 and 2:

    per epoch:   reset the B envs of every agent
    per update:  roll P steps -> GAE -> PPO gradient per agent
                 -> strategy transform + local step (VPA: variation mask and
                    Adam; CIRL: top-k gossip payload with error feedback,
                    one gossip round, SGD)
                 -> every tau updates, the server sync (VPA: the mean of the
                    replicas and of Adam's moments; CIRL: the mean of top-k
                    uplink deltas with error feedback)
    per epoch end: the squared norm of the server policy's gradient on a
                   fixed evaluation stream

The PRNG key discipline is the documented one of a seeded run (a run is a
function of its key), so the reference draws the same fleet, resets and
action noise as a run of the system with the same key. Departures from
the system's arithmetic, none of which changes the mathematics:

* parameters live in one (m, n) matrix laid out leaf by leaf in sorted key
  order, each leaf row-major (the same layout as ``ravel_pytree``);
* sums run in whatever order XLA picks, not in the system's kernel order;
* the top-k threshold is read from a full descending sort of each row.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

AGENT_BLOCK = 1024       # agents per gradient block
FLEET_BLOCK = 16384      # agents per rollout block, for larger fleets

ENV_FIELDS = ("length", "dt", "v_max", "a_max", "min_gap", "crash_penalty",
              "idm_v0", "idm_T", "idm_a", "idm_b", "idm_s0",
              "zone_start", "zone_end", "zone_vmax")


# --- policy -------------------------------------------------------------------

def policy_shapes(policy: dict) -> dict:
    """Leaf shapes of the Gaussian actor-critic, per head."""
    o, h, a = policy["obs_dim"], policy["hidden"], policy["act_dim"]
    return {
        "pi": {"w1": (o, h), "b1": (h,), "w2": (h, h), "b2": (h,),
               "w3": (h, a), "b3": (a,), "log_std": (a,)},
        "vf": {"w1": (o, h), "b1": (h,), "w2": (h, h), "b2": (h,),
               "w3": (h, 1), "b3": (1,)},
    }


def leaf_names(policy: dict) -> list:
    """Leaves as ``head.name`` in sorted key order (the flat layout)."""
    shapes = policy_shapes(policy)
    return [f"{h}.{k}" for h in sorted(shapes) for k in sorted(shapes[h])]


def n_params(policy: dict) -> int:
    shapes = policy_shapes(policy)
    return sum(math.prod(shapes[h][k]) for h in shapes for k in shapes[h])


def init_policy(key, policy: dict) -> dict:
    """Orthogonal weights from six subkeys, zero biases, log_std = -0.5; the
    actor's output layer scaled by 0.01."""
    o, h, a = policy["obs_dim"], policy["hidden"], policy["act_dim"]
    ks = jax.random.split(key, 6)
    g = jax.nn.initializers.orthogonal()
    return {
        "pi": {"w1": g(ks[0], (o, h)), "b1": jnp.zeros(h),
               "w2": g(ks[1], (h, h)), "b2": jnp.zeros(h),
               "w3": 0.01 * g(ks[2], (h, a)), "b3": jnp.zeros(a),
               "log_std": jnp.full((a,), -0.5)},
        "vf": {"w1": g(ks[3], (o, h)), "b1": jnp.zeros(h),
               "w2": g(ks[4], (h, h)), "b2": jnp.zeros(h),
               "w3": g(ks[5], (h, 1)), "b3": jnp.zeros(1)},
    }


def ravel(tree: dict, lead: int = 0) -> jnp.ndarray:
    """A policy tree as rows of length n; ``lead`` leading axes are kept."""
    leaves = [tree[h][k] for h in sorted(tree) for k in sorted(tree[h])]
    shape = leaves[0].shape[:lead]
    return jnp.concatenate([l.reshape(shape + (-1,)) for l in leaves], axis=-1)


def unravel(flat, policy: dict) -> dict:
    """Rows of length n back to a policy tree with the same leading axes."""
    shapes = policy_shapes(policy)
    lead = flat.shape[:-1]
    out, i = {}, 0
    for h in sorted(shapes):
        out[h] = {}
        for k in sorted(shapes[h]):
            size = math.prod(shapes[h][k])
            out[h][k] = flat[..., i:i + size].reshape(lead + shapes[h][k])
            i += size
    return out


def _mlp(p, x):
    h = jnp.tanh(jnp.dot(x, p["w1"]) + p["b1"])
    h = jnp.tanh(jnp.dot(h, p["w2"]) + p["b2"])
    return jnp.dot(h, p["w3"]) + p["b3"]


def action_mean(pi, obs):
    return jnp.tanh(_mlp(pi, obs))


def value(params, obs):
    return _mlp(params["vf"], obs)[..., 0]


def gaussian_logp(act, mean, log_std):
    var = jnp.exp(2.0 * log_std)
    return jnp.sum(-0.5 * ((act - mean) ** 2 / var + 2.0 * log_std
                           + jnp.log(2.0 * jnp.pi)), axis=-1)


def ppo_loss(params, data, ppo: dict):
    """Clipped surrogate + value loss - entropy bonus on one agent's batch."""
    pi = params["pi"]
    mean = action_mean(pi, data["obs"])
    logp = gaussian_logp(data["act"], mean, pi["log_std"])
    ratio = jnp.exp(logp - data["logp_old"])
    adv = data["adv"]
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    clip = ppo["clip"]
    pg = -jnp.mean(jnp.minimum(ratio * adv,
                               jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv))
    vf = jnp.mean((value(params, data["obs"]) - data["ret"]) ** 2)
    entropy = jnp.sum(pi["log_std"] + 0.5 * jnp.log(2.0 * jnp.pi * jnp.e))
    return pg + ppo["vf_coef"] * vf - ppo["ent_coef"] * entropy


# --- environment ----------------------------------------------------------------

def fleet_env_params(env: dict, m: int, key) -> dict:
    """(m,) per-agent dynamics: each heterogeneous field times
    ``max(1 + scale * U(-1, 1), 0.1)``, one subkey per field in listed order."""
    out = {f: jnp.full((m,), env[f], jnp.float32) for f in ENV_FIELDS}
    fields = env["hetero_fields"]
    for f, k in zip(fields, jax.random.split(key, len(fields))):
        u = jax.random.uniform(k, (m,), minval=-1.0, maxval=1.0)
        out[f] = out[f] * jnp.maximum(1.0 + env["hetero_scale"] * u, 0.1)
    return out


def env_reset(env: dict, p: dict, key):
    """Vehicles spread evenly round the ring with +-20% jitter, sorted."""
    n = env["n_vehicles"]
    spacing = p["length"] / n
    jitter = jax.random.uniform(key, (n,), minval=-0.2, maxval=0.2) * spacing
    x = jnp.sort((jnp.arange(n) * spacing + jitter) % p["length"])
    return {"x": x, "v": jnp.full((n,), 0.5, jnp.float32),
            "crashed": jnp.zeros((), bool)}


def _gaps(p, x):
    """Gap to the leader (the next vehicle round the ring) of each vehicle."""
    return (jnp.roll(x, -1) - x) % p["length"]


def observe(env: dict, p: dict, s):
    """(n_rl, 6): own position and speed, then the leader's and the
    follower's gap and speed, normalised by ring length and speed limit."""
    rl = np.asarray(env["rl_indices"])
    gaps = _gaps(p, s["x"])
    v = s["v"]
    return jnp.stack([
        s["x"][rl] / p["length"], v[rl] / p["v_max"],
        gaps[rl] / p["length"], jnp.roll(v, -1)[rl] / p["v_max"],
        jnp.roll(gaps, 1)[rl] / p["length"], jnp.roll(v, 1)[rl] / p["v_max"],
    ], axis=-1)


def env_step(env: dict, p: dict, s, rl_accel):
    """IDM for the background vehicles, the policy's acceleration for the RL
    ones, emergency braking, the slow zone and the no-overtaking cap; the
    reward is the team's normalised average speed, or the crash penalty."""
    rl = np.asarray(env["rl_indices"])
    x, v = s["x"], s["v"]
    gaps = _gaps(p, x)
    v_lead = jnp.roll(v, -1)
    s_star = p["idm_s0"] + v * p["idm_T"] + v * (v - v_lead) / (
        2.0 * jnp.sqrt(p["idm_a"] * p["idm_b"]))
    s_star = jnp.maximum(s_star, 0.0)
    accel = p["idm_a"] * (1.0 - (v / p["idm_v0"]) ** 4
                          - (s_star / jnp.maximum(gaps, 0.1)) ** 2)
    accel = accel.at[rl].set(jnp.clip(rl_accel, -1.0, 1.0) * p["a_max"])
    brake = gaps < p["min_gap"] + v * p["dt"] * 2.0
    accel = jnp.where(brake, -2.0 * p["idm_b"], accel)
    in_zone = (x >= p["zone_start"]) & (x < p["zone_end"])
    v_new = jnp.clip(v + accel * p["dt"], 0.0,
                     jnp.where(in_zone, p["zone_vmax"], p["v_max"]))
    v_new = jnp.minimum(v_new, gaps / p["dt"] + jnp.roll(v_new, -1))
    x_new = (x + v_new * p["dt"]) % p["length"]
    crossed = gaps + (jnp.roll(v_new, -1) - v_new) * p["dt"] < 0.0
    crash = (jnp.any(_gaps(p, x_new) < 0.5 * p["min_gap"]) | jnp.any(crossed)
             | s["crashed"])
    reward = jnp.where(crash, -p["crash_penalty"], jnp.mean(v_new) / p["v_max"])
    return {"x": x_new, "v": v_new, "crashed": crash}, reward


def fleet_reset(env, env_params, key, m: int, b: int):
    """(m, B) envs; env (i, j) draws from subkey ``i * B + j``."""
    keys = jax.random.split(key, m * b).reshape((m, b))
    return jax.vmap(lambda p, ks: jax.vmap(
        lambda k: env_reset(env, p, k))(ks))(env_params, keys)


def rollout(env, env_params, params_m, state, key, n_steps: int,
            m_total: int = 0, lo=0):
    """P steps of every (agent, env). Per step one subkey is split into
    m * B env keys (row-major), and each env's key into one action key per
    RL vehicle. Returns the final state and (m, B, P, ...) buffers.

    For a block of the fleet, the agents ``lo .. lo + m`` of ``m_total``,
    the block's rows of the whole fleet's keys are used."""
    n_rl = len(env["rl_indices"])
    m, b = state["x"].shape[:2]
    m_total = m_total or m

    def one_env(p, params, s, k):
        obs = observe(env, p, s)
        mean = action_mean(params["pi"], obs)                     # (n_rl, 1)
        log_std = params["pi"]["log_std"]
        eps = jax.vmap(lambda kk: jax.random.normal(kk, mean.shape[1:]))(
            jax.random.split(k, n_rl))
        act = mean + jnp.exp(log_std) * eps
        logp = gaussian_logp(act, mean, log_std)
        val = value(params, obs)
        s, rew = env_step(env, p, s, act[:, 0])
        return s, {"obs": obs, "act": act, "logp_old": logp, "val": val,
                   "rew": rew}

    step_all = jax.vmap(jax.vmap(one_env, in_axes=(None, None, 0, 0)))

    def step(carry, _):
        s, key = carry
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, m_total * b).reshape((m_total, b))
        keys = jax.lax.dynamic_slice_in_dim(keys, lo, m)
        s, out = step_all(env_params, params_m, s, keys)
        return (s, key), out

    (state, _), traj = jax.lax.scan(step, (state, key), None, length=n_steps)
    return state, jax.tree.map(lambda x: jnp.moveaxis(x, 0, 2), traj)


def gae(rew, val, last_val, gamma, lam):
    """Generalised advantage estimates along the last (time) axis."""
    def step(carry, inp):
        adv_next, v_next = carry
        r, v = inp
        adv = r + gamma * v_next - v + gamma * lam * adv_next
        return (adv, v), adv

    _, adv = jax.lax.scan(step, (jnp.zeros_like(last_val), last_val),
                          (jnp.moveaxis(rew, -1, 0), jnp.moveaxis(val, -1, 0)),
                          reverse=True)
    adv = jnp.moveaxis(adv, 0, -1)
    return adv, adv + val


def agent_grads(cfg, env_params, params_m, traj, state):
    """Per-agent PPO loss and gradient over its B * P * n_rl transitions."""
    env = cfg["env"]
    last_val = jax.vmap(lambda p, par, s: jax.vmap(
        lambda ss: value(par, observe(env, p, ss)))(s))(
        env_params, params_m, state)                               # (m, B, n_rl)
    rew = jnp.broadcast_to(traj["rew"][..., None], traj["val"].shape)
    val = jnp.moveaxis(traj["val"], 2, -1)                         # (m,B,n_rl,P)
    adv, ret = gae(jnp.moveaxis(rew, 2, -1), val, last_val,
                   cfg["gamma"], cfg["lam"])
    adv, ret = jnp.moveaxis(adv, -1, 2), jnp.moveaxis(ret, -1, 2)
    m = adv.shape[0]
    data = {"obs": traj["obs"].reshape(m, -1, traj["obs"].shape[-1]),
            "act": traj["act"].reshape(m, -1, traj["act"].shape[-1]),
            "logp_old": traj["logp_old"].reshape(m, -1),
            "adv": adv.reshape(m, -1), "ret": ret.reshape(m, -1)}
    loss_grad = jax.value_and_grad(functools.partial(ppo_loss, ppo=cfg["ppo"]))
    # In blocks of agents, so that the reference fits beside what it holds.
    return jax.lax.map(lambda a: loss_grad(*a), (params_m, data),
                       batch_size=min(m, AGENT_BLOCK))


# --- the job ------------------------------------------------------------------

def kind_module(family: str, kind: str):
    """The reference of one kind of strategy, topology, optimizer or
    payload: ``fedbench/reference/<family>/<kind>.py``."""
    name = f"fedbench.reference.{family}.{kind}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(f"fedbench: no reference {family}/{kind}.py") from e


class Job:
    """One training job of the configuration, driven update by update."""

    def __init__(self, cfg: dict, env_params):
        self.cfg = cfg
        self.env_params = env_params
        self.m = cfg["agents"]
        self.b = cfg["envs_per_agent"]
        self.tau = cfg["tau"]
        self.precision = cfg["matmul_precision"]
        self.strategy = kind_module(
            "strategies", cfg["strategy"]["kind"]).Strategy(cfg)
        self.opt_spec = cfg["optimizer"]
        self.opt = kind_module("optimizers", self.opt_spec["kind"])
        self.comm = cfg.get("comm")
        self.encode = self.comm and kind_module("comm", self.comm["kind"]).keep
        self.block = self.m if self.m <= FLEET_BLOCK else FLEET_BLOCK // 2
        if self.m % self.block:
            raise ValueError("reference: fleet must split into equal blocks")
        self._collect = jax.jit(self._collect_impl)
        self._local = jax.jit(self._local_impl)
        self._sync = jax.jit(self._sync_impl)
        self._eval = jax.jit(self._eval_impl)
        self._reset = jax.jit(lambda p, k: fleet_reset(
            cfg["env"], p, k, self.m, self.b))

    # Each piece traces under the configuration's matmul precision.
    def _collect_impl(self, env_params, flat, state, key, lo):
        """Rollout and gradients of one block of agents."""
        with jax.default_matmul_precision(self.precision):
            cfg = self.cfg
            rk, _ = jax.random.split(key)
            params_m = unravel(flat, cfg["policy"])
            state, traj = rollout(cfg["env"], env_params, params_m, state,
                                  rk, cfg["minibatch"], self.m, lo)
            loss, grads = agent_grads(cfg, env_params, params_m, traj, state)
            g = ravel(grads, lead=1)
            return state, g, loss.mean(), jnp.mean(traj["rew"])

    def _blocks(self, fn, *trees):
        """``fn(lo, *block_of_each_tree)`` over equal blocks of agents."""
        outs = []
        for lo in range(0, self.m, self.block):
            part = jax.tree.map(lambda x: x[lo:lo + self.block], trees)
            outs.append(fn(lo, *part))
        return outs

    def collect(self, flat, state, key):
        outs = self._blocks(
            lambda lo, p, f, s: self._collect(p, f, s, key, lo),
            self.env_params, flat, state)
        cat = lambda i: jax.tree.map(lambda *x: jnp.concatenate(x),
                                     *[o[i] for o in outs])
        return (cat(0), cat(1), jnp.mean(jnp.stack([o[2] for o in outs])),
                jnp.mean(jnp.stack([o[3] for o in outs])))

    def _local_impl(self, flat, g, opt, comm, offset):
        """The strategy's step direction, then the optimizer's step."""
        d, comm = self.strategy.direction(g, comm, offset)
        flat, opt = self.opt.step(flat, d, opt, self.cfg["eta"], self.opt_spec)
        return flat, opt, comm

    def _sync_impl(self, flat, opt, comm):
        """The server sync: the mean of the replicas, or with a ``comm`` the
        mean of each agent's encoded delta from the server row, the unsent
        remainder kept as error feedback; then the optimizer's."""
        if self.comm:
            delta = flat - comm["ref"][None, :]
            if "err_up" in comm:
                delta = delta + comm["err_up"]
            sent = self.encode(delta, self.comm)
            row = comm["ref"] + sent.sum(axis=0) / self.m
            comm = dict(comm, ref=row)
            if "err_up" in comm:
                comm["err_up"] = delta - sent
        else:
            row = flat.mean(axis=0)
        return jnp.broadcast_to(row, flat.shape), self.opt.sync(opt), comm

    def _eval_impl(self, env_params, server_row, lo):
        """Sum over one block of agents of the plain gradient of the server
        policy on the fixed evaluation stream."""
        cfg = self.cfg
        m = env_params["dt"].shape[0]
        k_reset, k_roll = jax.random.split(jax.random.key(cfg["eval_seed"]))
        flat = jnp.broadcast_to(server_row, (m, server_row.shape[0]))
        keys = jax.random.split(k_reset, self.m * self.b).reshape(
            (self.m, self.b))
        keys = jax.lax.dynamic_slice_in_dim(keys, lo, m)
        state = jax.vmap(lambda p, ks: jax.vmap(
            lambda k: env_reset(cfg["env"], p, k))(ks))(env_params, keys)
        k_roll, _ = jax.random.split(k_roll)
        with jax.default_matmul_precision(self.precision):
            params_m = unravel(flat, cfg["policy"])
            state, traj = rollout(cfg["env"], env_params, params_m, state,
                                  k_roll, cfg["minibatch"], self.m, lo)
            _, grads = agent_grads(cfg, env_params, params_m, traj, state)
        return ravel(grads, lead=1).sum(axis=0)

    def eval_grad_sq(self, server_row) -> float:
        """Squared norm of the agent-mean plain gradient of the server
        policy on the fixed evaluation stream."""
        parts = self._blocks(lambda lo, p: self._eval(p, server_row, lo),
                             self.env_params)
        g = sum(parts) / self.m
        return float(jnp.sum(g * g))

    def run(self, key, n_epochs: int) -> dict:
        """The whole job from its key: per-epoch metrics, the initial and
        final server parameters (flat rows) and the agent-mean gradient of
        the first update."""
        cfg = self.cfg
        n = n_params(cfg["policy"])
        key, pk = jax.random.split(key)
        init = ravel(init_policy(pk, cfg["policy"]))
        flat = jnp.broadcast_to(init, (self.m, n))
        opt = self.opt.init(flat, self.opt_spec)
        comm = self.strategy.init(flat)
        if self.comm:
            comm["ref"] = init
            if self.comm["error_feedback"]:
                comm["err_up"] = jnp.zeros((self.m, n), jnp.float32)
        updates = cfg["epoch_len"] // cfg["minibatch"]
        metrics = {"loss": [], "nas": [], "grad_sq": []}
        first_grad, k = None, 0
        for _ in range(n_epochs):
            key, ek = jax.random.split(key)
            state = self._reset(self.env_params, ek)
            losses, nas = [], []
            for _ in range(updates):
                key, rk = jax.random.split(key)
                state, g, loss, rew = self.collect(flat, state, rk)
                if first_grad is None:
                    first_grad = g.mean(axis=0)
                flat, opt, comm = self._local(flat, g, opt, comm, k % self.tau)
                k += 1
                if k % self.tau == 0:
                    flat, opt, comm = self._sync(flat, opt, comm)
                losses.append(loss)
                nas.append(rew)
            metrics["loss"].append(float(np.mean(jax.device_get(losses))))
            metrics["nas"].append(float(np.mean(jax.device_get(nas))))
            metrics["grad_sq"].append(self.eval_grad_sq(flat.mean(axis=0)))
        return {"metrics": metrics,
                "init": np.asarray(init),
                "server": np.asarray(flat.mean(axis=0)),
                "first_grad": np.asarray(first_grad)}


def period_bytes(cfg: dict) -> int:
    """Wire bytes of one federation period: every agent's uplink once, plus
    each payload agents receive from one another within the period. A
    dense payload is 4 bytes per parameter."""
    n = n_params(cfg["policy"])
    comm = cfg.get("comm")
    per = (kind_module("comm", comm["kind"]).payload_bytes(comm, n) if comm
           else 4 * n)
    strategy = kind_module("strategies", cfg["strategy"]["kind"]).Strategy(cfg)
    return (cfg["agents"] + strategy.receives_per_period()) * per

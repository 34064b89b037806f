"""The plain references against the system under test, piece by piece, at a
small size on the CPU. Whole jobs are compared in test_fedbench_cells.py."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench.harness import load_json
from fedbench.reference import fedrl as ref
from fedbench.reference import serve as sref
from fedbench.reference.strategies import periodic

HERE = os.path.dirname(os.path.abspath(__file__))
VPA = load_json(os.path.join(HERE, "configs", "fleet1k-vpa.json"))
CIRL = load_json(os.path.join(HERE, "configs", "fleet10k-cirl.json"))


def _tree_rows(tree):
    return np.asarray(jax.flatten_util.ravel_pytree(tree)[0])


def test_config_states_the_program_scenario_and_policy():
    from repro.rl.env import EnvParams
    from repro.rl.fedrl import policy_payload_elems
    from repro.rl.scenarios import get_scenario

    sc = get_scenario(VPA["scenario"])
    env = VPA["env"]
    assert env["n_vehicles"] == sc.cfg.n_vehicles
    assert tuple(env["rl_indices"]) == sc.cfg.rl_indices
    for f in EnvParams._fields:
        assert env[f] == pytest.approx(getattr(sc.cfg, f)), f
    assert env["hetero_scale"] == sc.hetero_scale
    assert tuple(env["hetero_fields"]) == sc.hetero_fields
    assert ref.n_params(VPA["policy"]) == policy_payload_elems() \
        == VPA["policy"]["params"]


def test_fleet_and_policy_init_match():
    from repro.rl import make_fleet
    from repro.rl.policy import init_policy

    key = jax.random.key(3)
    _, prog = make_fleet("figure_eight", 16, key)
    mine = ref.fleet_env_params(VPA["env"], 16, key)
    for f in ref.ENV_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(prog, f)),
                                      np.asarray(mine[f]))
    np.testing.assert_array_equal(
        _tree_rows(init_policy(key, 6)),
        np.asarray(ref.ravel(ref.init_policy(key, VPA["policy"]))))


def test_env_reset_observe_and_step_match():
    from repro.rl.env import FIGURE_EIGHT, env_reset, env_step, get_obs
    from repro.rl.scenarios import make_fleet

    key = jax.random.key(7)
    _, prog_p = make_fleet("figure_eight", 4, key)
    mine_p = ref.fleet_env_params(VPA["env"], 4, key)
    for i in range(4):
        pp = jax.tree.map(lambda x: x[i], prog_p)
        mp = {k: v[i] for k, v in mine_p.items()}
        k = jax.random.fold_in(key, i)
        s_prog = env_reset(FIGURE_EIGHT, k, params=pp)
        s_mine = ref.env_reset(VPA["env"], mp, k)
        np.testing.assert_allclose(s_prog.x, s_mine["x"], rtol=1e-6)
        for t in range(40):
            acts = jax.random.uniform(jax.random.fold_in(k, t), (7,),
                                      minval=-1.5, maxval=1.5)
            np.testing.assert_allclose(get_obs(FIGURE_EIGHT, s_prog, params=pp),
                                       ref.observe(VPA["env"], mp, s_mine),
                                       rtol=1e-5, atol=1e-6)
            s_prog, r_prog, _ = env_step(FIGURE_EIGHT, s_prog, acts, params=pp)
            s_mine, r_mine = ref.env_step(VPA["env"], mp, s_mine, acts)
            np.testing.assert_allclose(s_prog.v, s_mine["v"], rtol=1e-5,
                                       atol=1e-5)
            assert bool(s_prog.crashed) == bool(s_mine["crashed"])
            np.testing.assert_allclose(r_prog, r_mine, rtol=1e-5, atol=1e-6)


def _batch(key, d=56):
    k = jax.random.split(key, 5)
    return {"obs": jax.random.normal(k[0], (d, 6)),
            "act": jax.random.normal(k[1], (d, 1)),
            "logp_old": jax.random.normal(k[2], (d,)) - 1.0,
            "adv": jax.random.normal(k[3], (d,)),
            "ret": jax.random.normal(k[4], (d,))}


def test_policy_forward_and_ppo_loss_and_gradient_match():
    from repro.rl.policy import init_policy, policy_apply, policy_value
    from repro.rl.ppo import ppo_loss

    key = jax.random.key(11)
    params = init_policy(key, 6)
    data = _batch(key)
    mean, _ = policy_apply(params, data["obs"])
    np.testing.assert_allclose(mean, ref.action_mean(params["pi"], data["obs"]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(policy_value(params, data["obs"]),
                               ref.value(params, data["obs"]), rtol=1e-5,
                               atol=1e-6)
    l_prog, g_prog = jax.value_and_grad(ppo_loss)(params, data)
    l_mine, g_mine = jax.value_and_grad(ref.ppo_loss)(params, data, VPA["ppo"])
    assert float(l_prog) == pytest.approx(float(l_mine), rel=1e-5)
    np.testing.assert_allclose(_tree_rows(g_prog), np.asarray(ref.ravel(g_mine)),
                               rtol=1e-4, atol=1e-6)


def _small(cfg, m):
    return dict(cfg, agents=m, tau=3,
                strategy=dict(cfg["strategy"], **(
                    {"taus": {"lo": 1, "hi": 3, "seed": 0}}
                    if "taus" in cfg["strategy"] else {})))


def test_vpa_adam_step_and_mean_match():
    from repro.core import make_strategy, uniform_taus
    from repro.optim.flat import FlatOptimizer, server_average_state

    m, n = 12, 40
    cfg = _small(VPA, m)
    job = ref.Job(cfg, ref.fleet_env_params(cfg["env"], m, jax.random.key(0)))
    taus = uniform_taus(1, 3, m, seed=0)
    np.testing.assert_array_equal(
        taus, periodic.uniform_taus(cfg["strategy"]["taus"], m))
    strat = make_strategy("periodic", tau=3, taus=taus, m=m, backend="jnp")
    o = cfg["optimizer"]
    opt = FlatOptimizer("adam", b1=o["b1"], b2=o["b2"], eps=o["eps"])
    k = jax.random.split(jax.random.key(1), 3)
    flat = jax.random.normal(k[0], (m, n))
    state_p, state_m = opt.init(flat), {"mu": jnp.zeros((m, n)),
                                         "nu": jnp.zeros((m, n)),
                                         "t": jnp.zeros((), jnp.int32)}
    fp, fm = flat, flat
    for step in range(3):
        g = jax.random.normal(jax.random.fold_in(k[1], step), (m, n))
        fp, state_p, _ = strat.flat_local_step(fp, g, step, cfg["eta"], opt,
                                               state_p, {})
        fm, state_m, _ = job._local_impl(fm, g, state_m, {}, step)
        np.testing.assert_allclose(fp, fm, rtol=1e-6, atol=1e-7)
    fp, _ = strat.flat_sync(fp, {})
    state_p = server_average_state(strat, state_p)
    fm, state_m, _ = job._sync_impl(fm, state_m, {})
    np.testing.assert_allclose(fp, fm, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(state_p["nu"], state_m["nu"], rtol=1e-6)


def test_cirl_topk_gossip_and_sync_with_error_feedback_match():
    from repro.comm import topk
    from repro.core import make_strategy, topology

    m, n, kk = 64, 300, 16
    cfg = dict(_small(CIRL, m), comm=dict(CIRL["comm"], k=kk))
    job = ref.Job(cfg, ref.fleet_env_params(cfg["env"], m, jax.random.key(0)))
    topo = topology.knn_ring(m, cfg["strategy"]["topology"]["k"])
    strat = make_strategy("consensus", tau=3, topo=topo,
                          eps=0.5 / topo.max_degree, m=m, backend="jnp",
                          comm=topk(kk))
    assert strat.sparse
    k = jax.random.split(jax.random.key(2), 2)
    flat = jax.random.normal(k[0], (m, n))
    comm_p = strat.init_comm_state(flat)
    comm_m = {"ref": flat[0], "err_up": jnp.zeros((m, n)),
              "err_gossip": jnp.zeros((m, n))}
    fp, fm = flat, flat
    for step in range(3):
        g = jax.random.normal(jax.random.fold_in(k[1], step), (m, n))
        fp, _, comm_p = strat.flat_local_step(fp, g, step, cfg["eta"], None,
                                              {}, comm_p)
        fm, _, comm_m = job._local_impl(fm, g, {}, comm_m, step)
        np.testing.assert_allclose(fp, fm, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(comm_p["err_gossip"], comm_m["err_gossip"],
                                   atol=1e-7)
    fp, comm_p = strat.flat_sync(fp, comm_p)
    fm, _, comm_m = job._sync_impl(fm, {}, comm_m)
    np.testing.assert_allclose(fp, fm, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(comm_p["err_up"], comm_m["err_up"], atol=1e-7)


@pytest.mark.parametrize("cfg", [VPA, CIRL], ids=["vpa", "cirl"])
def test_period_bytes_match_the_ledger(cfg):
    from fedbench.traffic import train_periods as tp
    from repro.rl.fedrl import fedrl_ledger

    small = dict(cfg, agents=64)
    pcfg = tp.program_config(small, n_epochs=2)
    assert fedrl_ledger(pcfg).total_bytes() == ref.period_bytes(small)


def test_served_decision_matches_the_engine_path():
    from repro.kernels import dispatch

    key = jax.random.key(5)
    pi = sref.make_pi(key, CIRL["policy"])
    obs = np.asarray(jax.random.normal(key, (64, 6)), np.float32)
    prog = dispatch.policy_infer(obs, pi, jnp.zeros(6), jnp.ones(6),
                                 jnp.zeros((64, 1)), backend="jnp")
    np.testing.assert_allclose(prog, sref.decisions(pi, obs, block=32),
                               rtol=1e-5, atol=1e-6)
    # The control really is coarser than the reference.
    gap = np.abs(sref.decisions(pi, obs, fn=sref.decide_fp8)
                 - sref.decisions(pi, obs)).max()
    assert gap > 1e-2

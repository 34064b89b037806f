"""Generator ``train_periods``: closed-loop, back-to-back federation periods.

The system under test is ``repro.rl.run_fedrl_core`` on the flat carry: one
compiled program runs a whole job of ``periods_per_call`` federation periods
from its key (reset, P-step rollouts, PPO gradients, the strategy's local
steps and syncs, and the per-epoch evaluation). Set-up compiles it once and
drives it through its first ``checked_calls`` calls (a number the
configuration gives, so that the reference stays near the window's length),
on keys drawn from the seed; the window then calls the same executable back to back on further
keys, one call in flight behind the one being waited for, until the
window's length has passed. The window ends at the ``block_until_ready`` of
its last call.

``env_steps_per_s``: agent env-steps of the periods completed in the window
over the window's time, where one period is m * B * tau * P env-steps (one
of the m * B environments advancing one ``dt``). Evaluation steps are not
counted.

``correct``: the plain reference (``fedbench.reference.fedrl``) re-runs the
checked calls' jobs from the same keys once the window has closed; their
per-epoch loss, mean reward and evaluation gradient norm, the change of
each parameter leaf, and the wire bytes of a period are compared.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from fedbench.harness import Check, CompileCounter, Outcome, memory_peak, seed_key
from fedbench.reference import fedrl as ref


# --- the system under test ----------------------------------------------------

def periods_per_epoch(cfg: dict) -> float:
    return cfg["epoch_len"] / (cfg["tau"] * cfg["minibatch"])


def epochs_per_call(cfg: dict, traffic: dict) -> int:
    e = traffic["periods_per_call"] / periods_per_epoch(cfg)
    if e != int(e):
        raise SystemExit("fedbench: periods_per_call must span whole epochs")
    return int(e)


def steps_per_period(cfg: dict) -> int:
    """Agent env-steps of one federation period: m * B * tau * P."""
    return (cfg["agents"] * cfg["envs_per_agent"] * cfg["tau"]
            * cfg["minibatch"])


def program_config(cfg: dict, n_epochs: int):
    """The system's ``FedRLConfig`` for the configuration (fleet left out:
    it is an argument of the compiled call). The strategy's own arguments
    come from ``fedbench/strategies/<kind>.py``; the payload transform is
    ``repro.comm.<kind>`` and the optimizer ``FlatOptimizer(<kind>)``
    (plain SGD where the kind is ``sgd``), each called with the block's
    other keys."""
    import importlib

    import repro.comm
    from repro.core import make_strategy
    from repro.optim.flat import FlatOptimizer
    from repro.rl import FedRLConfig
    from repro.rl.scenarios import get_scenario

    s = cfg["strategy"]
    name = f"fedbench.strategies.{s['kind']}"
    try:
        own = importlib.import_module(name).program(cfg)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(f"fedbench: no strategies/{s['kind']}.py") from e
    kw = dict(own, tau=cfg["tau"], m=cfg["agents"], backend=cfg["backend"])
    if cfg.get("comm"):
        kw["comm"] = _call(repro.comm, cfg["comm"])
    o = cfg["optimizer"]
    opt = None if o["kind"] == "sgd" else FlatOptimizer(**o)
    return FedRLConfig(
        env=get_scenario(cfg["scenario"]).cfg,
        strategy=make_strategy(s["kind"], **kw),
        eta=cfg["eta"], n_epochs=n_epochs, epoch_len=cfg["epoch_len"],
        minibatch=cfg["minibatch"], gamma=cfg["gamma"], lam=cfg["lam"],
        eval_seed=cfg["eval_seed"], optimizer=opt,
        num_envs=cfg["envs_per_agent"])


def _call(module, spec: dict):
    """``module.<spec's kind>`` called with the spec's other keys."""
    args = {k: v for k, v in spec.items() if k != "kind"}
    return getattr(module, spec["kind"])(**args)


@dataclasses.dataclass
class Trainer:
    """The compiled job call, its fleet and the keys it is driven with."""

    cfg: dict
    traffic: dict
    pcfg: object
    env_params: object
    call: object
    fleet_key: object
    base_key: object
    compile_s: float

    @classmethod
    def build(cls, ctx) -> "Trainer":
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro import sharding
        from repro.rl import make_fleet
        from repro.rl.fedrl import run_fedrl_core

        cfg, traffic = ctx.cell.config, ctx.cell.traffic
        pcfg = program_config(cfg, epochs_per_call(cfg, traffic))
        key = seed_key(ctx.seed)
        fleet_key, base_key = jax.random.split(key)
        rules = None
        if cfg.get("mesh"):
            rules = sharding.fleet_rules(
                sharding.fleet_mesh(cfg["mesh"]["agents"]))
        m = cfg["agents"]

        def fleet(k):
            return make_fleet(cfg["scenario"], m, k)[1]

        def job(env_params, k):
            c = dataclasses.replace(pcfg, env_params=env_params)
            return run_fedrl_core(c, k)

        with sharding.use_rules(rules):
            out_sh = None
            if rules is not None:
                out_sh = NamedSharding(rules.mesh, PartitionSpec("agents"))
            env_params = jax.jit(fleet, out_shardings=out_sh)(fleet_key)
            t0 = time.perf_counter()
            call = jax.jit(job).lower(
                env_params, jax.random.fold_in(base_key, 0)).compile()
            compile_s = time.perf_counter() - t0
        return cls(cfg, traffic, pcfg, env_params, call, fleet_key, base_key,
                   compile_s)

    def key(self, i: int):
        import jax

        return jax.random.fold_in(self.base_key, i)

    def run_checked(self) -> list:
        """The first calls, on keys 0 .. checked_calls - 1, fetched to host."""
        import jax

        outs = []
        for i in range(self.cfg["checked_calls"]):
            server, metrics = self.call(self.env_params, self.key(i))
            outs.append(jax.tree.map(np.asarray, jax.device_get(
                (server, metrics))))
        return outs

    def run_window(self, seconds: float, span):
        """Back-to-back calls on keys checked_calls, ...; one call in flight
        behind the one waited for. Returns ``(calls, seconds, in_flight)``:
        the window ends when the last counted call is ready, and the call
        then in flight is not counted."""
        import jax

        i = self.cfg["checked_calls"]
        t0 = time.perf_counter()
        with span("dispatch"):
            pending = self.call(self.env_params, self.key(i))
        calls = 0
        while True:
            i += 1
            with span("dispatch"):
                nxt = self.call(self.env_params, self.key(i))
            with span("block"):
                jax.block_until_ready(pending)
            calls += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return calls, elapsed, nxt
            pending = nxt

    def period_bytes(self) -> int:
        from repro.rl.fedrl import fedrl_ledger

        one = dataclasses.replace(
            self.pcfg, n_epochs=int(1 / periods_per_epoch(self.cfg)))
        return int(fedrl_ledger(one).total_bytes())


# --- the comparison with the reference -----------------------------------------

def leaf_change_gaps(cfg: dict, prog_server, r: dict):
    """Per leaf, the gap between the program's and the reference's norm of
    the change of that leaf over the job, against the larger of the
    reference's norm of that leaf and of the median leaf. Leaves whose
    reference gradient (agent mean, first update) is under a thousandth of
    the median leaf's are left out: they move by round-off alone. Returns
    ``{leaf: gap}`` over the leaves kept."""
    policy = cfg["policy"]
    names = ref.leaf_names(policy)
    p = ref.unravel(np.asarray(prog_server, np.float64) - r["init"], policy)
    q = ref.unravel(r["server"].astype(np.float64) - r["init"], policy)
    g = ref.unravel(r["first_grad"].astype(np.float64), policy)
    leaf = lambda t, n: np.asarray(t[n.split(".")[0]][n.split(".")[1]])
    d_p = np.array([np.linalg.norm(leaf(p, n)) for n in names])
    d_r = np.array([np.linalg.norm(leaf(q, n)) for n in names])
    g_n = np.array([np.linalg.norm(leaf(g, n)) for n in names])
    keep = g_n >= 1e-3 * np.median(g_n)
    scale = np.maximum(d_r, np.median(d_r[keep]))
    gaps = np.abs(d_p - d_r) / scale
    return {n: float(x) for n, x, k in zip(names, gaps, keep) if k}


def numbers(cfg: dict, traffic: dict, trainer: Trainer, outs: list,
            period_bytes: int) -> dict:
    """Every number that can be compared with the reference, worst over the
    checked calls: ``loss_gap``, ``nas_gap``, ``grad_sq_gap``, the worst
    leaf's change gap ``change_gap`` and the median leaf's
    ``change_median``, and ``ledger_bytes_gap``."""
    import jax

    env_params = jax.jit(lambda k: ref.fleet_env_params(
        cfg["env"], cfg["agents"], k))(trainer.fleet_key)
    job = ref.Job(cfg, env_params)
    n_epochs = epochs_per_call(cfg, traffic)
    out = dict.fromkeys(("loss_gap", "nas_gap", "grad_sq_gap", "change_gap",
                         "change_median"), 0.0)

    def worst(k, v):
        out[k] = max(out[k], v)

    for i, (server, metrics) in enumerate(outs):
        r = job.run(trainer.key(i), n_epochs)
        rm = r["metrics"]
        worst("loss_gap", _rel(metrics["loss"], rm["loss"]))
        worst("nas_gap", float(np.max(np.abs(
            np.asarray(metrics["nas"], np.float64) - rm["nas"]))))
        worst("grad_sq_gap",
              _rel(metrics["server_grad_sq_norm"], rm["grad_sq"]))
        gaps = leaf_change_gaps(cfg, ref.ravel(server), r)
        if max(gaps.values()) > out["change_gap"]:
            out["change_leaf"] = max(gaps, key=gaps.get)
        worst("change_gap", max(gaps.values()))
        worst("change_median", float(np.median(list(gaps.values()))))
    out["ledger_bytes_gap"] = float(abs(period_bytes - ref.period_bytes(cfg)))
    return out


def compare(cfg: dict, traffic: dict, trainer: Trainer, outs: list,
            period_bytes: int) -> list:
    """The numbers that the configuration gives a limit for (the ledger's
    bytes are exact), each with its limit."""
    lim = dict(cfg["limits"]["train_periods"], ledger_bytes_gap=0.0)
    got = numbers(cfg, traffic, trainer, outs, period_bytes)
    return [Check(k, got[k], v) for k, v in lim.items()]


def _rel(prog, refv) -> float:
    a = np.asarray(prog, np.float64)
    b = np.asarray(refv, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


# --- one run ------------------------------------------------------------------

def run(ctx) -> Outcome:
    import jax

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    t0 = time.perf_counter()
    trainer = Trainer.build(ctx)
    t_built = time.perf_counter()
    outs = trainer.run_checked()
    setup_s = ctx.setup_s()
    ma = trainer.call.memory_analysis()
    ctx.note("setup", build_s=f"{t_built - t0:.3f}",
             compile_s=f"{trainer.compile_s:.3f}",
             program_temp_bytes=getattr(ma, "temp_size_in_bytes", None),
             program_arg_bytes=getattr(ma, "argument_size_in_bytes", None),
             checked_calls_s=f"{time.perf_counter() - t_built:.3f}",
             setup_s=f"{setup_s:.3f}")
    with CompileCounter() as compiles, ctx.window():
        calls, window_s, in_flight = trainer.run_window(ctx.seconds, ctx.span)
    jax.block_until_ready(in_flight)
    periods = calls * traffic["periods_per_call"]
    rate = periods * steps_per_period(cfg) / window_s
    peak = memory_peak(ctx.devices)
    ctx.note("window", periods=periods, window_s=f"{window_s:.6f}",
             env_steps_per_s=f"{rate:.1f}", compiles=compiles.count,
             memory_peak_bytes=peak)
    bytes_prog = trainer.period_bytes()
    del trainer.call, trainer.env_params, in_flight
    gc.collect()
    t1 = time.perf_counter()
    checks = compare(cfg, traffic, trainer, outs, bytes_prog)
    checks.append(Check("window_compiles", float(compiles.count), 0.0))
    ctx.note("reference", seconds=f"{time.perf_counter() - t1:.3f}",
             calls=len(outs), period_bytes=bytes_prog)
    return Outcome(
        attempted=periods, failed=0, setup_s=setup_s,
        end_to_end={"env_steps_per_s": rate}, checks=checks,
        memory_peak_bytes=peak,
        layer={"periods": periods, "chips": len(ctx.devices)})

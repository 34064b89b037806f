"""Run the federated trainer and the policy server once on a TPU chip.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # the agent-sharded trainer on 4 chips,
                                      # against the same run on one chip

Everything runs in this one process (a chip belongs to one process at a
time), at the policy's real flat width n = 9,347 (6->64->64 actor plus
critic) and with fleets of m = 1,024 agents:

  (a) device    the first JAX device must be a TPU, else exit 1.
  (b) kernels   every dispatch primitive on ``backend="pallas"`` against
                ``backend="jnp"`` at m = 1,024, plus the sparse gossip
                gather at m = 10,000 with k = 8 neighbours, and the top-512
                payload threshold at m = 10,000, bitwise against
                ``lax.top_k``.
  (c) training  ``run_fedrl_core`` (the traced core of ``run_fedrl``) on a
                1,024-agent figure-eight fleet, 8 envs per agent, tau = 10,
                four 100-step epochs (two federation periods), for VPA /
                DIRL / sparse CIRL with a top-k uplink / dense CIRL, each on
                pallas and on jnp.
  (d) serving   ``ServeEngine`` on the VPA run's server policy, a few
                hundred requests through ``MicroBatchQueue``, against a jnp
                engine; no compile may happen after construction.

Each phase prints one line per check with its shapes, resolved backend,
compile and run seconds and parity numbers. Any failed check raises, so
the script exits non-zero before the last line, which on success is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

M = 1024          # agents in the kernel and training phases
M_GOSSIP = 10_000  # agents in the sparse gossip check
K_GOSSIP = 8      # neighbours per agent in the sparse gossip check
K_TOPK = 512      # entries a top-k payload row keeps
ENVS = 8          # parallel envs per agent
TAU = 10

# Kernel parity (b): both paths accumulate in fp32 and differ only in
# association order (blocked sums, fused multiply-adds) and in the chip's
# division/sqrt, a few ulps each. 1e-5 of the output's scale is ~80 fp32
# ulps; a tiling or indexing fault shows up at the scale of the data.
KERNEL_RTOL = 1e-5
# Training (c): those ulps also reach the rollouts, whose actions and
# dynamics are continuous, over 20 updates at lr 1e-3; 1e-4 on parameters of
# scale ~0.1-1 leaves headroom for that drift and still catches a wrong
# update, which moves parameters by ~lr per step.
TRAIN_ATOL = 1e-4
# Serving (d): both engines multiply fp32 on the MXU at its default
# precision (bf16 passes, ~3 significant digits), in different orders;
# actions are tanh outputs in [-1, 1].
SERVE_ATOL = 1e-2


def emit(phase: str, **fields) -> None:
    """One ``[phase] key=value ...`` line; non-string values as JSON."""
    print(f"[{phase}] " + " ".join(
        f"{k}={v if isinstance(v, str) else json.dumps(v)}"
        for k, v in fields.items()), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def compile_and_run(fn, *args):
    """``(out, compile_s, run_s, hlo_text)`` of ``jax.jit(fn)`` on ``args``."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1, compiled.as_text()


def max_dev(a, b) -> float:
    import jax
    import numpy as np

    return max(
        float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def max_abs(a) -> float:
    import jax
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(x)))) for x in jax.tree.leaves(a))


# --- (b) kernels --------------------------------------------------------------

def primitive_cases(m: int, n: int, m_gossip: int, key):
    """``(name, fn(backend) -> callable, args)`` for every dispatch primitive."""
    import jax
    import jax.numpy as jnp

    from repro.comm import topk_threshold
    from repro.core import topology as T
    from repro.kernels import dispatch

    k1, k2, k3 = jax.random.split(key, 3)
    g = jax.random.normal(k1, (m, n), jnp.float32)
    g2 = jax.random.normal(k2, (m, n), jnp.float32)
    w = jax.random.uniform(k3, (m,), jnp.float32)
    mom = {"mu": 0.1 * g2}
    adam = {"mu": 0.1 * g2, "nu": 0.01 * g2 * g2, "t": jnp.ones((), jnp.int32)}
    topo = T.knn_ring(m, K_GOSSIP)
    mix = jnp.asarray(T.mixing_matrix(topo, 0.5 / topo.max_degree), jnp.float32)
    nl = T.knn_ring_neighbors(m_gossip, K_GOSSIP)
    nl_w = T.neighbor_weights(nl, 0.5 / K_GOSSIP)
    g_big = jax.random.normal(k1, (m_gossip, n), jnp.float32)
    return [
        ("decay_accum", lambda b: lambda a, x, d: dispatch.decay_accum(
            a, x, d, backend=b), (g, g2, w)),
        ("scale_rows", lambda b: lambda x, d: dispatch.scale_rows(
            x, d, backend=b), (g, w)),
        ("flat_opt_update[momentum]", lambda b: lambda p, x, d, s:
            dispatch.flat_opt_update(p, x, d, s, kind="momentum", lr=1e-3,
                                     backend=b), (g, g2, w, mom)),
        ("flat_opt_update[adam]", lambda b: lambda p, x, d, s:
            dispatch.flat_opt_update(p, x, d, s, kind="adam", lr=1e-3,
                                     backend=b), (g, g2, w, adam)),
        ("consensus_mix", lambda b: lambda x, p: dispatch.consensus_mix(
            x, p, backend=b), (g, mix)),
        ("row_mean", lambda b: lambda x: dispatch.row_mean(x, backend=b), (g,)),
        ("topk_scatter", lambda b: lambda x: dispatch.topk_scatter(
            x, topk_threshold(x, K_TOPK), backend=b), (g,)),
        ("consensus_gather", lambda b: lambda x, i, v: dispatch.consensus_gather(
            x, i, v, backend=b), (g_big, jnp.asarray(nl.idx), nl_w)),
    ]


def phase_kernels(backend: str, m: int, n: int, m_gossip: int) -> None:
    import jax

    for name, make, args in primitive_cases(m, n, m_gossip, jax.random.key(0)):
        out, c_s, r_s, _ = compile_and_run(make(backend), *args)
        ref = compile_and_run(make("jnp"), *args)[0]
        dev = max_dev(out, ref)
        tol = KERNEL_RTOL * max(1.0, max_abs(ref))
        emit("b kernels", prim=name, shape=args[0].shape,
             backend=backend, compile_s=f"{c_s:.3f}", run_s=f"{r_s:.4f}",
             max_abs_dev=f"{dev:.3e}", tol=f"{tol:.3e}")
        check(dev <= tol, f"{name}: pallas deviates {dev} from jnp (tol {tol})")
    check_topk_threshold(backend, m_gossip, n, jax.random.key(1))


def check_topk_threshold(backend: str, m: int, n: int, key) -> None:
    """The radix-select threshold against ``lax.top_k``, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch

    x = jax.random.normal(key, (m, n), jnp.float32)
    got, c_s, r_s, _ = compile_and_run(
        lambda v: dispatch.topk_threshold(v, K_TOPK, backend=backend), x)
    want = compile_and_run(
        lambda v: dispatch.topk_threshold(v, K_TOPK, backend="jnp"), x)[0]
    differ = int(np.sum(np.asarray(got).view(np.int32)
                        != np.asarray(want).view(np.int32)))
    emit("b kernels", prim="topk_threshold", shape=x.shape, k=K_TOPK,
         backend=backend, compile_s=f"{c_s:.3f}", run_s=f"{r_s:.4f}",
         rows_differing=differ)
    check(differ == 0, f"topk_threshold: {differ} of {m} rows differ from "
          f"lax.top_k")


# --- (c) training ---------------------------------------------------------------

def strategy_specs(m: int):
    """The paper's three schemes as the training phase runs them."""
    from repro.comm import topk
    from repro.core import make_strategy, topology as T, uniform_taus
    from repro.core.decay import exponential_decay
    from repro.optim.flat import FlatOptimizer

    sparse = T.knn_ring(m, K_GOSSIP)
    dense = T.erdos_renyi(m, 0.3, seed=0)
    return {
        "VPA": (lambda b: make_strategy(
            "periodic", tau=TAU, taus=uniform_taus(1, TAU, m), m=m, backend=b),
            FlatOptimizer("adam")),
        "DIRL": (lambda b: make_strategy(
            "decay", tau=TAU, m=m, decay=exponential_decay(0.95), backend=b),
            None),
        "CIRL-sparse-topk": (lambda b: make_strategy(
            "consensus", tau=TAU, topo=sparse, eps=0.5 / sparse.max_degree, m=m,
            comm=topk(512), backend=b), None),
        "CIRL-dense": (lambda b: make_strategy(
            "consensus", tau=TAU, topo=dense, eps=0.5 / dense.max_degree, m=m,
            backend=b), None),
    }


def fleet_config(strategy, optimizer, m: int, envs: int):
    import jax

    from repro.rl import FedRLConfig, make_fleet

    env, env_params = make_fleet("figure_eight", m, jax.random.key(1))
    return FedRLConfig(
        env=env, strategy=strategy, optimizer=optimizer, num_envs=envs,
        env_params=env_params, epoch_len=100, minibatch=20, n_epochs=4,
    )


def train(cfg):
    """``(server_params, metrics, ledger, compile_s, run_s, hlo)`` of one run."""
    import jax
    import numpy as np

    from repro.rl.fedrl import fedrl_ledger, run_fedrl_core

    (server, metrics), c_s, r_s, hlo = compile_and_run(
        lambda seed: run_fedrl_core(cfg, jax.random.key(seed)), np.int32(0)
    )
    metrics = jax.tree.map(np.asarray, jax.device_get(metrics))
    return server, metrics, fedrl_ledger(cfg), c_s, r_s, hlo


def compare_runs(phase: str, label: str, backend: str, run, ref, shape) -> None:
    import numpy as np

    server, metrics, ledger, c_s, r_s, _ = run
    ref_server, _, ref_ledger = ref[:3]
    finite = all(np.all(np.isfinite(v)) for v in metrics.values())
    dev = max_dev(server, ref_server)
    bytes_k, bytes_r = ledger.table_row(), ref_ledger.table_row()
    emit(phase, strategy=label, fleet=shape, backend=backend,
         compile_s=f"{c_s:.2f}", run_s=f"{r_s:.2f}",
         final_nas=f"{float(metrics['nas'][-1]):.6f}",
         final_grad_sq=f"{float(metrics['server_grad_sq_norm'][-1]):.6e}",
         server_max_abs_dev=f"{dev:.3e}", tol=TRAIN_ATOL,
         ledger_bytes=bytes_k["total_bytes"], ledger_equal=bytes_k == bytes_r)
    check(finite, f"{label}: non-finite metrics")
    check(dev <= TRAIN_ATOL, f"{label}: server params deviate {dev} from reference")
    check(bytes_k == bytes_r, f"{label}: ledgers differ {bytes_k} vs {bytes_r}")


def phase_training(backend: str, m: int, envs: int):
    """Every strategy on ``backend`` against jnp; returns the VPA server."""
    servers = {}
    for label, (make, opt) in strategy_specs(m).items():
        run = train(fleet_config(make(backend), opt, m, envs))
        ref = train(fleet_config(make("jnp"), opt, m, envs))
        compare_runs("c training", label, backend, run, ref, f"{m}x{envs}")
        servers[label] = run[0]
    return servers["VPA"]


# --- (d) serving ------------------------------------------------------------------

WAVES = (5, 50, 200, 345)  # one wave per default bucket: 8 / 64 / 256 / 1024


def phase_serving(backend: str, params) -> None:
    import numpy as np

    from repro.analysis.retrace import count_compiles
    from repro.serve import MicroBatchQueue, ObsRequest, ServeEngine

    t0 = time.perf_counter()
    engine = ServeEngine(params, backend=backend)
    c_s = time.perf_counter() - t0
    ref = ServeEngine(params, backend="jnp")
    queue = MicroBatchQueue(engine.max_batch(), engine.obs_dim)
    rng = np.random.default_rng(0)
    dev, n_req, t_run = 0.0, 0, 0.0
    with count_compiles() as compiles:
        for wave in WAVES:
            queue.push_all([
                ObsRequest(client_id=i, t_arrival=float(n_req + i),
                           obs=rng.standard_normal(engine.obs_dim))
                for i in range(wave)
            ])
            n_req += wave
            while (batch := queue.next_batch()) is not None:
                obs, _ = batch
                t1 = time.perf_counter()
                act = engine.decide(obs)
                t_run += time.perf_counter() - t1
                dev = max(dev, max_dev(act, ref.decide(obs)))
    emit("d serving", requests=n_req, buckets=engine.buckets,
         bucket_calls={str(b): c for b, c in engine.bucket_calls.items()},
         backend=engine.backend,
         compile_s=f"{c_s:.2f}", run_s=f"{t_run:.4f}",
         max_abs_dev=f"{dev:.3e}", tol=SERVE_ATOL,
         compiles_after_construction=compiles.count)
    check(engine.backend == backend, f"engine resolved {engine.backend}")
    check(compiles.count == 0, f"{compiles.count} compiles on the serving path")
    check(dev <= SERVE_ATOL, f"decisions deviate {dev} from the jnp engine")


# --- four chips ---------------------------------------------------------------------

def phase_sharded(n_chips: int, m: int, n: int, envs: int) -> None:
    """VPA under the agent-sharding rules on ``n_chips`` vs one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro import sharding
    from repro.kernels import dispatch

    rules = sharding.fleet_rules(sharding.fleet_mesh(n_chips))
    with sharding.use_rules(rules):
        try:
            dispatch.resolve_backend("pallas")
        except ValueError as e:
            reason = str(e)
        else:
            raise SystemExit("chip_smoke: FAILED: pallas was not refused "
                             "under the agent mesh")
    backend = "jnp"
    emit("4 sharded", backend=backend, why=reason)
    # Where the driver's shard_agents puts the (m, n) flat carry.
    spec = rules.spec(("agents", None), (m, n))
    carry = jax.device_put(jnp.zeros((m, n), jnp.float32),
                           NamedSharding(rules.mesh, spec))
    shards = {str(s.device.id): s.data.shape for s in carry.addressable_shards}
    make, opt = strategy_specs(m)["VPA"]
    cfg = fleet_config(make(backend), opt, m, envs)
    with sharding.use_rules(rules):
        run = train(cfg)
    hlo = run[-1]
    shard_dims = f"[{m // n_chips},{n}]"
    emit("4 sharded", carry_spec=str(spec), shard_shapes=shards,
         shard_in_hlo=shard_dims in hlo, full_carry_in_hlo=f"[{m},{n}]" in hlo,
         all_gathers=hlo.count(" all-gather("))
    check(all(s == (m // n_chips, n) for s in shards.values()),
          f"carry shards {shards}")
    check(shard_dims in hlo and f"[{m},{n}]" not in hlo,
          "the sharded program does not keep the carry in per-device shards")
    compare_runs("4 sharded", "VPA", backend, run, train(cfg), f"{m}x{envs}")


# --- entry point ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the agent-sharded trainer")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this script measures nothing elsewhere",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    emit("a device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache)

    from repro.rl.fedrl import policy_payload_elems

    n = policy_payload_elems()
    if args.chips == 4:
        phase_sharded(4, M, n, ENVS)
    else:
        phase_kernels("pallas", M, n, M_GOSSIP)
        server = phase_training("pallas", M, ENVS)
        phase_serving("pallas", server)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

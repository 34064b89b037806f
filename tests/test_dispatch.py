"""Backend-dispatch layer: kernel (interpret) vs pure-jnp parity + validation.

The kernel path on CPU runs the Pallas bodies in interpret mode, so these
tests prove the exact code the TPU compiles agrees with the jnp reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.strategies import (
    ConsensusStrategy,
    DecayStrategy,
    PeriodicStrategy,
    make_strategy,
)
from repro.core.decay import exponential_decay
from repro.kernels import dispatch
from repro.kernels.consensus_step import consensus_step_pallas
from repro.kernels.decay_accum import decay_accum_pallas

TAUS = np.array([4, 2, 1])  # heterogeneous -> variation masks are non-trivial


def _grads(m=3, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    # leaf sizes chosen so n = 5*7 + 11 = 46: not a multiple of any block_n
    return {
        "w": jax.random.normal(k1, (m, 5, 7)),
        "b": jax.random.normal(k2, (m, 11)),
    }


# --- backend resolution -------------------------------------------------------

def test_resolve_backend():
    assert dispatch.resolve_backend("jnp") == "jnp"
    assert dispatch.resolve_backend("interpret") == "interpret"
    expected = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert dispatch.resolve_backend("auto") == expected
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda")


def test_pallas_is_refused_while_agents_are_sharded():
    from jax.sharding import AbstractMesh

    from repro.sharding import FLEET_RULES, MeshRules, use_rules

    rules = MeshRules(mesh=AbstractMesh((2,), ("agents",)),
                      rules=dict(FLEET_RULES))
    with use_rules(rules):
        with pytest.raises(ValueError, match="backend='jnp'"):
            dispatch.resolve_backend("pallas")
        assert dispatch.resolve_backend("jnp") == "jnp"
        assert dispatch.resolve_backend("interpret") == "interpret"
    assert dispatch.resolve_backend("pallas") == "pallas"


def test_strategy_rejects_unknown_backend():
    with pytest.raises(ValueError):
        PeriodicStrategy(tau=2, m=3, backend="nope")


def test_make_strategy_passes_backend_through():
    s = make_strategy("periodic", tau=4, m=3, backend="interpret")
    assert s.backend == "interpret"
    s = make_strategy("decay", tau=4, m=3, backend="jnp")
    assert s.backend == "jnp"


# --- flat <-> tree plumbing ---------------------------------------------------

def test_stacked_ravel_spec_views():
    g = _grads()
    flat, spec = dispatch.stacked_ravel_spec(g)
    assert flat.shape == (3, 5 * 7 + 11)
    one = spec.unravel_one(flat[1])
    np.testing.assert_array_equal(one["w"], g["w"][1])
    np.testing.assert_array_equal(one["b"], g["b"][1])
    np.testing.assert_array_equal(spec.ravel_one(one), flat[1])
    back = spec.unravel(flat)
    np.testing.assert_array_equal(back["w"], g["w"])


def test_unravel_cache_is_bounded_lru():
    dispatch.clear_caches()
    assert len(dispatch._UNRAVEL_CACHE) == 0
    dispatch.stacked_ravel(_grads())
    assert len(dispatch._UNRAVEL_CACHE) == 1
    dispatch.stacked_ravel(_grads(seed=1))  # same structure -> cache hit
    assert len(dispatch._UNRAVEL_CACHE) == 1
    for i in range(dispatch._UNRAVEL_CACHE_MAXSIZE + 5):
        dispatch.stacked_ravel({"x": jnp.zeros((2, i + 1))})
    assert len(dispatch._UNRAVEL_CACHE) <= dispatch._UNRAVEL_CACHE_MAXSIZE
    dispatch.clear_caches()
    assert len(dispatch._UNRAVEL_CACHE) == 0


def test_stacked_ravel_roundtrip():
    g = _grads()
    flat, unravel = dispatch.stacked_ravel(g)
    assert flat.shape == (3, 5 * 7 + 11)
    back = unravel(flat)
    np.testing.assert_array_equal(back["w"], g["w"])
    np.testing.assert_array_equal(back["b"], g["b"])


def test_stacked_ravel_rejects_mismatched_leading_axis():
    bad = {"w": jnp.ones((3, 2)), "b": jnp.ones((4, 2))}
    with pytest.raises(ValueError):
        dispatch.stacked_ravel(bad)


# --- dispatched primitive parity ---------------------------------------------

@pytest.mark.parametrize("n", [100, 46, 4096])  # includes non-multiple-of-block
def test_decay_accum_interpret_matches_jnp_1d(n):
    ks = jax.random.split(jax.random.key(n), 2)
    acc = jax.random.normal(ks[0], (n,))
    g = jax.random.normal(ks[1], (n,))
    a = dispatch.decay_accum(acc, g, 0.7, backend="jnp")
    b = dispatch.decay_accum(acc, g, 0.7, backend="interpret", block_n=64)
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_decay_accum_per_agent_coefficients():
    m, n = 5, 37  # n deliberately not a multiple of block_n
    ks = jax.random.split(jax.random.key(0), 3)
    acc = jax.random.normal(ks[0], (m, n))
    g = jax.random.normal(ks[1], (m, n))
    d = jax.random.uniform(ks[2], (m,))
    a = dispatch.decay_accum(acc, g, d, backend="jnp")
    b = dispatch.decay_accum(acc, g, d, backend="interpret", block_n=16)
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_scale_rows_parity():
    g = jax.random.normal(jax.random.key(1), (4, 53))
    w = jnp.asarray([1.0, 0.5, 0.0, 2.0])
    a = dispatch.scale_rows(g, w, backend="jnp")
    b = dispatch.scale_rows(g, w, backend="interpret", block_n=32)
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_scale_rows_rejects_bad_shapes():
    with pytest.raises(ValueError):
        dispatch.scale_rows(jnp.zeros(6), jnp.ones(3), backend="jnp")  # 1-D g
    with pytest.raises(ValueError):
        dispatch.scale_rows(jnp.zeros((3, 6)), jnp.ones(4), backend="jnp")


def test_consensus_mix_parity():
    m, n = 6, 101  # non-multiple of block_n
    topo = T.ring(m)
    p = jnp.asarray(T.mixing_matrix(topo, 0.25), jnp.float32)
    g = jax.random.normal(jax.random.key(2), (m, n))
    a = dispatch.consensus_mix(g, p, backend="jnp")
    b = dispatch.consensus_mix(g, p, backend="interpret", block_n=32)
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_consensus_mix_low_precision_parity(dtype):
    """Kernel path must accumulate the gossip matmul in fp32 like the jnp
    reference — bf16/fp16 gradient buffers must not drift between backends."""
    m, n = 6, 101
    topo = T.ring(m)
    p = jnp.asarray(T.mixing_matrix(topo, 0.25), jnp.float32)
    g = jax.random.normal(jax.random.key(11), (m, n)).astype(dtype)
    a = dispatch.consensus_mix(g, p, backend="jnp")
    b = dispatch.consensus_mix(g, p, backend="interpret", block_n=32)
    np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decay_accum_low_precision_parity(dtype):
    acc = jax.random.normal(jax.random.key(0), (77,)).astype(dtype)
    g = jax.random.normal(jax.random.key(1), (77,)).astype(dtype)
    a = dispatch.decay_accum(acc, g, 0.3, backend="jnp")
    b = dispatch.decay_accum(acc, g, 0.3, backend="interpret", block_n=16)
    if dtype == jnp.bfloat16:
        # fp32 accumulation then one bf16 rounding: bit-identical paths
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )
    else:
        # fp32: XLA may fuse the FMA differently between paths (1-ulp)
        np.testing.assert_allclose(a, b, atol=1e-6)


# --- row_mean (server averaging, eq. 11) --------------------------------------

@pytest.mark.parametrize("n", [46, 128, 1000])  # includes non-multiple-of-block
def test_row_mean_parity(n):
    g = jax.random.normal(jax.random.key(n), (5, n))
    a = dispatch.row_mean(g, backend="jnp")
    b = dispatch.row_mean(g, backend="interpret", block_n=32)
    assert a.shape == (n,)
    np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(a, jnp.mean(g, axis=0), atol=1e-6)


def test_row_mean_bf16_accumulates_fp32():
    # 33 agents at values that round badly in bf16: an fp32 accumulation of
    # the mean is exact here, a bf16 one is not.
    g = jnp.full((33, 40), 0.1, jnp.bfloat16)
    a = dispatch.row_mean(g, backend="jnp")
    b = dispatch.row_mean(g, backend="interpret", block_n=16)
    np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)
    )


def test_row_mean_rejects_1d():
    with pytest.raises(ValueError):
        dispatch.row_mean(jnp.zeros(8), backend="jnp")


# --- derived (block_m, block_n) tiling at the policy's real width ------------
#
# No block_n override: the kernels pick their TPU blocks from shape and dtype
# (repro.kernels.tiling). n = 9,347 is the flat policy width — not a multiple
# of 128, so the last column block overhangs — and m = 3 / 13 is not a
# multiple of 8. m = 515 overhangs the 512-row block of the reducing kernels,
# which must mask the overhang (interpret mode fills it with NaN).

POLICY_N = 9347
RE_BLOCKED = ("decay_accum", "scale_rows", "momentum", "adam",
              "consensus_mix", "consensus_gather", "row_mean", "topk_threshold",
              "topk_scatter")


def _run_primitive(name, g, backend):
    m = g.shape[0]
    w = jnp.linspace(0.0, 1.5, m, dtype=jnp.float32)
    if name == "decay_accum":
        return dispatch.decay_accum(g, jnp.cos(g), w, backend=backend)
    if name == "scale_rows":
        return dispatch.scale_rows(g, w, backend=backend)
    if name in ("momentum", "adam"):
        z = jnp.full(g.shape, 0.25, jnp.float32)
        state = ({"mu": z} if name == "momentum"
                 else {"mu": z, "nu": z, "t": jnp.ones((), jnp.int32)})
        return dispatch.flat_opt_update(g, jnp.cos(g), w, state, kind=name,
                                        lr=0.05, backend=backend)
    if name == "consensus_mix":
        p = T.mixing_matrix(T.ring(m), 0.3)
        return dispatch.consensus_mix(g, jnp.asarray(p, jnp.float32),
                                      backend=backend)
    if name == "consensus_gather":
        topo = T.knn_ring(m, 2)
        nl = T.neighbor_list(topo)
        w_nl = T.neighbor_weights_from_matrix(nl, T.mixing_matrix(topo, 0.3))
        return dispatch.consensus_gather(g, nl.idx, w_nl, backend=backend)
    if name == "row_mean":
        return dispatch.row_mean(g, backend=backend)
    if name == "topk_threshold":
        return dispatch.topk_threshold(g, 50, backend=backend)
    if name == "topk_scatter":
        from repro.comm import topk_threshold

        return dispatch.topk_scatter(g, topk_threshold(g, 50), backend=backend)
    raise ValueError(name)


@pytest.mark.parametrize("m", [3, 13])
@pytest.mark.parametrize("name", RE_BLOCKED)
def test_reblocked_kernel_parity_at_policy_width(name, m):
    g = jax.random.normal(jax.random.key(m), (m, POLICY_N))
    a = jax.tree.leaves(_run_primitive(name, g, "jnp"))
    b = jax.tree.leaves(_run_primitive(name, g, "interpret"))
    for x, y in zip(a, b):
        assert x.shape == y.shape
        np.testing.assert_allclose(x, y, atol=1e-5)


@pytest.mark.parametrize("name,n", [("row_mean", POLICY_N),
                                    ("topk_threshold", POLICY_N),
                                    ("topk_scatter", POLICY_N),
                                    ("consensus_mix", 300)])
def test_reducing_kernels_mask_the_overhanging_agent_block(name, n):
    g = jax.random.normal(jax.random.key(515), (515, n))
    a = jax.tree.leaves(_run_primitive(name, g, "jnp"))
    b = jax.tree.leaves(_run_primitive(name, g, "interpret"))
    for x, y in zip(a, b):
        assert np.all(np.isfinite(np.asarray(y)))
        np.testing.assert_allclose(x, y, atol=1e-5)


# --- flat_opt_update (fused optimizer pass) -----------------------------------

def _opt_buffers(m=4, n=53, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    p = jax.random.normal(ks[0], (m, n))
    g = jax.random.normal(ks[1], (m, n))
    w = jax.random.uniform(ks[2], (m,))
    return p, g, w


def test_flat_opt_update_sgd_matches_decay_accum():
    p, g, w = _opt_buffers()
    out, state = dispatch.flat_opt_update(p, g, w, {}, kind="sgd", lr=0.1,
                                          backend="jnp")
    ref = dispatch.decay_accum(p, g, -0.1 * w, backend="jnp")
    np.testing.assert_allclose(out, ref, atol=1e-7)
    assert state == {}


@pytest.mark.parametrize("nesterov", [False, True])
def test_flat_opt_update_momentum_parity(nesterov):
    p, g, w = _opt_buffers(seed=1)
    state = {"mu": jnp.zeros(p.shape, jnp.float32)}
    pa, pb, sa, sb = p, p, dict(state), dict(state)
    for _ in range(3):
        pa, sa = dispatch.flat_opt_update(
            pa, g, w, sa, kind="momentum", lr=0.05, beta=0.9,
            nesterov=nesterov, backend="jnp")
        pb, sb = dispatch.flat_opt_update(
            pb, g, w, sb, kind="momentum", lr=0.05, beta=0.9,
            nesterov=nesterov, backend="interpret", block_n=16)
    np.testing.assert_allclose(pa, pb, atol=1e-5)
    np.testing.assert_allclose(sa["mu"], sb["mu"], atol=1e-5)


def test_flat_opt_update_momentum_matches_tree_optimizer():
    """The flat momentum rule must equal repro.optim.optimizers.momentum
    applied leaf-wise (with w folded into the grads first)."""
    from repro.optim.optimizers import momentum as tree_momentum

    p, g, w = _opt_buffers(seed=2)
    opt = tree_momentum(0.9)
    tree_state = opt.init(p)
    flat_state = {"mu": jnp.zeros(p.shape, jnp.float32)}
    pt, pf = p, p
    for _ in range(3):
        wg = g * w[:, None]
        pt, tree_state = opt.apply(wg, tree_state, pt, 0.05)
        pf, flat_state = dispatch.flat_opt_update(
            pf, g, w, flat_state, kind="momentum", lr=0.05, beta=0.9,
            backend="jnp")
    np.testing.assert_allclose(pt, pf, atol=1e-6)


def test_flat_opt_update_adam_parity():
    p, g, w = _opt_buffers(seed=3)
    z = jnp.zeros(p.shape, jnp.float32)
    sa = {"mu": z, "nu": z, "t": jnp.zeros((), jnp.int32)}
    sb = {"mu": z, "nu": z, "t": jnp.zeros((), jnp.int32)}
    pa, pb = p, p
    for _ in range(3):
        pa, sa = dispatch.flat_opt_update(pa, g, w, sa, kind="adam", lr=0.01,
                                          backend="jnp")
        pb, sb = dispatch.flat_opt_update(pb, g, w, sb, kind="adam", lr=0.01,
                                          backend="interpret", block_n=16)
    assert int(sa["t"]) == int(sb["t"]) == 3
    np.testing.assert_allclose(pa, pb, atol=1e-5)
    np.testing.assert_allclose(sa["nu"], sb["nu"], atol=1e-5)


def test_flat_opt_update_adam_matches_tree_adamw():
    from repro.optim.optimizers import adamw

    p, g, w = _opt_buffers(seed=4)
    opt = adamw(b1=0.9, b2=0.95, eps=1e-8)
    tree_state = opt.init(p)
    z = jnp.zeros(p.shape, jnp.float32)
    flat_state = {"mu": z, "nu": z, "t": jnp.zeros((), jnp.int32)}
    pt, pf = p, p
    for _ in range(3):
        wg = g * w[:, None]
        pt, tree_state = opt.apply(wg, tree_state, pt, 0.01)
        pf, flat_state = dispatch.flat_opt_update(
            pf, g, w, flat_state, kind="adam", lr=0.01, b1=0.9, b2=0.95,
            backend="jnp")
    np.testing.assert_allclose(pt, pf, atol=1e-6)


def test_flat_opt_update_validation():
    p = jnp.zeros((3, 8))
    with pytest.raises(ValueError):
        dispatch.flat_opt_update(p, p, 1.0, {}, kind="rmsprop", lr=0.1)
    with pytest.raises(ValueError):  # missing state buffer
        dispatch.flat_opt_update(p, p, 1.0, {}, kind="momentum", lr=0.1,
                                 backend="jnp")
    with pytest.raises(ValueError):  # non-fp32 accumulator
        dispatch.flat_opt_update(
            p, p, 1.0, {"mu": jnp.zeros((3, 8), jnp.bfloat16)},
            kind="momentum", lr=0.1, backend="jnp")
    with pytest.raises(ValueError):  # shape mismatch
        dispatch.flat_opt_update(p, jnp.zeros((3, 9)), 1.0, {}, kind="sgd",
                                 lr=0.1, backend="jnp")


# --- strategy-level parity (the load-bearing contract) ------------------------

def _strategy_pairs():
    topo = T.ring(3)
    builders = {
        "masked": lambda b: PeriodicStrategy(tau=4, taus=TAUS, backend=b),
        "decay": lambda b: DecayStrategy(
            tau=4, taus=TAUS, decay=exponential_decay(0.9), backend=b
        ),
        "consensus": lambda b: ConsensusStrategy(
            tau=4, topo=topo, eps=0.3, rounds=2, taus=TAUS, backend=b
        ),
        "consensus-unfused": lambda b: ConsensusStrategy(
            tau=4, topo=topo, eps=0.3, rounds=2, taus=TAUS, fused=False, backend=b
        ),
    }
    return [(k, mk("jnp"), mk("interpret")) for k, mk in builders.items()]


@pytest.mark.parametrize("name,s_jnp,s_kern", _strategy_pairs(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_transform_kernel_matches_jnp(name, s_jnp, s_kern):
    g = _grads()
    for offset in range(4):
        a = s_jnp.transform(g, offset)
        b = s_kern.transform(g, offset)
        np.testing.assert_allclose(a["w"], b["w"], atol=1e-5, err_msg=f"{name}@{offset}")
        np.testing.assert_allclose(a["b"], b["b"], atol=1e-5, err_msg=f"{name}@{offset}")


@pytest.mark.parametrize("name,s_jnp,s_kern", _strategy_pairs(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_flat_update_kernel_matches_jnp(name, s_jnp, s_kern):
    g = _grads(seed=3)
    g_flat, _ = dispatch.stacked_ravel(g)
    params = jax.random.normal(jax.random.key(4), g_flat.shape)
    for offset in range(4):
        a = s_jnp.flat_update(params, g_flat, offset, 0.05)
        b = s_kern.flat_update(params, g_flat, offset, 0.05)
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=f"{name}@{offset}")


def test_flat_update_matches_tree_reference():
    """Fused flat path == transform-then-SGD in tree space (same semantics)."""
    s = DecayStrategy(tau=4, taus=TAUS, decay=exponential_decay(0.8), backend="jnp")
    g = _grads(seed=5)
    params = _grads(seed=6)
    eta = 0.1
    p_flat, unravel = dispatch.stacked_ravel(params)
    g_flat, _ = dispatch.stacked_ravel(g)
    for offset in range(4):
        tg = s.transform(g, offset)
        ref = jax.tree.map(lambda p, gg: p - eta * gg, params, tg)
        out = unravel(s.flat_update(p_flat, g_flat, offset, eta, backend="interpret"))
        np.testing.assert_allclose(ref["w"], out["w"], atol=1e-5)
        np.testing.assert_allclose(ref["b"], out["b"], atol=1e-5)


@pytest.mark.parametrize("name,s_jnp,s_kern", _strategy_pairs(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_local_update_kernel_matches_jnp(name, s_jnp, s_kern):
    """The driver entry point: tree-space local step, both backends agree."""
    g = _grads(seed=8)
    params = _grads(seed=9)
    for offset in range(4):
        a = s_jnp.local_update(params, g, offset, 0.05)
        b = s_kern.local_update(params, g, offset, 0.05)
        np.testing.assert_allclose(a["w"], b["w"], atol=1e-5, err_msg=f"{name}@{offset}")
        np.testing.assert_allclose(a["b"], b["b"], atol=1e-5, err_msg=f"{name}@{offset}")


def test_transform_inside_scan_traced_offset():
    """Kernel path must trace under lax.scan with a traced period offset."""
    s = DecayStrategy(tau=4, taus=TAUS, decay=exponential_decay(0.9),
                      backend="interpret")
    s_ref = DecayStrategy(tau=4, taus=TAUS, decay=exponential_decay(0.9),
                          backend="jnp")
    g = _grads(seed=7)
    g_flat, _ = dispatch.stacked_ravel(g)

    def run(strat):
        def body(carry, offset):
            return strat.flat_update(carry, g_flat, offset, 0.1), None
        out, _ = jax.lax.scan(body, jnp.zeros_like(g_flat), jnp.arange(4))
        return out

    np.testing.assert_allclose(run(s_ref), run(s), atol=1e-5)


# --- traced-mask strategy copies (the variation axis, with_mask) ---------------

def _mask_pairs():
    """(name, static strategy, with_mask copy holding a jnp mask) triples.

    The copy's mask is the traced-constructor output (``mask_from_taus`` fed
    a float32 schedule, exactly what the sweep's taus axis produces) — the
    static strategy keeps its numpy-at-init mask.
    """
    from repro.core.variation import mask_from_taus

    topo = T.ring(3)
    builders = {
        "masked": lambda: PeriodicStrategy(tau=4, taus=TAUS, backend="jnp"),
        "decay": lambda: DecayStrategy(
            tau=4, taus=TAUS, decay=exponential_decay(0.9), backend="jnp"
        ),
        "consensus": lambda: ConsensusStrategy(
            tau=4, topo=topo, eps=0.3, rounds=2, taus=TAUS, backend="jnp"
        ),
    }
    out = []
    for name, mk in builders.items():
        s = mk()
        mask = mask_from_taus(jnp.asarray(TAUS, jnp.float32), 4)
        out.append((name, s, s.with_mask(mask)))
    return out


@pytest.mark.parametrize("name,s_static,s_traced", _mask_pairs(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_with_mask_bitwise_on_jnp(name, s_static, s_traced):
    """Traced-mask copy == static-numpy-mask strategy, BIT-identical on the
    jnp reference path (same ops on the same values, op by op)."""
    g = _grads(seed=12)
    params = _grads(seed=13)
    g_flat, _ = dispatch.stacked_ravel(g)
    p_flat, _ = dispatch.stacked_ravel(params)
    for offset in range(4):
        a = s_static.transform(g, offset)
        b = s_traced.transform(g, offset)
        np.testing.assert_array_equal(
            np.asarray(a["w"]), np.asarray(b["w"]), err_msg=f"{name}@{offset}"
        )
        a = s_static.local_update(params, g, offset, 0.05)
        b = s_traced.local_update(params, g, offset, 0.05)
        np.testing.assert_array_equal(
            np.asarray(a["b"]), np.asarray(b["b"]), err_msg=f"{name}@{offset}"
        )
        a = s_static.flat_update(p_flat, g_flat, offset, 0.05)
        b = s_traced.flat_update(p_flat, g_flat, offset, 0.05)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{name}@{offset}"
        )


@pytest.mark.parametrize("name,s_static,s_traced", _mask_pairs(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_with_mask_interpret_parity(name, s_static, s_traced):
    """The same traced-mask copies through the interpret kernels stay within
    ulp tolerance of the static kernels (weights are kernel operands either
    way, so only harness-level fusion may differ)."""
    import copy as _copy

    g = _grads(seed=14)
    g_flat, _ = dispatch.stacked_ravel(g)
    params = jax.random.normal(jax.random.key(15), g_flat.shape)
    s_static_k = _copy.copy(s_static)
    s_traced_k = _copy.copy(s_traced)
    object.__setattr__(s_static_k, "backend", "interpret")
    object.__setattr__(s_traced_k, "backend", "interpret")
    for offset in range(4):
        a = s_static_k.flat_update(params, g_flat, offset, 0.05)
        b = s_traced_k.flat_update(params, g_flat, offset, 0.05)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, err_msg=f"{name}@{offset}"
        )


def test_consensus_with_mask_refolds_tables():
    """with_mask must refold the per-offset masked mixing tables against the
    new mask — matching what the constructor builds for the same schedule."""
    topo = T.ring(3)
    base = ConsensusStrategy(tau=4, topo=topo, eps=0.3, rounds=2, m=3)
    ref = ConsensusStrategy(tau=4, topo=topo, eps=0.3, rounds=2, taus=TAUS)
    copy_ = base.with_mask(
        jnp.asarray(ref.mask), taus=TAUS
    )
    np.testing.assert_array_equal(np.asarray(copy_.mask), ref.mask)
    np.testing.assert_allclose(np.asarray(copy_.p_e_masked), ref.p_e_masked,
                               atol=0)
    np.testing.assert_allclose(np.asarray(copy_.p_masked), ref.p_masked,
                               atol=0)
    # untouched statics survive the copy
    np.testing.assert_array_equal(copy_.p_e, base.p_e)
    assert copy_.rounds == base.rounds and copy_.backend == base.backend


def test_with_mask_refreshes_host_accounting():
    """A with_mask copy given the concrete schedule keeps the comm
    accounting consistent (c2 = sum(taus), truncated variant included)."""
    base = PeriodicStrategy(tau=4, m=3)
    copy_ = base.with_mask(
        jnp.asarray(PeriodicStrategy._build_mask(TAUS, 4)), taus=TAUS
    )
    ref = PeriodicStrategy(tau=4, taus=TAUS)
    assert copy_.comm_events_per_period() == ref.comm_events_per_period()
    for n in range(4):
        assert (copy_.comm_events_partial_period(n)
                == ref.comm_events_partial_period(n))
    # without a schedule the copy keeps the previous static accounting
    assert (base.with_mask(jnp.asarray(base.mask)).comm_events_per_period()
            == base.comm_events_per_period())


# --- kernel shape/dtype validation (no silent mis-tiling) ---------------------

def test_decay_accum_rejects_shape_mismatch():
    with pytest.raises(ValueError):
        decay_accum_pallas(jnp.zeros(8), jnp.zeros(9), 1.0, interpret=True)
    with pytest.raises(ValueError):
        decay_accum_pallas(jnp.zeros((2, 4)), jnp.zeros((2, 4)), 1.0, interpret=True)


def test_decay_accum_rejects_dtype_mismatch():
    with pytest.raises(ValueError):
        decay_accum_pallas(jnp.zeros(8, jnp.float32), jnp.zeros(8, jnp.bfloat16),
                           1.0, interpret=True)


def test_decay_accum_rejects_nonscalar_d():
    with pytest.raises(ValueError):
        decay_accum_pallas(jnp.zeros(8), jnp.zeros(8), jnp.ones(2), interpret=True)


def test_consensus_rejects_bad_mixing_shape():
    g = jnp.zeros((4, 16))
    with pytest.raises(ValueError):
        consensus_step_pallas(g, jnp.eye(5), interpret=True)  # would mis-tile
    with pytest.raises(ValueError):
        consensus_step_pallas(g, jnp.eye(3), interpret=True)
    with pytest.raises(ValueError):
        consensus_step_pallas(jnp.zeros(16), jnp.eye(4), interpret=True)


def test_consensus_rejects_integer_mixing():
    with pytest.raises(ValueError):
        consensus_step_pallas(jnp.zeros((4, 16)), jnp.eye(4, dtype=jnp.int32),
                              interpret=True)


def test_dispatch_decay_accum_rejects_bad_d_rank():
    with pytest.raises(ValueError):
        dispatch.decay_accum(jnp.zeros(8), jnp.zeros(8), jnp.ones(3), backend="jnp")


def test_dispatch_consensus_mix_rejects_bad_shapes():
    with pytest.raises(ValueError):
        dispatch.consensus_mix(jnp.zeros((4, 8)), jnp.eye(6), backend="jnp")


# --- consensus_gather (sparse neighbor-list gossip) ---------------------------


def _knn_inputs(m=12, k=4, n=101, eps_frac=0.5, seed=4):
    topo = T.knn_ring(m, k)
    nl = T.neighbor_list(topo)
    p = T.mixing_matrix(topo, eps_frac / topo.max_degree)
    w = T.neighbor_weights_from_matrix(nl, p)
    g = jax.random.normal(jax.random.key(seed), (m, n))
    return topo, nl, p, w, g


def test_consensus_gather_interpret_matches_jnp():
    _, nl, _, w, g = _knn_inputs(n=101)  # non-multiple of block_n
    a = dispatch.consensus_gather(g, nl.idx, w, backend="jnp")
    b = dispatch.consensus_gather(g, nl.idx, w, backend="interpret", block_n=32)
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_consensus_gather_bitwise_vs_full_list_reference():
    """The parity contract: the k-sparse sequential FMA chain is bit-identical
    (eager) to evaluating the full (k_max = m) list in index order — padding
    adds 0.0 * row, a floating-point no-op."""
    topo, nl, p, w, g = _knn_inputs()
    full = T.neighbor_list(topo, k_max=topo.m)
    w_full = T.neighbor_weights_from_matrix(full, p)
    with jax.disable_jit():
        sparse = dispatch.consensus_gather(g, nl.idx, w, backend="jnp")
        ref = dispatch.consensus_gather(g, full.idx, w_full, backend="jnp")
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(ref))


def test_consensus_gather_matches_dense_mix():
    topo, nl, p, w, g = _knn_inputs()
    sparse = dispatch.consensus_gather(g, nl.idx, w, backend="jnp")
    dense = dispatch.consensus_mix(g, jnp.asarray(p, jnp.float32), backend="jnp")
    np.testing.assert_allclose(sparse, dense, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_consensus_gather_low_precision_accumulates_fp32(dtype):
    _, nl, _, w, g = _knn_inputs(n=64)
    g = g.astype(dtype)
    a = dispatch.consensus_gather(g, nl.idx, w, backend="jnp")
    b = dispatch.consensus_gather(g, nl.idx, w, backend="interpret", block_n=32)
    assert a.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)
    )


def test_consensus_gather_vmaps_shared_and_per_run_weights():
    _, nl, p, w, g = _knn_inputs(n=33)
    gs = jnp.stack([g, 2.0 * g, -g])
    shared = dispatch.consensus_gather(gs, nl.idx, w, backend="jnp")
    for s in range(3):
        np.testing.assert_array_equal(
            np.asarray(shared[s]),
            np.asarray(dispatch.consensus_gather(gs[s], nl.idx, w, backend="jnp")),
        )
    ws = jnp.stack([w, 0.5 * w, jnp.zeros_like(w)])
    per_run = dispatch.consensus_gather(gs, nl.idx, ws, backend="jnp")
    for s in range(3):
        np.testing.assert_array_equal(
            np.asarray(per_run[s]),
            np.asarray(
                dispatch.consensus_gather(gs[s], nl.idx, ws[s], backend="jnp")
            ),
        )


def test_consensus_gather_padded_rows_contribute_nothing():
    topo, nl, p, w, g = _knn_inputs()
    wide = T.neighbor_list(topo, k_max=nl.k_max + 3)
    w_wide = T.neighbor_weights_from_matrix(wide, p)
    with jax.disable_jit():
        tight = dispatch.consensus_gather(g, nl.idx, w, backend="jnp")
        padded = dispatch.consensus_gather(g, wide.idx, w_wide, backend="jnp")
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(padded))


def test_consensus_gather_rejects_bad_shapes():
    _, nl, _, w, g = _knn_inputs()
    with pytest.raises(ValueError):
        dispatch.consensus_gather(g, nl.idx[:-1], w[:-1], backend="jnp")
    with pytest.raises(ValueError):
        dispatch.consensus_gather(g, nl.idx, w[:, :-1], backend="jnp")
    with pytest.raises(ValueError):
        dispatch.consensus_gather(g, nl.idx.astype(jnp.float32), w, backend="jnp")
    with pytest.raises(ValueError):
        dispatch.consensus_gather(g[0], nl.idx, w, backend="jnp")
    from repro.kernels.consensus_gather import consensus_gather_pallas

    with pytest.raises(ValueError):
        consensus_gather_pallas(g, jnp.asarray(nl.idx), jnp.asarray(w), block_n=0)

"""Comm layer: payload transforms, error feedback, the fused top-k scatter
reduction, byte-exact ledger accounting, and the compressed drivers.

The load-bearing contracts:

* ``encode`` conservation — ``sent + residual == x`` exactly in fp32, for
  every transform kind (the error-feedback algebra depends on it);
* jnp-vs-interpret parity of ``dispatch.topk_scatter`` under the shared
  threshold selection rule;
* identity comm is a bitwise no-op: the flat drivers with ``IDENTITY``
  reproduce the legacy path exactly;
* the ledger's wire bytes are exact arithmetic — dense fp32 is
  ``events * payload_elems * 4`` including partial trailing periods.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    IDENTITY,
    PayloadTransform,
    dequantize_int8,
    identity,
    qbf16,
    qint8,
    quantize_int8,
    topk,
    topk_threshold,
)
from repro.core import make_strategy, uniform_taus
from repro.core import topology as T
from repro.core.decay import exponential_decay
from repro.kernels import dispatch
from repro.rl import FIGURE_EIGHT, FedRLConfig
from repro.rl.fedrl import (
    fedrl_bytes_curve,
    fedrl_ledger,
    policy_payload_elems,
    run_fedrl_core,
)

ALL_TRANSFORMS = (identity(), topk(5), qint8(), qbf16())


def _x(m=7, n=33, seed=0):
    return jax.random.normal(jax.random.key(seed), (m, n), jnp.float32)


# --- transform specs -----------------------------------------------------------

def test_payload_transform_validation():
    with pytest.raises(ValueError, match="unknown payload transform"):
        PayloadTransform("fp8")
    with pytest.raises(ValueError, match="k >= 1"):
        PayloadTransform("topk", k=0)
    with pytest.raises(ValueError, match="k only applies"):
        PayloadTransform("int8", k=3)
    strat = make_strategy("periodic", tau=2, m=7)
    with pytest.raises(TypeError, match="PayloadTransform"):
        strat.with_comm("topk")


def test_labels_and_payload_bytes():
    assert identity().label == "dense" and not identity().enabled
    assert topk(8).label == "topk8" and topk(8).enabled
    assert qint8().label == "int8" and qbf16().label == "bf16"
    n = 100
    assert identity().payload_bytes(n) == 4 * n
    assert topk(8).payload_bytes(n) == 8 * 8
    assert topk(8).payload_bytes(4) == 8 * 4      # k clips to n
    assert qint8().payload_bytes(n) == n + 4
    assert qbf16().payload_bytes(n) == 2 * n
    with pytest.raises(ValueError):
        identity().payload_bytes(-1)


def test_transforms_are_hashable_statics():
    """jit-closable like FlatOptimizer: equal specs hash equal."""
    assert topk(8) == topk(8) and hash(topk(8)) == hash(topk(8))
    assert topk(8) != topk(9) and qint8() != qint8(error_feedback=False)
    assert IDENTITY is identity()


# --- selection / quantization primitives ---------------------------------------

def test_topk_threshold_keeps_ties():
    x = jnp.asarray([[3.0, -3.0, 1.0, 0.5], [4.0, 0.1, 0.2, 0.3]])
    th = topk_threshold(x, 2)
    np.testing.assert_array_equal(
        np.asarray(th), np.asarray([3.0, 0.3], np.float32)
    )
    # both magnitude-3 entries survive the k=2 threshold (ties included)
    keep = np.abs(np.asarray(x)) >= np.asarray(th)[:, None]
    assert keep[0].sum() == 2 and keep[1].sum() == 2
    with pytest.raises(ValueError):
        topk_threshold(x, 0)
    with pytest.raises(ValueError):
        topk_threshold(x, 5)


def _hard_payload(shape, seed=0):
    """Normal rows plus, in the first five rows: all zeros, ±0 beside
    normals, subnormals only, a magnitude tie across two thirds of the row,
    and ±inf."""
    x = np.array(jax.random.normal(jax.random.key(seed), shape, jnp.float32))
    rows = x.reshape(-1, shape[-1])
    n = shape[-1]
    rows[0] = 0.0
    rows[1, : n // 2] = np.where(np.arange(n // 2) % 2, 0.0, -0.0)
    rows[2] *= np.float32(1e-40)
    rows[3, : n // 3] = 0.5
    rows[3, n // 3: 2 * n // 3] = -0.5
    rows[4, :7] = np.inf
    rows[4, 7:20] = -np.inf
    return jnp.asarray(x)


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


# m = 75 and 700 overhang the kernel's row blocks (72 rows at n = 9,347, 624
# at n = 1,024); (2, 5, n) is a sweep input.
@pytest.mark.parametrize("k", [1, 512, "n"])
@pytest.mark.parametrize("shape", [(75, 9347), (700, 1024), (2, 5, 9347)],
                         ids=lambda s: "x".join(map(str, s)))
def test_topk_threshold_kernel_is_bitwise_jnp(shape, k):
    x = _hard_payload(shape, seed=len(shape))
    k = shape[-1] if k == "n" else k
    want = dispatch.topk_threshold(x, k, backend="jnp")
    got = dispatch.topk_threshold(x, k, backend="interpret")
    assert got.shape == want.shape == shape[:-1]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if k == 1:
        assert float(got.reshape(-1)[4]) == np.inf
    assert float(got.reshape(-1)[0]) == 0.0


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_topk_threshold_k_out_of_range(backend):
    x = _x(3, 16)
    for k in (0, 17):
        with pytest.raises(ValueError, match="1 <= k <= 16"):
            dispatch.topk_threshold(x, k, backend=backend)


def test_topk_encode_and_reduce_kernel_thresholds_are_bitwise_jnp():
    """The threshold is the only thing the backend changes in ``encode``;
    in ``reduce_mean`` the payload is eighths up to 8 in magnitude, so every
    order of the server sum is exact and any selection difference shows."""
    tr = topk(512)
    x = _hard_payload((75, 9347), seed=9)
    s_j, r_j = tr.encode(x, backend="jnp")
    s_i, r_i = tr.encode(x, backend="interpret")
    np.testing.assert_array_equal(_bits(s_i), _bits(s_j))
    np.testing.assert_array_equal(_bits(r_i), _bits(r_j))
    eighths = jnp.round(jnp.clip(x, -8.0, 8.0) * 8.0) / 8.0
    mean_j, res_j = tr.reduce_mean(eighths, backend="jnp")
    mean_i, res_i = tr.reduce_mean(eighths, backend="interpret")
    np.testing.assert_array_equal(_bits(mean_i), _bits(mean_j))
    np.testing.assert_array_equal(_bits(res_i), _bits(res_j))


def test_int8_roundtrip_error_is_half_ulp_of_the_row_scale():
    x = _x(5, 64, seed=1) * jnp.asarray([1e-3, 1.0, 1e3, 1e-6, 42.0])[:, None]
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    bound = np.asarray(scale)[:, None] * (0.5 + 1e-6)
    assert np.all(err <= bound)


def test_int8_all_zero_row_is_safe():
    q, scale = quantize_int8(jnp.zeros((2, 8)))
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(scale) == 0.0)
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, scale)), 0.0)


# --- encode / reduce_mean ------------------------------------------------------

@pytest.mark.parametrize("tr", ALL_TRANSFORMS, ids=lambda t: t.label)
def test_encode_conservation_is_exact(tr):
    """sent + residual == x bitwise in fp32 — the EF-SGD invariant."""
    x = _x()
    sent, residual = tr.encode(x)
    np.testing.assert_array_equal(np.asarray(sent + residual), np.asarray(x))
    if tr.enabled:
        assert float(jnp.sum(residual != 0)) > 0  # actually lossy
    else:
        np.testing.assert_array_equal(np.asarray(residual), 0.0)


@pytest.mark.parametrize("tr", ALL_TRANSFORMS, ids=lambda t: t.label)
def test_reduce_mean_matches_encode_reference(tr):
    """The fused server reduction == mean over agents of the encoded rows."""
    x = _x(seed=2)
    mean, residual = tr.reduce_mean(x, backend="jnp")
    sent_ref, resid_ref = tr.encode(x)
    np.testing.assert_allclose(
        np.asarray(mean), np.asarray(sent_ref.mean(axis=0)), rtol=1e-6,
        atol=1e-7,
    )
    np.testing.assert_array_equal(np.asarray(residual), np.asarray(resid_ref))


def test_topk_scatter_jnp_interpret_parity():
    """Shared threshold selection rule: both backends pick identical entries
    (residual bitwise-equal), sums agree to fp32 reduction tolerance."""
    for m, n in ((7, 33), (3, 4096 + 17)):  # odd n exercises the tail block
        x = _x(m, n, seed=3)
        th = topk_threshold(x, max(1, n // 8))
        s_j, r_j = dispatch.topk_scatter(x, th, backend="jnp")
        s_i, r_i = dispatch.topk_scatter(x, th, backend="interpret")
        np.testing.assert_array_equal(np.asarray(r_j), np.asarray(r_i))
        np.testing.assert_allclose(
            np.asarray(s_j), np.asarray(s_i), rtol=1e-6, atol=1e-6
        )
        # residual is exactly the unselected remainder (sent + residual == x)
        kept = jnp.where(jnp.abs(x) >= th[:, None], x, 0.0)
        np.testing.assert_array_equal(np.asarray(r_j), np.asarray(x - kept))


def test_topk_scatter_sweep_axis_self_vmaps():
    S, m, n = 3, 5, 40
    x = jax.random.normal(jax.random.key(4), (S, m, n), jnp.float32)
    th = topk_threshold(x, 6)
    assert th.shape == (S, m)
    ssum, resid = dispatch.topk_scatter(x, th, backend="jnp")
    assert ssum.shape == (S, n) and resid.shape == (S, m, n)
    for s in range(S):
        ref_sum, ref_res = dispatch.topk_scatter(x[s], th[s], backend="jnp")
        np.testing.assert_array_equal(np.asarray(resid[s]), np.asarray(ref_res))
        np.testing.assert_allclose(
            np.asarray(ssum[s]), np.asarray(ref_sum), rtol=1e-6
        )


def test_topk_scatter_shape_validation():
    x = _x(4, 8)
    with pytest.raises(ValueError, match="thresh"):
        dispatch.topk_scatter(x, jnp.zeros(3), backend="jnp")
    with pytest.raises(ValueError, match="x must be"):
        dispatch.topk_scatter(jnp.zeros(8), jnp.zeros(1), backend="jnp")


# --- strategy seam: comm state + flat_sync -------------------------------------

def test_init_comm_state_structure():
    flat = _x(7, 20)
    base = make_strategy("periodic", tau=3, m=7)
    assert base.init_comm_state(flat) == {}
    ef = base.with_comm(topk(4))
    assert set(ef.init_comm_state(flat)) == {"ref", "err_up"}
    no_ef = base.with_comm(topk(4, error_feedback=False))
    assert set(no_ef.init_comm_state(flat)) == {"ref"}
    topo = T.random_regularish(7, 3, 4, seed=0)
    cons = make_strategy("consensus", tau=3, topo=topo, eps=0.1, m=7,
                         comm=qint8())
    assert set(cons.init_comm_state(flat)) == {"ref", "err_up", "err_gossip"}
    state = ef.init_comm_state(flat)
    np.testing.assert_array_equal(np.asarray(state["ref"]), np.asarray(flat[0]))
    np.testing.assert_array_equal(np.asarray(state["err_up"]), 0.0)


def test_flat_sync_identity_is_bitwise_legacy():
    strat = make_strategy("periodic", tau=3, m=7, backend="jnp")
    flat = _x(7, 31, seed=5)
    synced, state = strat.flat_sync(flat, {})
    assert state == {}
    row = dispatch.row_mean(flat, backend="jnp")
    np.testing.assert_array_equal(
        np.asarray(synced), np.asarray(jnp.broadcast_to(row[None], flat.shape))
    )


def test_flat_sync_compressed_advances_ref_and_banks_residual():
    """One compressed sync == encode the per-agent deltas (+ prior EF),
    move the shared reference by the mean reconstruction, bank the rest."""
    tr = topk(6)
    strat = make_strategy("periodic", tau=3, m=7, backend="jnp", comm=tr)
    row0 = jax.random.normal(jax.random.key(6), (29,), jnp.float32)
    flat = jnp.broadcast_to(row0[None], (7, 29)) + 0.1 * _x(7, 29, seed=7)
    err0 = 0.01 * _x(7, 29, seed=8)
    state = {"ref": row0, "err_up": err0}
    synced, new_state = strat.flat_sync(flat, state)

    delta = flat - row0[None, :] + err0
    sent, resid = tr.encode(delta)
    row_ref = row0 + sent.mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(new_state["ref"]), np.asarray(row_ref), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_array_equal(
        np.asarray(new_state["err_up"]), np.asarray(resid)
    )
    np.testing.assert_array_equal(
        np.asarray(synced),
        np.asarray(jnp.broadcast_to(new_state["ref"][None], flat.shape)),
    )


# --- drivers -------------------------------------------------------------------

def _cfg(comm=None, strategy=None, **kw):
    strat = strategy or make_strategy(
        "decay", tau=3, m=7, decay=exponential_decay(0.95), backend="jnp"
    )
    if comm is not None:
        strat = strat.with_comm(comm)
    # 3 updates/epoch with tau=3: the period sync fires once per epoch, so
    # the compressed uplink actually runs (2 updates would never sync).
    kw.setdefault("n_epochs", 2)
    kw.setdefault("epoch_len", 12)
    kw.setdefault("minibatch", 4)
    kw.setdefault("eta", 3e-3)
    return FedRLConfig(env=FIGURE_EIGHT, strategy=strat, **kw)


def _metrics(cfg, seed=0):
    return jax.device_get(
        jax.jit(lambda k: run_fedrl_core(cfg, k)[1])(jax.random.key(seed))
    )


def test_fedrl_flat_identity_comm_is_bitwise_legacy():
    """IDENTITY comm through the flat carry reproduces the tree-space
    reference exactly — the comm-state threading is a no-op when dense."""
    tree = _metrics(_cfg())                              # legacy tree path
    flat = _metrics(_cfg(buffer_dtype="float32"))        # flat carry, dense
    for k, arr in tree.items():
        np.testing.assert_array_equal(flat[k], np.asarray(arr), err_msg=k)


@pytest.mark.parametrize("tr", (topk(64), qint8()), ids=lambda t: t.label)
def test_fedrl_compressed_runs_and_is_a_real_knob(tr):
    dense = _metrics(_cfg())
    comp = _metrics(_cfg(comm=tr))
    assert np.all(np.isfinite(comp["server_grad_sq_norm"]))
    assert np.all(np.isfinite(comp["nas"]))
    assert float(np.max(np.abs(comp["nas"] - dense["nas"]))) > 0


def test_error_feedback_changes_the_trajectory():
    """The first sync's residual is zero, so EF first bites at the second
    sync — visible in the epoch-end server grad norm."""
    with_ef = _metrics(_cfg(comm=topk(64)))
    without = _metrics(_cfg(comm=topk(64, error_feedback=False)))
    diff = np.abs(with_ef["server_grad_sq_norm"]
                  - without["server_grad_sq_norm"])
    assert float(np.max(diff)) > 0


def test_consensus_compressed_gossip_runs():
    topo = T.random_regularish(7, 3, 4, seed=0)

    def run(comm):
        strat = make_strategy("consensus", tau=3, topo=topo, eps=0.1, m=7,
                              backend="jnp")
        return _metrics(_cfg(comm=comm, strategy=strat))

    dense, comp = run(None), run(qint8())
    assert np.all(np.isfinite(comp["nas"]))
    assert float(np.max(np.abs(comp["nas"] - dense["nas"]))) > 0


# --- ledger bytes --------------------------------------------------------------

def test_dense_ledger_bytes_are_events_times_4n():
    """The pinned dense contract, including a partial trailing period:
    c1_bytes == c1_events * payload_elems * 4 exactly."""
    n = policy_payload_elems()
    # 2 updates/epoch * 3 epochs = 6 updates; tau=4 -> 1 full + 2 partial
    cfg = _cfg(strategy=make_strategy("periodic", tau=4, m=7),
               n_epochs=3, epoch_len=8, minibatch=4)
    ledger = fedrl_ledger(cfg)
    assert ledger.c1_events == 7 * 2           # full-period + partial read
    assert ledger.c1_bytes == ledger.c1_events * n * 4
    assert ledger.w1_bytes == 0
    assert ledger.total_bytes() == ledger.c1_bytes
    row = ledger.table_row()
    assert row["uplink_bytes_C1"] == ledger.c1_bytes
    assert row["total_bytes"] == ledger.total_bytes()


@pytest.mark.parametrize(
    "tr,per_event",
    [
        (topk(50), 8 * 50),
        (qint8(), policy_payload_elems() + 4),
        (qbf16(), 2 * policy_payload_elems()),
    ],
    ids=lambda v: v.label if isinstance(v, PayloadTransform) else str(v),
)
def test_compressed_ledger_bytes_are_exact(tr, per_event):
    cfg = _cfg(comm=tr)
    ledger = fedrl_ledger(cfg)
    assert ledger.c1_bytes == ledger.c1_events * per_event
    dense = fedrl_ledger(_cfg())
    assert dense.c1_events == ledger.c1_events  # same event count, fewer bytes
    assert ledger.total_bytes() < dense.total_bytes()


def test_consensus_ledger_bills_gossip_bytes():
    topo = T.random_regularish(7, 3, 4, seed=0)
    strat = make_strategy("consensus", tau=3, topo=topo, eps=0.1, m=7,
                          comm=topk(50))
    cfg = _cfg(strategy=strat)
    ledger = fedrl_ledger(cfg)
    assert ledger.w1_events > 0
    assert ledger.w1_bytes == ledger.w1_events * 8 * 50
    assert ledger.total_bytes() == ledger.c1_bytes + ledger.w1_bytes


def test_bytes_curve_is_cumulative_and_matches_the_ledger():
    cfg = _cfg(comm=topk(50), n_epochs=4)
    curve = fedrl_bytes_curve(cfg)
    assert curve.shape == (4,)
    assert np.all(np.diff(curve) >= 0) and curve[0] > 0
    assert float(curve[-1]) == float(fedrl_ledger(cfg).total_bytes())


# --- sweep integration ---------------------------------------------------------

def test_compression_axis_labels_and_per_point_results():
    from repro.sweep import SweepSpec, compression_axis, run_sweep

    transforms = (identity(), topk(50), qint8())
    spec = SweepSpec(
        name="comm", base=_cfg(), seeds=(0,),
        static=(compression_axis(transforms),),
    )
    res = run_sweep(spec)
    assert set(res.metrics) == {"dense", "topk50", "int8"}
    nas = {lbl: np.asarray(m["nas"]) for lbl, m in res.metrics.items()}
    assert all(np.all(np.isfinite(v)) for v in nas.values())
    assert float(np.max(np.abs(nas["dense"] - nas["topk50"]))) > 0


def test_compression_axis_validates_points():
    from repro.sweep import compression_axis

    with pytest.raises(TypeError, match="PayloadTransform"):
        compression_axis((("bad", "topk"),))
    axis = compression_axis((("sparse", topk(3)),))
    assert axis.points[0][0] == "sparse"


def test_compression_sweep_compiles_once_per_point(assert_max_compiles):
    """The compression axis is static by design (kind/k change the trace):
    the runner compiles exactly once per transform, never inside a point."""
    from repro.sweep import SweepSpec, compression_axis, run_sweep
    from repro.sweep.runner import static_points

    spec = SweepSpec(
        name="comm-retrace", base=_cfg(n_epochs=1), seeds=(0, 1),
        static=(compression_axis((identity(), topk(50))),),
    )
    run_sweep(spec)  # warm the caches outside the counted window
    n_points = len(list(static_points(spec)))
    _, n = assert_max_compiles(n_points, run_sweep, spec)
    assert n == n_points


def test_transform_swap_keeps_training_statics():
    """with_comm is a pure comm swap: masks, taus and backend untouched."""
    strat = make_strategy("decay", tau=5, m=7,
                          taus=uniform_taus(1, 5, 7, seed=0),
                          decay=exponential_decay(0.9))
    swapped = strat.with_comm(qint8())
    assert swapped.comm == qint8() and strat.comm is IDENTITY
    np.testing.assert_array_equal(swapped.mask, strat.mask)
    np.testing.assert_array_equal(swapped.taus, strat.taus)
    assert swapped.tau == strat.tau and swapped.backend == strat.backend

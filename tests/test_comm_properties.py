"""Property-based tests (hypothesis) on the comm-layer invariants.

Pinned properties:

* int8 quantization error is bounded by half an ulp of the per-row scale;
* top-k encode conservation is *bitwise* — ``sent + residual == x`` exactly
  in fp32 for arbitrary payloads (the EF-SGD algebra depends on it);
* dense ledger bytes are exact arithmetic: ``events * payload_elems * 4``
  for any (tau, schedule, update-count) combination, partial periods
  included.
"""
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.comm import dequantize_int8, qint8, quantize_int8, topk
from repro.core import make_strategy, uniform_taus
from repro.core.accounting import CostLedger

SETTINGS = settings(max_examples=40, deadline=None)

# fp32 payload matrices: finite, wide magnitude range, no -0.0 (negative
# zero survives top-k selection asymmetrically at the bit level, which is
# irrelevant to the arithmetic conservation under test). Hypothesis needs
# bounds that float32 represents exactly: the float32 values of 1e-20, 1e20.
_signed_f32 = st.builds(
    lambda mag, sign: np.float32(mag) * np.float32(sign),
    st.floats(min_value=9.999999682655225e-21,
              max_value=1.0000000200408773e+20, allow_nan=False,
              allow_infinity=False, width=32),
    st.sampled_from([1.0, -1.0, 0.0]),
)
_payloads = hnp.arrays(
    np.float32,
    st.tuples(st.integers(1, 6), st.integers(1, 40)),
    elements=_signed_f32,
)


@SETTINGS
@given(x=_payloads)
def test_int8_error_bounded_by_half_ulp_of_the_row_scale(x):
    q, scale = quantize_int8(jnp.asarray(x))
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - x)
    # half an ulp of the row scale, with fp32 slack on the division/round
    bound = np.asarray(scale)[:, None] * (0.5 + 1e-5) + 1e-30
    assert np.all(err <= bound), (err.max(), np.asarray(scale))


@SETTINGS
@given(x=_payloads, k=st.integers(1, 40))
def test_topk_encode_conservation_is_bitwise(x, k):
    k = min(k, x.shape[1])
    sent, residual = topk(k).encode(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(sent) + np.asarray(residual), x)
    # selection is a partition: every entry lands wholly on one side
    assert np.all((np.asarray(sent) == 0) | (np.asarray(residual) == 0))


@SETTINGS
@given(x=_payloads)
def test_int8_encode_conservation_is_exact_in_fp32(x):
    sent, residual = qint8().encode(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(sent) + np.asarray(residual), x)


@SETTINGS
@given(tau=st.integers(1, 10), m=st.integers(1, 10),
       n_updates=st.integers(0, 50), n=st.integers(1, 10_000),
       seed=st.integers(0, 99))
def test_dense_ledger_bytes_are_events_times_4n(tau, m, n_updates, n, seed):
    strat = make_strategy("periodic", tau=tau,
                          taus=uniform_taus(1, tau, m, seed=seed))
    full, rem = divmod(n_updates, tau)
    ledger = CostLedger()
    ledger.add_periods(strat, full, payload_elems=n)
    ledger.add_partial_period(strat, rem, payload_elems=n)
    assert ledger.c1_bytes == ledger.c1_events * n * 4
    assert ledger.w1_bytes == ledger.w1_events * n * 4 == 0
    assert ledger.total_bytes() == ledger.c1_bytes

"""Pallas TPU kernel: fused top-k select + scatter-accumulate server reduction.

The compressed uplink sync receives each agent's top-k-sparsified payload row
and accumulates it into the server sum. Done naively that is three passes
(materialise the dense ``sent`` matrix, reduce it, subtract for the error
residual); this kernel fuses them into one bandwidth-bound sweep over the
``(m, n)`` payload: per n-block it selects by the precomputed per-agent
magnitude threshold (``dispatch.topk_threshold``: the radix-select kernel of
``repro.kernels.topk_threshold`` on the kernel backends, ``lax.top_k`` on
jnp; ``|x| >= tau_i`` — ties included, matching the jnp
``segment_sum`` reference in ``repro.kernels.dispatch.topk_scatter``),
accumulates the selected values over the agent axis in fp32, and writes the
per-agent residual ``x - sent`` for the error-feedback carry.

The grid walks ``(block_m, block_n)`` tiles with the agent axis innermost
(``repro.kernels.tiling``): each tile writes its residual and adds its
selected column sums into an fp32 scratch row, flushed to the ``(n,)``
output after the last agent block. The thresholds ride as an ``(m, 1)`` fp32
column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling


def _topk_scatter_kernel(t_ref, x_ref, osum_ref, ores_ref, acc_ref, *, m):
    # fp32 select + accumulate regardless of the buffer dtype; only the
    # outputs are cast back, matching the jnp reference.
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)               # (block_m, block_n)
    sent = jnp.where(jnp.abs(x) >= t_ref[...], x, 0.0)  # threshold top-k select
    ores_ref[...] = (x - sent).astype(ores_ref.dtype)
    if m % x.shape[0]:
        sent = tiling.valid_rows(sent, i, m)
    part = jnp.sum(sent, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(i > 0)
    def _accum():
        acc_ref[...] += part

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        osum_ref[...] = acc_ref[...].astype(osum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def topk_scatter_pallas(x, thresh, *, block_n=None, interpret: bool = False):
    """x: (m, n) payloads; thresh: (m,) per-agent magnitude thresholds.

    Returns ``(sent_sum, residual)``: the ``(n,)`` fp32-accumulated sum of
    the selected entries over the agent axis (cast to ``x.dtype``) and the
    ``(m, n)`` unselected remainder.
    """
    if x.ndim != 2:
        raise ValueError(f"topk_scatter_pallas: x must be (m, n), got {x.shape}")
    m, n = x.shape
    if thresh.shape != (m,):
        raise ValueError(
            f"topk_scatter_pallas: thresh must be ({m},) for x {x.shape}, "
            f"got {thresh.shape}"
        )
    if n == 0:
        return jnp.zeros((0,), x.dtype), x
    bn = tiling.lane_block(n, block_n)
    bm = tiling.row_block(
        m, 2 * (2 * bn + tiling.LANES) * tiling.F32_BYTES, x.dtype
    )
    t_col = jnp.asarray(thresh, jnp.float32).reshape(m, 1)
    ssum, residual = pl.pallas_call(
        functools.partial(_topk_scatter_kernel, m=m),
        grid=(pl.cdiv(n, bn), pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), x.dtype),
            jax.ShapeDtypeStruct((m, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=interpret,
    )(t_col, x)
    return ssum[0], residual

"""Pallas TPU kernel: exact per-row top-k magnitude threshold by radix select.

The top-k payload encode keeps every entry with ``|x| >= t`` where ``t`` is
the k-th largest ``|x|`` of its row (``repro.comm.topk_threshold``). Only
that one number per row is needed, but ``lax.top_k`` lowers on the TPU to a
full sort of every row. This kernel finds it without sorting.

For non-negative floats the IEEE-754 bit pattern read as an int32 is
monotone in the value, so the k-th largest ``|x|`` is the largest int32
``t`` with ``count(bits(|x|) >= t) >= k``. The kernel builds that ``t`` one
bit at a time, from bit 30 down to bit 0: each pass tries ``cand = prefix |
(1 << b)``, counts the row's entries with ``bits >= cand`` and keeps
``cand`` where the count reaches k. After 31 compare-and-count passes
``prefix`` is the bit pattern of an element of the row, so the threshold is
bitwise equal to ``lax.top_k(|x|, k)[0][..., -1]`` (ties, zeros, ``-0``,
subnormals and infinities included; a NaN pattern sorts above ``+inf``).

The grid walks row blocks only; each ``(block_m, n)`` block holds whole
rows, so a row's passes never leave VMEM. The block is first written once,
as ``bits(|x|)``, into an int32 scratch of ``ceil(n / 128)`` lane chunks of
``(block_m, 128)``; the lanes of the last chunk past n are zero, and a
candidate is at least 1, so they never count. A pass adds its compare
results chunk by chunk into a ``(block_m, 128)`` accumulator with VPU adds
and makes one cross-lane reduce per row. Rows past m in the last block are
zeroed first; their results fall off the end of the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

ABS_MASK = 0x7FFFFFFF  # clears the sign bit: bits(|x|), with -0 mapped to 0
PASSES = 31            # bits 30..0; bit 31 of a magnitude is always 0


def _topk_threshold_kernel(x_ref, o_ref, bits_ref, *, k, m):
    bm, n = x_ref.shape
    lanes = tiling.LANES
    full, tail = divmod(n, lanes)

    def abs_bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32) & ABS_MASK

    def load(lo, hi):
        x = x_ref[:, lo:hi]
        if m % bm:
            x = tiling.valid_rows(x, pl.program_id(0), m)
        return abs_bits(x)

    for j in range(full):
        bits_ref[j] = load(j * lanes, (j + 1) * lanes)
    if tail:
        bits_ref[full] = jnp.zeros((bm, lanes), jnp.int32)
        bits_ref[full, :, :tail] = load(full * lanes, n)

    def one_pass(p, prefix):
        cand = prefix | jax.lax.shift_left(jnp.int32(1), PASSES - 1 - p)
        cand_lanes = jnp.broadcast_to(cand, (bm, lanes))

        def count_chunk(j, acc):
            return acc + jnp.where(bits_ref[j] >= cand_lanes, 1, 0)

        acc = jax.lax.fori_loop(
            0, bits_ref.shape[0], count_chunk, jnp.zeros((bm, lanes), jnp.int32)
        )
        count = jnp.sum(acc, axis=1, keepdims=True)
        return jnp.where(count >= k, cand, prefix)

    prefix = jax.lax.fori_loop(
        0, PASSES, one_pass, jnp.zeros((bm, 1), jnp.int32)
    )
    o_ref[...] = jax.lax.bitcast_convert_type(prefix, jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_threshold_pallas(x, k: int, *, interpret: bool = False):
    """x: (m, n) fp32 rows, 1 <= k <= n. Returns the (m,) fp32 k-th largest
    ``|x|`` per row."""
    if x.ndim != 2 or x.dtype != jnp.float32:
        raise ValueError(
            f"topk_threshold_pallas: x must be (m, n) float32, got "
            f"{x.shape} {x.dtype}"
        )
    m, n = x.shape
    chunks = pl.cdiv(n, tiling.LANES)
    padded = chunks * tiling.LANES
    # per row: the input block double-buffered, the int32 scratch, and the
    # (block_m, 1) output column double-buffered (a lane tile each)
    row_bytes = (2 * padded + padded + 2 * tiling.LANES) * tiling.F32_BYTES
    bm = tiling.row_block(m, row_bytes, x.dtype)
    out = pl.pallas_call(
        functools.partial(_topk_threshold_kernel, k=k, m=m),
        grid=(pl.cdiv(m, bm),),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chunks, bm, tiling.LANES), jnp.int32)],
        interpret=interpret,
    )(x)
    return out[:, 0]

"""Payload transforms: what actually crosses the federated links, in bytes.

A :class:`PayloadTransform` is a frozen hashable spec (like
``repro.optim.flat.FlatOptimizer``) describing the lossy encoding applied to
a flat ``(m, n)`` payload matrix before it is communicated — uplink deltas at
the period sync, gossip payloads on the consensus path. Four kinds:

* ``identity`` — dense fp32; 4n bytes per event. The default; strategies
  with this transform keep their exact pre-comm-layer behaviour.
* ``topk``     — per-agent top-k magnitude sparsification. The selection rule
  is *threshold* form: keep every entry with ``|x| >= kth largest |x|`` of
  its row (magnitude ties at the threshold are all kept, so the jnp
  ``segment_sum`` reference and the fused Pallas kernel agree exactly).
  The threshold is ``lax.top_k`` of ``|x|`` on the jnp backend and an exact
  radix select (``repro.kernels.topk_threshold``, no sort) on the kernel
  backends; both give the same bits.
  Wire size: k (value, index) pairs = 8k bytes per event.
* ``int8``     — symmetric per-row quantization, ``s = max|x| / 127``,
  ``q = round(x/s)`` in [-127, 127]; n + 4 bytes per event (payload + fp32
  row scale). The dequantized error is bounded by s/2 — half an ulp of the
  row scale.
* ``bf16``     — round-trip through bfloat16; 2n bytes per event.

Error feedback (EF-SGD style): ``encode`` returns ``(sent, residual)`` with
``sent + residual == x`` exactly in fp32 arithmetic — for top-k the kept
entries pass through bitwise and the dropped entries land in the residual
whole. The caller folds the previous residual into the next payload
(``encode(x + err)``) and stores the new one; the strategies keep those
``(m, n)`` fp32 accumulators in the drivers' flat scan carry next to the
optimizer moments (the PR-2 fp32-moments pattern).

``reduce_mean`` is the compressed server reduction: the mean over the agent
axis of the encoded payloads, accumulated in fp32 on every backend. The
top-k path takes its threshold from ``dispatch.topk_threshold`` and routes
through ``dispatch.topk_scatter`` — the fused
select + scatter-accumulate kernel — so the dense ``sent`` matrix is never
materialised on kernel backends.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.utils.scopes import PAYLOAD_ENCODE, TOPK_SELECT

KINDS = ("identity", "topk", "int8", "bf16")


def topk_threshold(x, k: int, backend: str = "jnp"):
    """Per-row top-k magnitude threshold: the k-th largest ``|x|`` per row.

    ``x``: ``(..., n)``. Returns the ``(...,)`` thresholds; an entry is kept
    iff ``|x| >= threshold`` (ties included — the one selection rule shared
    by the jnp reference and the Pallas kernel, so parity is exact).
    ``dispatch.topk_threshold`` computes it: ``lax.top_k`` of ``|x|`` on
    ``jnp`` (a full sort of each row on the TPU), the radix-select kernel on
    ``pallas``/``interpret``, bitwise equal. The ops run under the
    ``fed_topk_select`` scope.
    """
    with jax.named_scope(TOPK_SELECT):
        return dispatch.topk_threshold(x, k, backend=backend)


def quantize_int8(x):
    """Symmetric per-row int8 quantization: ``(q, scale)``.

    ``scale = max|x| / 127`` per row; ``q = round(x / scale)`` clipped to
    [-127, 127] (all-zero rows quantize through a safe unit scale to q=0).
    """
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[..., None]), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    """fp32 reconstruction of a per-row-quantized payload."""
    return q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)[..., None]


@dataclasses.dataclass(frozen=True)
class PayloadTransform:
    """Frozen spec of one link compression scheme (hashable, jit-closable).

    ``k`` is static (it fixes the top-k wire size and the kernel trace);
    sweeping it is a *static* axis (``repro.sweep.overrides.compression_axis``).
    ``error_feedback`` adds the per-agent fp32 residual accumulators to the
    strategy's comm state.
    """

    kind: str = "identity"
    k: int = 0
    error_feedback: bool = True

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown payload transform kind {self.kind!r}; expected one "
                f"of {KINDS}"
            )
        if self.kind == "topk":
            if self.k < 1:
                raise ValueError(f"topk transform needs k >= 1, got {self.k}")
        elif self.k:
            raise ValueError(f"k only applies to the topk kind, got k={self.k}")

    @property
    def enabled(self) -> bool:
        """True when the transform actually changes the payload."""
        return self.kind != "identity"

    @property
    def label(self) -> str:
        if self.kind == "identity":
            return "dense"
        if self.kind == "topk":
            return f"topk{self.k}"
        return self.kind

    # --- bytes accounting ------------------------------------------------------
    def payload_bytes(self, n: int) -> int:
        """Wire bytes of ONE encoded n-element payload (one comm event).

        identity: 4n (dense fp32); topk: 8k nominal ((fp32 value, int32
        index) per kept element — threshold ties may send a few extra, the
        accounting uses the nominal k); int8: n + 4 (int8 payload + fp32 row
        scale); bf16: 2n.
        """
        n = int(n)
        if n < 0:
            raise ValueError(f"payload_bytes: n must be >= 0, got {n}")
        if self.kind == "identity":
            return 4 * n
        if self.kind == "topk":
            return 8 * min(self.k, n)
        if self.kind == "int8":
            return n + 4
        return 2 * n

    # --- encoding --------------------------------------------------------------
    def encode(self, x, *, backend: str = "auto"):
        """Encode/decode round-trip of a payload matrix: ``(sent, residual)``.

        ``x``: ``(m, n)`` (or ``(S, m, n)``) fp32 payloads — callers fold the
        previous error-feedback residual in *before* encoding. ``sent`` is
        the receiver-visible fp32 reconstruction, ``residual = x - sent``
        (exact in fp32: kept/dequantized values subtract out bitwise for
        top-k). The ``backend`` picks the top-k threshold's path
        (:func:`topk_threshold`); the keep mask and the dense encodes are
        elementwise jnp on every backend.
        """
        with jax.named_scope(PAYLOAD_ENCODE):
            x = jnp.asarray(x, jnp.float32)
            if self.kind == "identity":
                return x, jnp.zeros_like(x)
            if self.kind == "topk":
                thresh = topk_threshold(x, self.k, backend)
                keep = jnp.abs(x) >= thresh[..., None]
                sent = jnp.where(keep, x, 0.0)
            elif self.kind == "int8":
                sent = dequantize_int8(*quantize_int8(x))
            else:  # bf16
                sent = x.astype(jnp.bfloat16).astype(jnp.float32)
            return sent, x - sent

    def reduce_mean(self, x, *, backend: str = "auto"):
        """Compressed server reduction: ``(mean over agents, residual)``.

        The uplink sync primitive: each agent's row of ``x`` is encoded and
        the server averages the reconstructions, accumulating in fp32 on
        every backend. Top-k runs the fused ``dispatch.topk_scatter``
        select + scatter-accumulate (dense ``sent`` never materialises on
        kernel backends); int8/bf16 dequantize and ``row_mean``.
        """
        x = jnp.asarray(x, jnp.float32)
        m = x.shape[-2]
        if self.kind == "topk":
            with jax.named_scope(PAYLOAD_ENCODE):
                thresh = topk_threshold(x, self.k, backend)
            ssum, residual = dispatch.topk_scatter(x, thresh, backend=backend)
            return ssum / m, residual
        sent, residual = self.encode(x, backend=backend)
        return dispatch.row_mean(sent, backend=backend), residual


IDENTITY = PayloadTransform("identity", error_feedback=False)


def identity() -> PayloadTransform:
    """The dense fp32 no-op transform (byte accounting still applies)."""
    return IDENTITY


def topk(k: int, error_feedback: bool = True) -> PayloadTransform:
    """Top-k magnitude sparsification of each agent's payload row."""
    return PayloadTransform("topk", k=int(k), error_feedback=error_feedback)


def qint8(error_feedback: bool = True) -> PayloadTransform:
    """Symmetric per-row int8 quantization (n + 4 bytes per event)."""
    return PayloadTransform("int8", error_feedback=error_feedback)


def qbf16(error_feedback: bool = True) -> PayloadTransform:
    """bfloat16 round-trip (2n bytes per event)."""
    return PayloadTransform("bf16", error_feedback=error_feedback)


# --- trace-safety audit registration (repro.analysis.jaxpr_audit) -------------

def _reduce_hot_path(kind: str, backend: str):
    """Audit entry for the compressed server reduction on one backend.

    The contract under audit: the reduction over the agent axis accumulates
    in fp32 even when the wire format is int8/sparse — JXA001 would flag a
    sub-fp32 accumulation the moment one appeared in the lowered jaxpr.
    """

    def factory() -> dispatch.HotPathEntry:
        m, n = 4, 96
        tr = topk(8) if kind == "topk" else PayloadTransform(kind)
        return dispatch.HotPathEntry(
            fn=lambda x: tr.reduce_mean(x, backend=backend),
            args=(jax.ShapeDtypeStruct((m, n), jnp.float32),),
        )

    return factory


for _kind in ("topk", "int8"):
    for _backend in ("jnp", "interpret"):
        dispatch.register_hot_path(
            f"comm.{_kind}_reduce[{_backend}]",
            _reduce_hot_path(_kind, _backend),
        )

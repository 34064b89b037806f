"""Backend dispatch: route the federated hot-path transforms to the kernels.

The within-period gradient transforms (variation mask / decay weighting /
consensus gossip), the local optimizer update, and the server averaging step
are the per-step work of Algorithms 1 & 2. This module is the single switch
that decides how they execute:

  * ``jnp``       — pure-jnp reference path (tree ops / matmul). Always
                    available; the allclose target for everything else.
  * ``pallas``    — compiled Pallas TPU kernels (``decay_accum_pallas``,
                    ``consensus_step_pallas``, ``consensus_gather_pallas``,
                    ``row_mean_pallas``, ``momentum_update_pallas`` /
                    ``adam_update_pallas``): one fused bandwidth-bound pass
                    over flat parameter buffers.
  * ``interpret`` — the same Pallas kernels in interpret mode. Runs the
                    kernel bodies as traced jax on CPU; used for parity tests
                    and CPU debugging of the kernel path.
  * ``auto``      — ``pallas`` when the default backend is TPU, else ``jnp``.

Strategies carry a ``backend=`` field (default ``auto``) so every existing
call site keeps working; the drivers resolve it once at trace time.

The kernel path works on flat ``(m, n)`` matrices — m agents by n parameters.
Since PR 2 the *drivers* also keep their scan carry in that form (ravel once
at run start, unravel only where user code needs trees), so the per-step cost
on kernel backends is one ravel of the gradients the user closure returns —
no params round-trip. ``stacked_ravel_spec`` hands out the cached
flatten/unflatten closures (full-stack and per-agent views); the cache is a
bounded LRU keyed on (treedef, per-agent shapes, dtypes) and can be emptied
with ``clear_caches()``.

Numerics: every dispatched primitive accumulates in fp32 on every backend
(inputs are upcast, outputs cast back to the input dtype), so bf16/fp16
gradient buffers stay bit-comparable between the jnp reference and the
kernel path, and a later bf16-buffer mode slots in without parity drift.

Kernel block sizes are derived from each call's shapes and dtype
(``repro.kernels.tiling``) so that every kernel compiles for a v5e at any m;
``block_n`` overrides only the column block (tests use it to force
multi-block grids at toy sizes).

Weights are *operands*, never baked-in constants: the per-agent coefficient
``d``/``w`` of ``decay_accum``/``scale_rows``/``flat_opt_update`` and the
(mask-folded) ``mixing`` matrix of ``consensus_mix`` arrive as arguments on
every backend, so the traced variation masks of the sweep engine's ``taus``
axis (columns of an ``(S, m, tau)`` batched mask → ``(S, m)`` coefficients,
or folded per-run ``(S, m, m)`` mixing tables) batch through the same entry
points with no kernel changes (DESIGN.md §11).
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp

from repro.sharding.rules import logical_axis_size

# NOTE: the Pallas kernel modules are imported lazily inside the kernel
# branches below — the jnp reference path (and hence repro.core) must stay
# importable on JAX builds where jax.experimental.pallas fails to import.

BACKENDS = ("auto", "jnp", "pallas", "interpret")

OPT_KINDS = ("sgd", "momentum", "adam")


# --- hot-path entry-point registry --------------------------------------------
#
# The trace-safety analyzer (repro.analysis.jaxpr_audit) audits whatever is
# registered here: each entry is a lazily-built (fn, abstract args) pair that
# make_jaxpr can lower without running anything. Modules that own a hot path
# (this one for the dispatch primitives, rl.fedrl / core.fmarl for the
# drivers, sweep.runner for the per-static-point batched fn) register at
# import time; the registry lives here because every one of those modules
# already imports dispatch, so there is exactly one import direction.


class HotPathEntry(NamedTuple):
    """One auditable entry point: ``fn`` plus abstract example arguments.

    ``args`` are ``jax.ShapeDtypeStruct``s (or concrete arrays) shaped like a
    *small* but structurally faithful invocation — the audit only needs the
    jaxpr, so tiny shapes keep lowering fast while exercising every primitive
    the real sizes hit. ``donate_argnums`` declares buffers the entry point
    intends to donate under jit; the auditor verifies the lowering actually
    aliases them (rule JXA004).
    """

    fn: Callable
    args: Tuple
    donate_argnums: Tuple[int, ...] = ()


_HOT_PATH_FACTORIES: "collections.OrderedDict[str, Callable[[], HotPathEntry]]" = (
    collections.OrderedDict()
)


def register_hot_path(name: str, factory: Callable[[], HotPathEntry]) -> None:
    """Register ``factory`` (called lazily by the audit) under ``name``.

    Re-registration under the same name overwrites (module reloads in tests);
    names are namespaced by convention, e.g. ``dispatch.row_mean[jnp]`` or
    ``rl.run_fedrl_core``.
    """
    _HOT_PATH_FACTORIES[name] = factory


def hot_path_factories() -> Dict[str, Callable[[], HotPathEntry]]:
    """Snapshot of the registered entry-point factories (name -> factory)."""
    return dict(_HOT_PATH_FACTORIES)


def resolve_backend(backend: str = "auto") -> str:
    """Collapse ``auto`` to a concrete backend for the current platform.

    Raises for ``pallas`` while the agent axis is sharded over more than one
    device (``use_rules(fleet_rules(...))``): XLA cannot partition a Mosaic
    kernel, and running it would first gather the whole carry onto every
    device. Such runs pass ``backend="jnp"``; kernels under the mesh need a
    ``shard_map`` wrapper (ROADMAP S8).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "pallas":
        shards = logical_axis_size("agents")
        if shards > 1:
            raise ValueError(
                f"dispatch: the pallas backend cannot run with the agent axis "
                f"sharded over {shards} devices (Mosaic kernels are not "
                f"partitioned); pass backend='jnp' under the fleet rules"
            )
    return backend


def is_kernel_backend(backend: str) -> bool:
    return resolve_backend(backend) in ("pallas", "interpret")


# --- flat <-> pytree plumbing -------------------------------------------------

class FlatSpec(NamedTuple):
    """Cached flatten/unflatten closures for one replica-pytree structure.

    ``unravel`` maps the full ``(m, n)`` matrix back to the stacked tree;
    ``unravel_one`` maps a single ``(n,)`` row to a per-agent tree (the view
    rollout/grad closures receive on the flat-carry path); ``ravel_one`` is
    its inverse for the gradients those closures return.
    """

    unravel: Callable
    unravel_one: Callable
    ravel_one: Callable


# Bounded LRU: keyed on live treedef objects, so an unbounded dict would
# pin every tree structure ever raveled (and grow across tests / long
# sessions). 64 distinct (treedef, shapes, dtypes) structures is far beyond
# what one process legitimately cycles through.
_UNRAVEL_CACHE_MAXSIZE = 64
_UNRAVEL_CACHE: "collections.OrderedDict" = collections.OrderedDict()


def clear_caches() -> None:
    """Drop all cached unravel closures (tests; releases treedef refs)."""
    _UNRAVEL_CACHE.clear()


def _ravel_one(tree) -> jnp.ndarray:
    return jax.flatten_util.ravel_pytree(tree)[0]


def _flat_spec(leaves, treedef) -> FlatSpec:
    key = (treedef, tuple((l.shape[1:], jnp.dtype(l.dtype).name) for l in leaves))
    spec = _UNRAVEL_CACHE.get(key)
    if spec is None:
        template = jax.tree.unflatten(
            treedef, [jnp.zeros(l.shape[1:], l.dtype) for l in leaves]
        )
        _, unravel_one = jax.flatten_util.ravel_pytree(template)
        spec = FlatSpec(
            unravel=jax.vmap(unravel_one),
            unravel_one=unravel_one,
            ravel_one=_ravel_one,
        )
        _UNRAVEL_CACHE[key] = spec
        if len(_UNRAVEL_CACHE) > _UNRAVEL_CACHE_MAXSIZE:
            _UNRAVEL_CACHE.popitem(last=False)
    else:
        _UNRAVEL_CACHE.move_to_end(key)
    return spec


def stacked_ravel_spec(tree_m):
    """Flatten an (m, ...)-leaved replica pytree to ``(flat, FlatSpec)``.

    ``flat`` is the ``(m, n)`` matrix; the spec carries the cached unflatten
    closures (see :class:`FlatSpec`). The cache key is (treedef, per-agent
    leaf shapes, dtypes) in a bounded LRU.
    """
    leaves, treedef = jax.tree.flatten(tree_m)
    if not leaves:
        raise ValueError("stacked_ravel: empty pytree")
    m = leaves[0].shape[0]
    for l in leaves:
        if l.ndim < 1 or l.shape[0] != m:
            raise ValueError(
                f"stacked_ravel: every leaf needs leading agent axis {m}, "
                f"got shape {l.shape}"
            )
    spec = _flat_spec(leaves, treedef)
    flat = jax.vmap(_ravel_one)(tree_m)
    return flat, spec


def compute_view(buf, storage_dtype):
    """fp32 compute view of a flat carry buffer.

    The single policy point for the reduced-precision buffer mode: when a
    storage dtype is set (e.g. bf16 carries), user-facing tree views upcast
    to fp32 before unraveling; with the default fp32 storage it is the
    identity. Both drivers route every unravel through this.
    """
    return buf.astype(jnp.float32) if storage_dtype is not None else buf


def stacked_ravel(tree_m):
    """Flatten an (m, ...)-leaved replica pytree to an ``(m, n)`` matrix.

    Returns ``(flat, unravel)`` where ``unravel`` maps an ``(m, n)`` matrix
    back to the original tree structure. See ``stacked_ravel_spec`` for the
    full set of cached views.
    """
    flat, spec = stacked_ravel_spec(tree_m)
    return flat, spec.unravel


# --- dispatched primitives ----------------------------------------------------
#
# Sweep axis: every flat primitive also accepts a leading sweep axis S on its
# buffers — ``(S, m, n)`` instead of ``(m, n)`` — by vmapping itself over axis
# 0 (the per-axis coefficient / mixing arguments gain a matching leading axis
# or broadcast). This is the shape the sweep engine (repro.sweep) produces
# when it vmaps a whole federated run over seeds/hyperparameters: one trace
# covers all S runs, no per-run retraces.


def _per_row(w, params):
    """The kernels' weight layout: scalar for (n,) buffers, (m,) for (m, n)."""
    return jnp.broadcast_to(w, params.shape[:1]) if params.ndim == 2 else w


def decay_accum(acc, g, d, *, backend: str = "auto", block_n=None):
    """``acc + d * g`` — the fused FMA at the heart of the decay/SGD step.

    ``acc``/``g``: ``(n,)`` or ``(m, n)`` buffers, or ``(S, m, n)`` with a
    leading sweep axis; ``d``: scalar, or ``(m,)`` per-agent coefficients when
    the inputs are ``(m, n)`` (one kernel pass over the whole matrix, the
    coefficients as an fp32 column), or additionally ``(S,)`` / ``(S, m)``
    per-run coefficients on the sweep path.
    Accumulates in fp32 on every backend; the result is cast back to
    ``acc.dtype``.
    """
    b = resolve_backend(backend)
    if acc.ndim == 3:
        if acc.shape != g.shape:
            raise ValueError(
                f"decay_accum: acc/g must match on the sweep path, got "
                f"{acc.shape} vs {g.shape}"
            )
        d_arr = jnp.asarray(d, jnp.float32)
        S, m = acc.shape[0], acc.shape[1]
        if d_arr.ndim == 1 and S == m and d_arr.shape[0] == S:
            # A 1-D d could mean per-run (S,) or shared per-agent (m,) and
            # the two disagree numerically — refuse rather than guess.
            raise ValueError(
                f"decay_accum: 1-D d of length {S} is ambiguous on a sweep "
                f"path with S == m == {S}; pass (S, m) coefficients (tile "
                f"the shared/per-run vector) or a scalar"
            )
        if d_arr.ndim == 2 or (d_arr.ndim == 1 and d_arr.shape[0] == S):
            # per-run coefficients: (S,) or (S, m)
            return jax.vmap(
                lambda a, gi, di: decay_accum(a, gi, di, backend=b, block_n=block_n)
            )(acc, g, d_arr)
        return jax.vmap(
            lambda a, gi: decay_accum(a, gi, d_arr, backend=b, block_n=block_n)
        )(acc, g)
    if acc.ndim not in (1, 2) or acc.shape != g.shape:
        raise ValueError(
            f"decay_accum: acc/g must be matching (n,) or (m, n) buffers, "
            f"got {acc.shape} vs {g.shape}"
        )
    if acc.dtype != g.dtype:
        # Enforced on every backend so 'auto' behaves identically on CPU/TPU.
        raise ValueError(
            f"decay_accum: acc/g dtypes must match, got {acc.dtype} vs {g.dtype}"
        )
    d_arr = jnp.asarray(d, jnp.float32)
    if d_arr.ndim not in (0, 1) or (d_arr.ndim == 1 and acc.ndim != 2):
        raise ValueError(
            f"decay_accum: d must be scalar or (m,) with (m, n) inputs, "
            f"got d shape {d_arr.shape} for input shape {acc.shape}"
        )
    if b == "jnp":
        d_b = d_arr[:, None] if d_arr.ndim == 1 else d_arr
        out = acc.astype(jnp.float32) + d_b * g.astype(jnp.float32)
        return out.astype(acc.dtype)
    from repro.kernels.decay_accum import decay_accum_pallas

    return decay_accum_pallas(
        acc, g, _per_row(d_arr, acc), block_n=block_n,
        interpret=(b == "interpret"),
    )


def scale_rows(g, w, *, backend: str = "auto", block_n=None):
    """Row-scale ``(m, n)`` grads by per-agent weights ``w``: out[i] = w[i]*g[i].

    On the kernel path this is ``decay_accum(g, g, w - 1)`` = g + (w-1)*g —
    both operands alias the same buffer, so no zeros accumulator is ever
    materialised. The drivers avoid even this pass by fusing the weight into
    the SGD coefficient (see ``AggregationStrategy.flat_update``); this
    standalone form backs ``transform`` when called outside the fused update.
    """
    b = resolve_backend(backend)
    if g.ndim == 3:
        w_arr = jnp.asarray(w, jnp.float32)
        if w_arr.ndim == 2:  # (S, m) per-run weights
            return jax.vmap(
                lambda gi, wi: scale_rows(gi, wi, backend=b, block_n=block_n)
            )(g, w_arr)
        if w_arr.ndim == 1 and g.shape[0] == g.shape[1]:
            # S == m: a 1-D w could be read as per-run or per-agent under
            # the sweep conventions — refuse rather than guess (matches
            # decay_accum's guard).
            raise ValueError(
                f"scale_rows: 1-D w of length {g.shape[1]} is ambiguous on a "
                f"sweep path with S == m == {g.shape[0]}; pass (S, m) weights"
            )
        return jax.vmap(
            lambda gi: scale_rows(gi, w_arr, backend=b, block_n=block_n)
        )(g)
    if g.ndim != 2:
        raise ValueError(f"scale_rows: g must be (m, n), got {g.shape}")
    w_arr = jnp.asarray(w, jnp.float32)
    if w_arr.shape != (g.shape[0],):
        raise ValueError(
            f"scale_rows: w must be ({g.shape[0]},) for g {g.shape}, "
            f"got {w_arr.shape}"
        )
    if b == "jnp":
        return (g.astype(jnp.float32) * w_arr[:, None]).astype(g.dtype)
    return decay_accum(g, g, w_arr - 1.0, backend=b, block_n=block_n)


def consensus_mix(g, mixing, *, backend: str = "auto", block_n=None):
    """One (possibly fused-E, possibly mask-folded) gossip mix: ``mixing @ g``.

    Both backends accumulate the matmul in fp32 at HIGHEST precision (the
    MXU's default fp32 path truncates operands to bf16 passes, which would
    drift from the CPU reference) and cast back to ``g.dtype``.
    """
    b = resolve_backend(backend)
    if g.ndim == 3:
        mixing = jnp.asarray(mixing)
        if mixing.ndim == 3:  # (S, m, m) per-run mixing matrices
            return jax.vmap(
                lambda gi, mi: consensus_mix(gi, mi, backend=b, block_n=block_n)
            )(g, mixing)
        return jax.vmap(
            lambda gi: consensus_mix(gi, mixing, backend=b, block_n=block_n)
        )(g)
    if g.ndim != 2:
        raise ValueError(f"consensus_mix: g must be (m, n), got {g.shape}")
    m = g.shape[0]
    if mixing.shape != (m, m):
        raise ValueError(
            f"consensus_mix: mixing must be ({m}, {m}) for g {g.shape}, "
            f"got {mixing.shape}"
        )
    if b == "jnp":
        out = jnp.matmul(
            mixing.astype(jnp.float32),
            g.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return out.astype(g.dtype)
    from repro.kernels.consensus_step import consensus_step_pallas

    return consensus_step_pallas(
        g, mixing, block_n=block_n, interpret=(b == "interpret")
    )


def consensus_gather(g, idx, w, *, backend: str = "auto", block_n=None):
    """One sparse neighbor-list gossip round: ``out[i] = sum_k w[i,k]*g[idx[i,k]]``.

    The O(m*k) twin of :func:`consensus_mix` for sparse topologies. ``g``:
    ``(m, n)`` flat grads (or ``(S, m, n)`` with a leading sweep axis);
    ``idx``: static ``(m, k_max)`` integer neighbor ids in the
    ``repro.core.topology.NeighborList`` layout (ascending valid prefix, self
    included, padding = own row); ``w``: ``(m, k_max)`` edge weights with
    padding slots exactly 0.0 (or ``(S, m, k_max)`` per-run weights — the eps
    sweep axis rebuilds them traced via ``neighbor_weights``).

    Numerics contract: fp32 accumulation on every backend, result cast back
    to ``g.dtype``. The jnp path states the sum as a *sequential* FMA chain
    in ascending-k order — in eager mode this is bit-identical to evaluating
    the dense ``P @ g`` row sum in index order with zero weights on the
    non-edges (adding ``0.0 * row`` is exact), which is the dense/sparse
    bitwise-parity contract pinned by the tests; under jit, XLA fusion may
    re-associate within 1 ulp (same caveat as every dispatched primitive).
    """
    b = resolve_backend(backend)
    idx = jnp.asarray(idx)
    if idx.ndim != 2 or not jnp.issubdtype(idx.dtype, jnp.integer):
        raise ValueError(
            f"consensus_gather: idx must be an (m, k_max) integer array, got "
            f"shape {idx.shape} dtype {idx.dtype}"
        )
    if g.ndim == 3:
        w_arr = jnp.asarray(w, jnp.float32)
        if w_arr.ndim == 3:  # (S, m, k_max) per-run edge weights
            return jax.vmap(
                lambda gi, wi: consensus_gather(
                    gi, idx, wi, backend=b, block_n=block_n
                )
            )(g, w_arr)
        return jax.vmap(
            lambda gi: consensus_gather(gi, idx, w_arr, backend=b, block_n=block_n)
        )(g)
    if g.ndim != 2:
        raise ValueError(f"consensus_gather: g must be (m, n), got {g.shape}")
    m = g.shape[0]
    if idx.shape[0] != m:
        raise ValueError(
            f"consensus_gather: idx must be ({m}, k_max) for g {g.shape}, "
            f"got {idx.shape}"
        )
    w_arr = jnp.asarray(w, jnp.float32)
    if w_arr.shape != idx.shape:
        raise ValueError(
            f"consensus_gather: w must match idx {idx.shape}, got {w_arr.shape}"
        )
    if b == "jnp":
        g32 = g.astype(jnp.float32)
        k_max = idx.shape[1]
        out = w_arr[:, 0, None] * jnp.take(g32, idx[:, 0], axis=0)
        for k in range(1, k_max):
            out = out + w_arr[:, k, None] * jnp.take(g32, idx[:, k], axis=0)
        return out.astype(g.dtype)
    from repro.kernels.consensus_gather import consensus_gather_pallas

    return consensus_gather_pallas(
        g, idx, w_arr, block_n=block_n, interpret=(b == "interpret")
    )


def row_mean(g, *, backend: str = "auto", block_n=None):
    """Server averaging (eq. 11) on the flat carry: mean over the agent axis.

    ``g``: ``(m, n)``. Returns the ``(n,)`` server row (broadcast it back over
    the agent axis to re-seed the replicas). Accumulates in fp32 on every
    backend and casts back to ``g.dtype``.
    """
    b = resolve_backend(backend)
    if g.ndim == 3:
        return jax.vmap(lambda gi: row_mean(gi, backend=b, block_n=block_n))(g)
    if g.ndim != 2:
        raise ValueError(f"row_mean: g must be (m, n), got {g.shape}")
    if b == "jnp":
        return jnp.mean(g.astype(jnp.float32), axis=0).astype(g.dtype)
    from repro.kernels.flat_update import row_mean_pallas

    return row_mean_pallas(g, block_n=block_n, interpret=(b == "interpret"))


def topk_threshold(x, k: int, *, backend: str = "auto"):
    """Per-row top-k magnitude threshold: the k-th largest ``|x|`` per row.

    ``x``: ``(..., n)``, e.g. ``(m, n)`` payload rows or ``(S, m, n)`` with a
    leading sweep axis. Returns the ``(...,)`` thresholds. The jnp path is
    ``lax.top_k`` of ``|x|`` (on the TPU a full sort of every row); the
    kernel backends run the exact radix select of
    ``repro.kernels.topk_threshold`` over the rows viewed as one ``(rows,
    n)`` fp32 matrix. Both return the bit pattern of an element of the row,
    so the thresholds are bitwise equal.
    """
    n = x.shape[-1]
    if not 1 <= k <= n:
        raise ValueError(f"topk_threshold: need 1 <= k <= {n}, got k={k}")
    b = resolve_backend(backend)
    if b == "jnp":
        return jax.lax.top_k(jnp.abs(x), k)[0][..., -1]
    from repro.kernels.topk_threshold import topk_threshold_pallas

    rows = x.reshape(-1, n).astype(jnp.float32)
    thresh = topk_threshold_pallas(rows, k, interpret=(b == "interpret"))
    return thresh.reshape(x.shape[:-1]).astype(x.dtype)


def topk_scatter(x, thresh, *, backend: str = "auto", block_n=None):
    """Fused top-k select + scatter-accumulate: the compressed server reduction.

    ``x``: ``(m, n)`` payload rows (or ``(S, m, n)`` with a leading sweep
    axis); ``thresh``: ``(m,)`` (or ``(S, m)``) per-agent magnitude
    thresholds, normally ``topk_threshold(x, k)`` on the same backend
    (``lax.top_k`` on jnp, the radix-select kernel otherwise). Selection is
    threshold form — keep ``|x| >= thresh`` with ties included — so the jnp
    reference and the Pallas kernel pick identical entries. Returns
    ``(sent_sum, residual)``: the ``(n,)`` sum of the selected entries over
    the agent axis (fp32 accumulation on every backend, cast back to
    ``x.dtype``) and the ``(m, n)`` unselected remainder (the error-feedback
    residual; ``sent + residual == x`` exactly, elementwise).

    The jnp path states the scatter-accumulate explicitly: the selected
    (value, column) pairs of every agent scatter-add into the server row via
    ``segment_sum`` over the flattened column ids.
    """
    b = resolve_backend(backend)
    if x.ndim == 3:
        thresh = jnp.asarray(thresh, jnp.float32)
        if thresh.shape != x.shape[:2]:
            raise ValueError(
                f"topk_scatter: thresh must be {x.shape[:2]} on the sweep "
                f"path, got {thresh.shape}"
            )
        return jax.vmap(
            lambda xi, ti: topk_scatter(xi, ti, backend=b, block_n=block_n)
        )(x, thresh)
    if x.ndim != 2:
        raise ValueError(f"topk_scatter: x must be (m, n), got {x.shape}")
    m, n = x.shape
    thresh = jnp.asarray(thresh, jnp.float32)
    if thresh.shape != (m,):
        raise ValueError(
            f"topk_scatter: thresh must be ({m},) for x {x.shape}, "
            f"got {thresh.shape}"
        )
    if b == "jnp":
        x32 = x.astype(jnp.float32)
        sent = jnp.where(jnp.abs(x32) >= thresh[:, None], x32, 0.0)
        cols = jnp.broadcast_to(jnp.arange(n)[None, :], (m, n))
        ssum = jax.ops.segment_sum(sent.ravel(), cols.ravel(), num_segments=n)
        return ssum.astype(x.dtype), (x32 - sent).astype(x.dtype)
    from repro.kernels.topk_scatter import topk_scatter_pallas

    return topk_scatter_pallas(
        x, thresh, block_n=block_n, interpret=(b == "interpret")
    )


def policy_infer(obs, pi, norm_mean, norm_std, noise, *, sample: bool = False,
                 backend: str = "auto", block_b: int = 256):
    """Fused serving inference: obs-normalize -> policy MLP -> sample/mean.

    The serving-side primitive (DESIGN.md §16): ``obs`` is a ``(B, obs_dim)``
    observation batch, ``pi`` the Gaussian policy head (the ``params["pi"]``
    subtree of ``repro.rl.policy.init_policy`` — w1/b1/w2/b2/w3/b3/log_std),
    ``norm_mean``/``norm_std`` the ``(obs_dim,)`` fp32 normalization stats and
    ``noise`` a ``(B, act_dim)`` standard-normal operand. Returns the
    ``(B, act_dim)`` actions: the tanh policy mean (``sample=False`` — the
    deterministic decision, the density argmax of the squashed Gaussian) or
    ``mean + exp(log_std) * noise`` (``sample=True``).

    Bitwise contract: the jnp path *is* eager ``rl.policy.policy_apply`` on
    the normalized batch — bit-identical to the training-side policy in eager
    mode, pinned by the serving bench and tests. ``noise`` is an operand in
    both modes so the serving engine can donate its buffer (it aliases the
    action output; JXA004-verified on the ``serve.engine_step`` entry). No
    leading sweep axis: serving batches are bucket-shaped, not swept.
    """
    b = resolve_backend(backend)
    if obs.ndim != 2:
        raise ValueError(f"policy_infer: obs must be (B, obs_dim), got {obs.shape}")
    for name in ("w1", "b1", "w2", "b2", "w3", "b3", "log_std"):
        if name not in pi:
            raise ValueError(f"policy_infer: pi needs {name!r} (got {sorted(pi)})")
    B, obs_dim = obs.shape
    act_dim = pi["w3"].shape[1]
    if pi["w1"].shape[0] != obs_dim:
        raise ValueError(
            f"policy_infer: w1 expects obs_dim {pi['w1'].shape[0]}, "
            f"obs has {obs_dim}"
        )
    if noise.shape != (B, act_dim):
        raise ValueError(
            f"policy_infer: noise must be ({B}, {act_dim}), got {noise.shape}"
        )
    nm = jnp.asarray(norm_mean, jnp.float32)
    ns = jnp.asarray(norm_std, jnp.float32)
    if nm.shape != (obs_dim,) or ns.shape != (obs_dim,):
        raise ValueError(
            f"policy_infer: norm stats must be ({obs_dim},), got "
            f"{nm.shape} / {ns.shape}"
        )
    if b == "jnp":
        from repro.rl.policy import policy_apply

        obsn = (obs.astype(jnp.float32) - nm) / ns
        mean, log_std = policy_apply({"pi": pi}, obsn)
        act = mean + jnp.exp(log_std) * noise.astype(jnp.float32) if sample else mean
        return act.astype(obs.dtype)
    from repro.kernels.policy_infer import policy_infer_pallas

    return policy_infer_pallas(
        obs, pi["w1"], pi["b1"], pi["w2"], pi["b2"], pi["w3"], pi["b3"],
        pi["log_std"], nm, ns, noise,
        sample=sample, block_b=block_b, interpret=(b == "interpret"),
    )


def _check_opt_state(state, required, params, kind):
    for name in required:
        buf = state.get(name)
        if buf is None:
            raise ValueError(f"flat_opt_update[{kind}]: state needs {name!r}")
        if name == "t":
            continue
        if buf.shape != params.shape:
            raise ValueError(
                f"flat_opt_update[{kind}]: state[{name!r}] shape {buf.shape} "
                f"must match params {params.shape}"
            )
        if buf.dtype != jnp.float32:
            raise ValueError(
                f"flat_opt_update[{kind}]: state[{name!r}] must be an fp32 "
                f"accumulator, got {buf.dtype}"
            )


def flat_opt_update(
    params,
    g,
    w,
    state,
    *,
    kind: str,
    lr,
    beta: float = 0.9,
    nesterov: bool = False,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    backend: str = "auto",
    block_n=None,
):
    """Fused within-period-weighted optimizer update on flat buffers.

    ``params``/``g``: matching ``(n,)`` or ``(m, n)`` buffers. ``w`` is the
    strategy's per-step weight (variation mask x decay; scalar or ``(m,)``),
    folded into the gradient *before* any moment accumulation — so a masked
    agent's momentum genuinely does not advance. ``state`` holds the fp32
    accumulators (see ``repro.optim.flat``):

      * ``sgd``      — ``{}``; delegates to the fused :func:`decay_accum` pass.
      * ``momentum`` — ``{"mu"}``; mu <- beta*mu + w*g, params -= lr*mu
                       (nesterov: params -= lr*(beta*mu_new + w*g)),
                       matching ``repro.optim.optimizers.momentum``.
      * ``adam``     — ``{"mu", "nu", "t"}``; bias-corrected Adam(W) matching
                       ``repro.optim.optimizers.adamw`` with fp32 state.

    Returns ``(new_params, new_state)``. All math runs in fp32; params are
    cast back to their own dtype (the moments stay fp32), so bf16 parameter /
    gradient buffers lose nothing in the accumulators.
    """
    if kind not in OPT_KINDS:
        raise ValueError(f"unknown optimizer kind {kind!r}; expected {OPT_KINDS}")
    b = resolve_backend(backend)
    if params.ndim not in (1, 2) or params.shape != g.shape:
        raise ValueError(
            f"flat_opt_update: params/g must be matching (n,) or (m, n) "
            f"buffers, got {params.shape} vs {g.shape}"
        )
    w_arr = jnp.asarray(w, jnp.float32)
    if w_arr.ndim not in (0, 1) or (w_arr.ndim == 1 and params.ndim != 2):
        raise ValueError(
            f"flat_opt_update: w must be scalar or (m,) with (m, n) inputs, "
            f"got w shape {w_arr.shape} for input shape {params.shape}"
        )

    if kind == "sgd":
        new_p = decay_accum(params, g, -lr * w_arr, backend=b, block_n=block_n)
        return new_p, state

    if kind == "momentum":
        _check_opt_state(state, ("mu",), params, kind)
        mu = state["mu"]
        if b == "jnp":
            w_b = w_arr[:, None] if w_arr.ndim == 1 else w_arr
            wg = w_b * g.astype(jnp.float32)
            new_mu = beta * mu + wg
            upd = beta * new_mu + wg if nesterov else new_mu
            new_p = (params.astype(jnp.float32) - lr * upd).astype(params.dtype)
            return new_p, dict(state, mu=new_mu)
        from repro.kernels.flat_update import momentum_update_pallas

        new_p, new_mu = momentum_update_pallas(
            params, g, mu, _per_row(w_arr, params), jnp.asarray(lr, jnp.float32),
            beta, nesterov=nesterov, block_n=block_n,
            interpret=(b == "interpret"),
        )
        return new_p, dict(state, mu=new_mu)

    # kind == "adam"
    _check_opt_state(state, ("mu", "nu", "t"), params, kind)
    mu, nu = state["mu"], state["nu"]
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    bc1 = 1.0 - jnp.float32(b1) ** tf
    bc2 = 1.0 - jnp.float32(b2) ** tf
    if b == "jnp":
        w_b = w_arr[:, None] if w_arr.ndim == 1 else w_arr
        wg = w_b * g.astype(jnp.float32)
        new_mu = b1 * mu + (1.0 - b1) * wg
        new_nu = b2 * nu + (1.0 - b2) * jnp.square(wg)
        p32 = params.astype(jnp.float32)
        step = (new_mu / bc1) / (jnp.sqrt(new_nu / bc2) + eps)
        step = step + weight_decay * p32
        new_p = (p32 - lr * step).astype(params.dtype)
        return new_p, dict(state, mu=new_mu, nu=new_nu, t=t)
    from repro.kernels.flat_update import adam_update_pallas

    new_p, new_mu, new_nu = adam_update_pallas(
        params, g, mu, nu, _per_row(w_arr, params), jnp.asarray(lr, jnp.float32),
        bc1, bc2, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        block_n=block_n, interpret=(b == "interpret"),
    )
    return new_p, dict(state, mu=new_mu, nu=new_nu, t=t)


# --- hot-path registrations ---------------------------------------------------

def _primitive_hot_path(prim: str, backend: str) -> Callable[[], HotPathEntry]:
    """Audit entry for one dispatched primitive on one backend.

    Shapes are tiny (the audit reads jaxprs, not timings) but keep the real
    structure: ``(m, n)`` buffers with per-agent coefficients, so the fp32
    accumulation contract is visible in the lowered equations.
    """

    def factory() -> HotPathEntry:
        m, n = 4, 96

        def buf(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32)

        if prim == "decay_accum":
            return HotPathEntry(
                fn=lambda acc, g, d: decay_accum(acc, g, d, backend=backend),
                args=(buf(m, n), buf(m, n), buf(m)),
            )
        if prim == "scale_rows":
            return HotPathEntry(
                fn=lambda g, w: scale_rows(g, w, backend=backend),
                args=(buf(m, n), buf(m)),
            )
        if prim == "consensus_mix":
            return HotPathEntry(
                fn=lambda g, mix: consensus_mix(g, mix, backend=backend),
                args=(buf(m, n), buf(m, m)),
            )
        if prim == "consensus_gather":
            k_max = 3
            return HotPathEntry(
                fn=lambda g, idx, w: consensus_gather(g, idx, w, backend=backend),
                args=(
                    buf(m, n),
                    jax.ShapeDtypeStruct((m, k_max), jnp.int32),
                    buf(m, k_max),
                ),
            )
        if prim == "row_mean":
            return HotPathEntry(
                fn=lambda g: row_mean(g, backend=backend),
                args=(buf(m, n),),
            )
        if prim == "topk_threshold":
            return HotPathEntry(
                fn=lambda x: topk_threshold(x, 8, backend=backend),
                args=(buf(m, n),),
            )
        if prim == "topk_scatter":
            return HotPathEntry(
                fn=lambda x, t: topk_scatter(x, t, backend=backend),
                args=(buf(m, n), buf(m)),
            )
        if prim == "policy_infer":
            B, od, h, ad = 8, 6, 16, 2
            pi = {
                "w1": buf(od, h), "b1": buf(h),
                "w2": buf(h, h), "b2": buf(h),
                "w3": buf(h, ad), "b3": buf(ad),
                "log_std": buf(ad),
            }
            return HotPathEntry(
                fn=lambda obs, p, nm, ns, z: policy_infer(
                    obs, p, nm, ns, z, sample=True, backend=backend
                ),
                args=(buf(B, od), pi, buf(od), buf(od), buf(B, ad)),
            )
        raise ValueError(f"unknown dispatch primitive {prim!r}")

    return factory


DISPATCH_PRIMITIVES = (
    "decay_accum", "scale_rows", "consensus_mix", "consensus_gather",
    "row_mean", "topk_threshold", "topk_scatter", "policy_infer",
)

# The pallas backend proper needs a TPU to lower; jnp + interpret cover both
# code paths (reference math and kernel bodies) on any host.
for _prim in DISPATCH_PRIMITIVES:
    for _backend in ("jnp", "interpret"):
        register_hot_path(
            f"dispatch.{_prim}[{_backend}]",
            _primitive_hot_path(_prim, _backend),
        )

"""Device time of the training program's layers, by the scopes it names.

The program wraps each layer of ``run_fedrl_core`` in a ``jax.named_scope``
(``repro.utils.scopes``); on the TPU each op's ``tf_op``, read by
``fedbench.xplane_meta``, holds the path of scopes it was traced under. An
op belongs to the first top-level scope on its path (the outermost wins:
the evaluation's rollout counts as evaluation). ``fed_payload_encode`` nests
inside ``fed_local_step`` (gossip) or ``fed_sync`` (uplink) and is counted
besides, so those two are inclusive of it. An op with no scope on its path,
no ``tf_op`` (XLA's own copies) or an ambiguous one is ``unscoped``.

An op counts its own time: its interval clipped to the window, less the
time of the ops nested in it (a ``while`` keeps the gaps between its body's
ops), averaged over the devices; so the top-level scopes and ``unscoped``
partition the devices' busy time. ``Reduced.op_seconds`` counts leaf ops
instead, and takes an op for a parent when another event starts where it
starts: on the TPU a large fusion at times shares its start with a short
event, and then drops out of the sum, so a leaf-op sum moves by percents
between runs of one seed.

The harness hands a metric the reduced trace but not its file, so the file
is found where the harness writes it: the newest trace under its
``fedbench-trace-*`` directories whose device ops name every op of the
reduced trace.
"""
from __future__ import annotations

import glob
import os
import re
import tempfile

import numpy as np

from fedbench import xplane_meta

TOP = ("fed_rollout", "fed_ppo_grad", "fed_local_step", "fed_sync",
       "fed_evaluate")
NESTED = "fed_payload_encode"
UNSCOPED = "unscoped"
TRACE_DIRS = "fedbench-trace-*"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def op_scopes(tf_op) -> tuple:
    """``(top-level scope or None, whether the payload encode is on the
    path)`` of one op's ``tf_op``. Names count as whole words, so a scope
    under a transform (``transpose(jvp(fed_x))``) still counts."""
    words = _WORD.findall(tf_op or "")
    top = next((w for w in words if w in TOP), None)
    return top, NESTED in words


def merged_tf_ops(path: str) -> dict:
    """``{op name: tf_op or None}`` over all device planes of a trace; a
    name the planes disagree on is None."""
    out = {}
    for plane in xplane_meta.tf_ops(path).values():
        for name, tf_op in plane.items():
            out[name] = tf_op if out.get(name, tf_op) == tf_op else None
    return out


def find_tf_ops(reduced):
    """The ``tf_op`` map of the trace ``reduced`` came from, or None."""
    texts = set()
    for d in reduced.devices:
        texts.update(d.texts)
    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                           "*.xplane.pb")
    paths = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                   reverse=True)
    for path in paths:
        ops = merged_tf_ops(path)
        if texts <= ops.keys():
            return ops
    return None


def own_ns(reduced, d) -> np.ndarray:
    """Each event's own time in the window (ns): its clipped interval less
    those of the events directly nested in it. Events of one line nest, so
    an event's parent is the innermost one still open where it starts."""
    s, e = reduced._clipped(d)
    keep = np.flatnonzero(e > s)
    order = keep[np.lexsort((s[keep] - e[keep], s[keep]))]
    s, e = s[order], e[order]
    # Depth: the events already open where each one starts.
    depth = np.arange(len(s)) - np.searchsorted(np.sort(e), s, side="right")
    parent = np.full(len(s), -1)
    for k in range(1, int(depth.max(initial=0)) + 1):
        child, up = np.flatnonzero(depth == k), np.flatnonzero(depth == k - 1)
        parent[child] = up[np.searchsorted(s[up], s[child], side="right") - 1]
    own = (e - s).astype(np.float64)
    nested = parent >= 0
    own -= np.bincount(parent[nested], weights=(e - s)[nested],
                       minlength=len(s))
    out = np.zeros(len(d.start))
    out[order] = own
    return out


def scope_seconds(reduced, tf_ops: dict) -> dict:
    """Device seconds in the window per top-level scope, ``fed_payload_encode``
    and ``unscoped`` (own time of each op, averaged over devices)."""
    out = dict.fromkeys(TOP + (NESTED, UNSCOPED), 0.0)
    for d in reduced.devices:
        per_text = np.bincount(d.index, weights=own_ns(reduced, d),
                               minlength=len(d.texts))
        for i in np.flatnonzero(per_text):
            top, encode = op_scopes(tf_ops.get(d.texts[i]))
            out[top or UNSCOPED] += per_text[i] * 1e-9
            if encode:
                out[NESTED] += per_text[i] * 1e-9
    n = max(len(reduced.devices), 1)
    return {k: float(v / n) for k, v in out.items()}


_last = [None, None]   # (reduced, its scope seconds): seven metrics, one read


def seconds_of(reduced):
    """``scope_seconds`` of the trace ``reduced`` came from, or None where the
    trace names no scope (a program without them)."""
    if _last[0] is not reduced:
        ops = find_tf_ops(reduced)
        secs = scope_seconds(reduced, ops) if ops is not None else None
        if secs is not None and not any(secs[k] for k in TOP + (NESTED,)):
            secs = None
        _last[:] = [reduced, secs]
    return _last[1]


def ms_per_period(out, reduced, scope: str):
    """Device ms of ``scope`` per federation period, or None where it ran
    no op."""
    secs = seconds_of(reduced)
    periods = out.layer.get("periods")
    if not secs or not secs[scope] or not periods:
        return None
    return 1e3 * secs[scope] / periods

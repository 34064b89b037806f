"""Trace-safety analyzer suite: RPR lint rules, jaxpr audit, baseline
workflow, CLI gating, and the retrace guard (DESIGN.md §12).

Each RPR rule has a fixture snippet that must trigger it *exactly once* (and
no other rule); the jaxpr audit is exercised on deliberately-broken toy
entries (bf16 dot, callback-in-scan, constant folding, dead donation); the
CI gate is demonstrated end to end by running ``python -m repro.analysis
--check`` as a subprocess against a file with a fresh violation.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.findings import (
    Finding,
    diff_baseline,
    fingerprint_counts,
    load_baseline,
    save_baseline,
)
from repro.analysis.lint import lint_paths, lint_source
from repro.analysis.retrace import RetraceError, count_compiles

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _rules(src: str):
    return [f.rule for f in lint_source(textwrap.dedent(src), "snippet.py")]


# --- RPR rule fixtures: each fires exactly once --------------------------------

def test_rpr001_key_reuse_fires_exactly_once():
    src = """
    import jax

    def f(key, x):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b + x
    """
    assert _rules(src) == ["RPR001"]


def test_rpr002_python_loop_in_scan_body_fires_exactly_once():
    src = """
    import jax

    def body(carry, x):
        for _ in range(3):
            carry = carry + x
        return carry, None

    def run(xs):
        return jax.lax.scan(body, 0.0, xs)
    """
    assert _rules(src) == ["RPR002"]


def test_rpr003_host_numpy_on_traced_value_fires_exactly_once():
    src = """
    import numpy as np
    import jax

    def cell(p, x):
        y = p * x
        return np.mean(y)

    def run(p, xs):
        return jax.vmap(cell)(p, xs)
    """
    assert _rules(src) == ["RPR003"]


def test_rpr004_concretization_fires_exactly_once():
    src = """
    import jax

    @jax.jit
    def g(x):
        s = x.sum()
        return float(s)
    """
    assert _rules(src) == ["RPR004"]


def test_rpr005_mutable_jit_default_fires_exactly_once():
    src = """
    import jax

    @jax.jit
    def h(x, opts={}):
        return x
    """
    assert _rules(src) == ["RPR005"]


def test_rpr005_jit_in_loop_fires():
    src = """
    import jax

    def bench(fns, x):
        outs = []
        for f in fns:
            outs.append(jax.jit(f)(x))
        return outs
    """
    assert _rules(src) == ["RPR005"]


# --- RPR001 dataflow corners ---------------------------------------------------

def test_rpr001_split_rebind_is_clean():
    src = """
    import jax

    def f(key):
        key, sub = jax.random.split(key)
        a = jax.random.normal(sub, (3,))
        key, sub = jax.random.split(key)
        return a + jax.random.normal(sub, (3,))
    """
    assert _rules(src) == []


def test_rpr001_early_return_branches_are_exclusive():
    # `if c: return f(key)` / `return g(key)` consumes the key once.
    src = """
    import jax

    def f(key, flag):
        if flag:
            return jax.random.normal(key, (3,))
        return jax.random.uniform(key, (3,))
    """
    assert _rules(src) == []


def test_rpr001_double_split_of_same_key_flagged():
    # Splitting one key twice yields identical streams.
    src = """
    import jax

    def f(key):
        a = jax.random.split(key, 2)
        b = jax.random.split(key, 2)
        return a, b
    """
    assert _rules(src) == ["RPR001"]


def test_rpr001_captured_key_in_tree_map_lambda_flagged():
    # The quickstart bug: same key drawn once per leaf.
    src = """
    import jax

    def noisy(params, key):
        return jax.tree.map(
            lambda x: x + jax.random.normal(key, x.shape), params
        )
    """
    assert _rules(src) == ["RPR001"]


def test_rpr001_loop_reuse_flagged_and_noqa_suppresses():
    src = """
    import jax

    def f(key, n):
        out = 0.0
        for _ in range(n):
            out = out + jax.random.normal(key, ())
        return out
    """
    assert _rules(src) == ["RPR001"]
    suppressed = src.replace(
        "jax.random.normal(key, ())",
        "jax.random.normal(key, ())  # noqa: RPR001",
    )
    assert _rules(suppressed) == []


# --- the satellite regression: the hot-path RL modules stay RPR001-clean -------

def test_rl_modules_have_no_prng_reuse():
    """rl/rollout.py + rl/fedrl.py + core/fmarl.py + the quickstart example
    carry zero RPR001 findings (the `_eval_grad_norm` bug class, PR 2, and
    the per-leaf quickstart noise fix stay fixed)."""
    paths = [
        os.path.join(ROOT, "src", "repro", "rl"),
        os.path.join(ROOT, "src", "repro", "core", "fmarl.py"),
        os.path.join(ROOT, "examples", "quickstart.py"),
    ]
    findings = [f for f in lint_paths(paths, root=ROOT) if f.rule == "RPR001"]
    assert findings == [], [f.render() for f in findings]


# --- baseline bookkeeping ------------------------------------------------------

def _finding(rule="RPR001", path="a.py", scope="f", snippet="key=k"):
    return Finding(rule=rule, path=path, scope=scope,
                   message="m", snippet=snippet, line=3)


def test_fingerprint_ignores_line_numbers():
    a = _finding()
    b = Finding(**{**a.__dict__, "line": 99})
    assert a.fingerprint == b.fingerprint


def test_baseline_roundtrip_and_diff(tmp_path):
    f1, f2 = _finding(), _finding(scope="g")
    p = str(tmp_path / "baseline.json")
    save_baseline([f1, f2], p)
    base = load_baseline(p)
    assert base == fingerprint_counts([f1, f2])

    # same findings -> nothing new; one extra duplicate -> exactly it is new
    new, resolved = diff_baseline([f1, f2], base)
    assert (new, resolved) == ([], [])
    new, resolved = diff_baseline([f1, f1, f2], base)
    assert new == [f1] and resolved == []
    # a baselined finding disappearing is reported as resolved
    new, resolved = diff_baseline([f2], base)
    assert new == [] and resolved == [f1.fingerprint]


def test_committed_baseline_is_schema_valid_and_current():
    """The checked-in baseline matches what the lint produces today — a
    stale baseline would hide rot in either direction."""
    from repro.analysis.findings import BASELINE_PATH

    base = load_baseline(BASELINE_PATH)
    findings = lint_paths(
        [os.path.join(ROOT, d) for d in ("src/repro", "benchmarks", "examples")],
        root=ROOT,
    )
    new, resolved = diff_baseline(findings, base)
    assert new == [], [f.render() for f in new]
    assert resolved == []


# --- jaxpr audit ---------------------------------------------------------------

def _audit(fn, *args, donate=()):
    from repro.analysis.jaxpr_audit import audit_entry
    from repro.kernels.dispatch import HotPathEntry

    return audit_entry(
        "toy", HotPathEntry(fn=fn, args=args, donate_argnums=tuple(donate))
    )


def test_jxa001_flags_bf16_accumulating_dot():
    """A bf16 dot without preferred_element_type accumulates below fp32."""
    bf = jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)

    def bad(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    rules = [f.rule for f in _audit(bad, bf, bf)]
    assert rules == ["JXA001"]

    def good(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)

    assert [f.rule for f in _audit(good, bf, bf)] == []


def test_jxa001_flags_bf16_reduce_sum():
    bf = jax.ShapeDtypeStruct((16,), jnp.bfloat16)

    def bad(x):
        # keep the reduction in bf16 explicitly (jnp.sum would upcast)
        return jax.lax.reduce_sum_p.bind(x, axes=(0,))

    assert [f.rule for f in _audit(bad, bf)] == ["JXA001"]


def test_jxa002_flags_callback_inside_scan_only():
    xs = jax.ShapeDtypeStruct((4,), jnp.float32)

    def with_print(xs):
        def body(c, x):
            jax.debug.print("x={x}", x=x)
            return c + x, x
        return jax.lax.scan(body, 0.0, xs)

    rules = [f.rule for f in _audit(with_print, xs)]
    assert "JXA002" in rules

    def outside(xs):
        jax.debug.print("sum={s}", s=xs.sum())
        return xs * 2

    assert "JXA002" not in [f.rule for f in _audit(outside, xs)]


def test_jxa003_flags_large_constant_folded_literal():
    big = jnp.ones((256, 256))  # 65536 elements > LARGE_CONST_ELEMS
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    assert [f.rule for f in _audit(lambda v: v + big, x)] == ["JXA003"]

    small = jnp.ones((8, 8))
    y = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    assert [f.rule for f in _audit(lambda v: v + small, y)] == []


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_jxa004_flags_declared_but_unused_donation():
    x = jax.ShapeDtypeStruct((64,), jnp.float32)

    # output shape differs -> the donated buffer cannot be reused
    rules = [f.rule for f in _audit(lambda v: v.sum(), x, donate=(0,))]
    assert rules == ["JXA004"]

    # same-shape output -> XLA aliases the donated input, no finding
    assert [f.rule for f in _audit(lambda v: v + 1.0, x, donate=(0,))] == []


def test_audit_registry_covers_the_whole_hot_path():
    """The dispatch primitives on both CPU-executable backends, the
    compressed comm reductions, both driver cores, and the sweep engine's
    static-point fn are registered."""
    from repro.analysis.jaxpr_audit import collect_entries

    factories, import_findings = collect_entries()
    assert import_findings == []
    names = set(factories)
    for prim in ("decay_accum", "scale_rows", "consensus_mix", "row_mean",
                 "topk_threshold", "topk_scatter"):
        for backend in ("jnp", "interpret"):
            assert f"dispatch.{prim}[{backend}]" in names
    # the compressed server reductions register their own entries: the
    # fp32-accumulation contract holds even when the wire format is not fp32
    for kind in ("topk", "int8"):
        for backend in ("jnp", "interpret"):
            assert f"comm.{kind}_reduce[{backend}]" in names
    assert {"rl.run_fedrl_core", "core.run_fmarl_core",
            "sweep.static_point_fn"} <= names
    # async federation layer: the masked FedBuff server step on both
    # CPU-executable backends, plus the delay sweep axis's static-point fn
    for backend in ("jnp", "interpret"):
        assert f"async_fed.masked_server_step[{backend}]" in names
    assert "async_fed.delay_axis_fn" in names


@pytest.mark.slow
def test_full_audit_is_clean():
    """Zero sub-fp32 / callback / const / donation findings across every
    registered entry (the acceptance bar for the jnp + interpret backends)."""
    from repro.analysis.jaxpr_audit import run_audit

    findings = run_audit()
    assert findings == [], [f.render() for f in findings]


def test_audit_on_dispatch_primitives_is_clean_and_fast():
    """The tier-1 subset of the audit: the four primitives on both backends
    accumulate in fp32 (the docstring contract, now machine-checked)."""
    from repro.analysis.jaxpr_audit import run_audit
    from repro.kernels.dispatch import DISPATCH_PRIMITIVES

    names = [
        f"dispatch.{p}[{b}]"
        for p in DISPATCH_PRIMITIVES for b in ("jnp", "interpret")
    ]
    findings = run_audit(only=names)
    assert findings == [], [f.render() for f in findings]


# --- the CI gate, end to end ---------------------------------------------------

def _run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(ROOT, "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_cli_check_fails_on_new_finding_and_passes_when_clean(tmp_path):
    bad = tmp_path / "fresh_violation.py"
    bad.write_text(textwrap.dedent("""
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            return a + jax.random.uniform(key, (2,))
    """))
    empty_baseline = tmp_path / "baseline.json"
    empty_baseline.write_text(json.dumps(
        {"schema_version": 1, "findings": {}}
    ))

    r = _run_cli(
        ["--check", "--skip-jaxpr", "--baseline", str(empty_baseline),
         str(bad)],
        cwd=str(tmp_path),
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "RPR001" in r.stdout

    good = tmp_path / "clean.py"
    good.write_text("import jax\n\ndef f(key):\n"
                    "    return jax.random.normal(key, (2,))\n")
    r = _run_cli(
        ["--check", "--skip-jaxpr", "--baseline", str(empty_baseline),
         str(good)],
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_update_baseline_then_check_passes(tmp_path):
    bad = tmp_path / "legacy.py"
    bad.write_text(textwrap.dedent("""
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            return a + jax.random.uniform(key, (2,))
    """))
    baseline = tmp_path / "baseline.json"
    r = _run_cli(
        ["--update-baseline", "--skip-jaxpr", "--baseline", str(baseline),
         str(bad)],
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli(
        ["--check", "--skip-jaxpr", "--baseline", str(baseline), str(bad)],
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stdout + r.stderr


# --- retrace guard -------------------------------------------------------------

def test_count_compiles_sees_fresh_jit_and_not_cache_hits():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.arange(4.0)
    with count_compiles() as c:
        jax.block_until_ready(f(x))
    assert c.count >= 1
    with count_compiles() as c2:
        jax.block_until_ready(f(x))
    assert c2.count == 0


def test_count_compiles_nests():
    g = jax.jit(lambda x: x - 3.0)
    x = jnp.arange(8.0)
    with count_compiles() as outer:
        with count_compiles() as inner:
            jax.block_until_ready(g(x))
    assert inner.count >= 1
    assert outer.count >= inner.count


def test_assert_max_compiles_fixture_enforces_budget(assert_max_compiles):
    h = jax.jit(lambda x: x ** 2 + 7.0)
    x = jnp.arange(16.0)
    jax.block_until_ready(h(x))  # warm
    _, n = assert_max_compiles(0, lambda: jax.block_until_ready(h(x)))
    assert n == 0

    h2 = jax.jit(lambda x: x ** 3 - 11.0)
    with pytest.raises(RetraceError):
        assert_max_compiles(0, lambda: jax.block_until_ready(h2(x)))

"""Fixture suite for the trace/shard-safety analyzer (dnn_tpu/analysis).

One known-bad snippet per rule ID (must be flagged) and one known-good
twin (must not be), plus: the self-lint gate (the repo is clean modulo
analysis/baseline.json, and every baseline entry still fires and is
justified), fingerprint stability under line drift, the jaxpr program
checks (PRG001/2/3/4) on hand-built programs, and the CLI exit-code
contract — 0 on HEAD, nonzero when a fixture hazard is injected.
"""

import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dnn_tpu.analysis.findings import (
    diff_against_baseline,
    load_baseline,
)
from dnn_tpu.analysis.lint import lint_paths, lint_source

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO_ROOT, "dnn_tpu")
ACCEPTED = os.path.join(PKG_DIR, "analysis", "baseline.json")


def rules_of(src):
    return sorted({f.rule for f in lint_source(textwrap.dedent(src), "t")})


# ----------------------------------------------------------------------
# rule fixtures: (rule, known-bad, known-good twin)
# ----------------------------------------------------------------------

FIXTURES = {
    "TPU001": (
        """
        import jax
        @jax.jit
        def relu_bad(x):
            if x > 0:
                return x
            return -x
        """,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def relu_good(x):
            return jnp.where(x > 0, x, -x)
        """,
    ),
    "TPU002": (
        """
        import jax
        @jax.jit
        def loss_bad(x):
            return float(x.sum())
        """,
        """
        import jax
        def loss_good(x):
            # host conversion OUTSIDE the traced function is fine
            return float(x.sum())
        """,
    ),
    "TPU003": (
        """
        import jax
        def draws_bad():
            key = jax.random.PRNGKey(0)
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a, b
        """,
        """
        import jax
        def draws_good():
            key = jax.random.PRNGKey(0)
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (4,))
            b = jax.random.uniform(k2, (4,))
            return a, b
        """,
    ),
    "TPU004": (
        """
        import jax
        def _step(cache, tok):
            return cache
        step = jax.jit(_step, donate_argnums=(0,))
        def decode_bad(cache, tok):
            out = step(cache, tok)
            return cache.sum() + out.sum()
        """,
        """
        import jax
        def _step(cache, tok):
            return cache
        step = jax.jit(_step, donate_argnums=(0,))
        def decode_good(cache, tok):
            cache = step(cache, tok)
            return cache.sum()
        """,
    ),
    "TPU005": (
        """
        import jax
        def _step(cache, pos):
            return cache
        step = jax.jit(_step)
        def run_bad(cache, t):
            for i in range(8):
                cache = step(cache, t + i)
            return cache
        """,
        """
        import jax
        import jax.numpy as jnp
        def _step(cache, pos):
            return cache
        step = jax.jit(_step)
        def run_good(cache, t):
            for i in range(8):
                cache = step(cache, jnp.int32(t + i))
            return cache
        """,
    ),
    "TPU006": (
        """
        import jax
        from jax import lax
        def make(mesh):
            def body(x):
                return lax.cond(lax.axis_index('s') == 0,
                                lambda v: lax.psum(v, 's'),
                                lambda v: v, x)
            return jax.shard_map(body, mesh=mesh, in_specs=None,
                                 out_specs=None)
        """,
        """
        import jax
        from jax import lax
        def make(mesh):
            def body(x):
                return lax.cond(lax.axis_index('s') == 0,
                                lambda v: lax.psum(2 * v, 's'),
                                lambda v: lax.psum(v, 's'), x)
            return jax.shard_map(body, mesh=mesh, in_specs=None,
                                 out_specs=None)
        """,
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fixture_pair(rule):
    bad, good = FIXTURES[rule]
    assert rule in rules_of(bad), f"{rule} must flag its bad fixture"
    assert rules_of(good) == [], \
        f"{rule} good twin must be clean, got {rules_of(good)}"


# extra per-rule behaviors beyond the canonical pair -------------------

def test_tpu001_static_shape_branching_is_clean():
    src = """
    import jax
    @jax.jit
    def f(ids):
        b, t = ids.shape
        if t > 128:
            raise ValueError("too long")
        return ids * b
    """
    assert rules_of(src) == []


def test_tpu001_static_argnums_params_untainted():
    src = """
    import jax
    def _run(x, n):
        if n > 4:
            return x[:4]
        return x
    run = jax.jit(_run, static_argnums=(1,))
    """
    assert rules_of(src) == []


def test_tpu003_reuse_across_loop_iterations():
    src = """
    import jax
    def f(key):
        key = jax.random.PRNGKey(0)
        out = []
        for i in range(4):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    assert "TPU003" in rules_of(src)
    good = """
    import jax
    def f():
        key = jax.random.PRNGKey(0)
        out = []
        for i in range(4):
            key, sub = jax.random.split(key)
            out.append(jax.random.normal(sub, (2,)))
        return out
    """
    assert rules_of(good) == []


def test_tpu004_donation_in_loop_without_rebind():
    src = """
    import jax
    def _step(cache):
        return cache
    step = jax.jit(_step, donate_argnums=(0,))
    def run(cache):
        for _ in range(4):
            out = step(cache)
        return out
    """
    assert "TPU004" in rules_of(src)


def test_tpu005_static_argnums_in_loop():
    src = """
    import jax
    def _grow(cache, n):
        return cache
    grow = jax.jit(_grow, static_argnums=(1,))
    def run(cache):
        for i in range(16):
            cache = grow(cache, i * 2)
        return cache
    """
    assert "TPU005" in rules_of(src)


def test_tpu006_python_if_divergence():
    src = """
    import jax
    from jax import lax
    def make(mesh, flag):
        def body(x):
            if flag:
                x = lax.psum(x, 's')
            return x
        return jax.shard_map(body, mesh=mesh, in_specs=None,
                             out_specs=None)
    """
    assert "TPU006" in rules_of(src)


# ----------------------------------------------------------------------
# fingerprints + baseline + self-lint
# ----------------------------------------------------------------------

def test_fingerprint_survives_line_drift():
    src = FIXTURES["TPU001"][0]
    before = lint_source(textwrap.dedent(src), "m")
    shifted = "# pad\n# pad\n# pad\n" + textwrap.dedent(src)
    after = lint_source(shifted, "m")
    assert [f.fingerprint for f in before] == \
        [f.fingerprint for f in after]
    assert before[0].line != after[0].line


def test_self_lint_clean_modulo_baseline():
    """The repo's own package carries no unbaselined AST findings, and
    every baseline entry both still fires and says why it stays."""
    findings = lint_paths([PKG_DIR], repo_root=REPO_ROOT)
    entries = load_baseline(ACCEPTED)
    new, suppressed, stale = diff_against_baseline(findings, entries)
    assert not new, "unbaselined findings:\n" + "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in new)
    lint_rules = {e["fingerprint"] for e in entries
                  if e["fingerprint"].startswith("TPU")}
    fired = {f.fingerprint for f in suppressed}
    assert lint_rules <= fired, \
        f"stale lint baseline entries: {lint_rules - fired}"


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"fingerprint": "TPU001:x:abc"}]}))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(p)


# ----------------------------------------------------------------------
# program pass (jaxpr checks)
# ----------------------------------------------------------------------

def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("s",))


def test_prg001_divergent_cond_collectives_flagged():
    from dnn_tpu.analysis.program import check_branch_collectives

    mesh = _mesh2()

    def body(x):
        return lax.cond(lax.axis_index("s") == 0,
                        lambda v: lax.psum(v, "s"),
                        lambda v: v * 1.0, x)

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.ones((4,)))
    findings = check_branch_collectives(closed, "fixture")
    assert any(f.rule == "PRG001" for f in findings)


def test_prg001_matched_cond_collectives_clean():
    from dnn_tpu.analysis.program import (
        check_branch_collectives,
        collective_signature,
    )

    mesh = _mesh2()

    def body(x):
        return lax.cond(lax.axis_index("s") == 0,
                        lambda v: lax.psum(2 * v, "s"),
                        lambda v: lax.psum(v, "s"), x)

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.ones((4,)))
    assert check_branch_collectives(closed, "fixture") == []
    assert "psum" in collective_signature(closed)


def test_prg002_baked_constant_flagged():
    from dnn_tpu.analysis.program import baked_constants

    big = jnp.zeros((512, 1024))  # 2 MB closed-over constant

    def f(x):
        return x @ big

    closed = jax.make_jaxpr(f)(jnp.ones((4, 512)))
    assert any(f.rule == "PRG002"
               for f in baked_constants(closed, min_bytes=1 << 20))

    def g(w, x):  # same math, weights as an argument — clean
        return x @ w

    closed = jax.make_jaxpr(g)(big, jnp.ones((4, 512)))
    assert baked_constants(closed, min_bytes=1 << 20) == []


def test_prg003_donation_coverage():
    from dnn_tpu.analysis.program import donation_report

    def step(w, cache):
        return cache.at[0].set(w.sum())

    cache = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8,), jnp.float32)
    rep = donation_report(step, (w, cache), (1,), where="fixture")
    assert rep["aliased"] == rep["expected"] == 1
    assert rep["findings"] == []

    def shrink(w, cache):  # output can never alias the donated input
        return cache[:1, :1]

    import warnings

    with warnings.catch_warnings():
        # the unusable-donation warning IS the condition under test
        warnings.simplefilter("ignore", UserWarning)
        rep = donation_report(shrink, (w, cache), (1,), where="fixture")
    assert any(f.rule == "PRG003" for f in rep["findings"])


def test_prg004_census_bound():
    from dnn_tpu.analysis.program import recompile_census

    shapes = [(jax.ShapeDtypeStruct((n, 4), jnp.float32),)
              for n in (1, 2, 3, 4)]
    rep = recompile_census(shapes, bound=2, where="fixture")
    assert rep["programs"] == 4
    assert any(f.rule == "PRG004" for f in rep["findings"])
    rep = recompile_census(shapes * 3, bound=4, where="fixture")
    assert rep["programs"] == 4 and rep["findings"] == []


def test_decode_audit_contract():
    """The real decode paths: full donation coverage, no cache-sized
    StableHLO transposes, bucketed census within the ladder bound, and
    the naive counterfactual correctly one-program-per-length."""
    from dnn_tpu.analysis.program import audit_decode_paths

    rep = audit_decode_paths(max_len=64)
    assert rep["findings"] == []
    assert rep["donation"]["aliased"] == rep["donation"]["expected"]
    assert rep["bucketed_census"]["programs"] <= len(rep["ladder"])
    assert rep["naive_census"]["programs"] == rep["naive_census"]["calls"]


def test_pipeline_audit_collectives_consistent():
    from dnn_tpu.analysis.program import audit_pipeline_programs

    rep = audit_pipeline_programs()
    assert rep.get("skipped") is None
    assert rep["findings"] == []
    # the GPipe loop: one hop ppermute + one last-stage psum, visible
    # in the traced program
    assert "ppermute" in rep["collective_signature"]
    assert "psum" in rep["collective_signature"]


def test_assert_collectives_consistent():
    """utils/audit.py's static triad leg: raises on divergent branches,
    passes on matched ones — without executing anything."""
    from dnn_tpu.utils.audit import assert_collectives_consistent

    mesh = _mesh2()

    def diverging(x):
        return lax.cond(lax.axis_index("s") == 0,
                        lambda v: lax.psum(v, "s"),
                        lambda v: v * 1.0, x)

    def matched(x):
        return lax.cond(lax.axis_index("s") == 0,
                        lambda v: lax.psum(2 * v, "s"),
                        lambda v: lax.psum(v, "s"), x)

    xs = jax.ShapeDtypeStruct((4,), jnp.float32)
    with pytest.raises(AssertionError, match="divergent collective"):
        assert_collectives_consistent(
            jax.shard_map(diverging, mesh=mesh, in_specs=P(),
                          out_specs=P(), check_vma=False), xs)
    assert_collectives_consistent(
        jax.shard_map(matched, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False), xs)


# ----------------------------------------------------------------------
# CLI gate
# ----------------------------------------------------------------------

def test_cli_exits_zero_on_head():
    """The acceptance gate: the full analyzer (lint + program pass) runs
    clean on HEAD against the checked-in baseline."""
    from dnn_tpu.analysis.__main__ import main

    assert main([]) == 0


def test_cli_nonzero_on_injected_hazard(tmp_path, capsys):
    from dnn_tpu.analysis.__main__ import main

    bad = tmp_path / "user_model.py"
    bad.write_text(textwrap.dedent(FIXTURES["TPU003"][0]))
    rc = main([str(bad), "--no-program", "--no-baseline"])
    assert rc == 1
    assert "TPU003" in capsys.readouterr().out

    good = tmp_path / "user_model_ok.py"
    good.write_text(textwrap.dedent(FIXTURES["TPU003"][1]))
    assert main([str(good), "--no-program", "--no-baseline"]) == 0


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_cli_nonzero_per_rule(rule, tmp_path):
    """Every rule's bad fixture, injected as user code, fails the gate."""
    from dnn_tpu.analysis.__main__ import main

    bad = tmp_path / f"inject_{rule.lower()}.py"
    bad.write_text(textwrap.dedent(FIXTURES[rule][0]))
    assert main([str(bad), "--no-program", "--no-baseline"]) == 1

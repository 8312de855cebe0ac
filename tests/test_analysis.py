"""Parity auditor (src/repro/analysis): every rule has a must-trigger and a
must-not-trigger case, pragmas and the baseline round-trip work, and the
clean tree audits to zero unbaselined findings.

AST rules run against tiny fixture trees laid out like the repo
(``src/repro/core/...``); jaxpr rules run against synthetic traced
functions (so each detector is exercised in isolation) AND against the
real captured engine calls. The CLI is driven through ``main(argv)``.
"""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import findings as F
from repro.analysis.ast_audit import audit_tree
from repro.analysis.jaxpr_audit import (audit_carry_only,
                                        audit_closed_jaxpr)
from repro.core.numerics import fma_free_madd, guarded_denominator

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------- helpers

def write_tree(root, files):
    for rel, src in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent(src))


def rules_of(findings):
    return sorted({f.rule for f in findings})


VDES_OK = """
    def simulate(x):
        def _select_events(s):
            return s

        def _fleet_stage(s):
            return s
        return _fleet_stage(_select_events(x))
"""

DES_OK = """
    # mirror: vdes._select_events
    A = 1
    # mirror: vdes._fleet_stage
    B = 2
"""


def fixture_findings(tmp_path, files):
    write_tree(str(tmp_path), files)
    return audit_tree(str(tmp_path))


# ------------------------------------------------------------- AST: mirror

def test_mirror_clean(tmp_path):
    fs = fixture_findings(tmp_path, {"src/repro/core/vdes.py": VDES_OK,
                                     "src/repro/core/des.py": DES_OK})
    assert rules_of(fs) == []


def test_mirror_missing_triggers(tmp_path):
    des = "# mirror: vdes._select_events\n"
    fs = fixture_findings(tmp_path, {"src/repro/core/vdes.py": VDES_OK,
                                     "src/repro/core/des.py": des})
    assert rules_of(fs) == ["mirror-missing"]
    assert "_fleet_stage" in fs[0].message


def test_mirror_stale_triggers(tmp_path):
    des = DES_OK + "    # mirror: vdes._gone_stage\n"
    fs = fixture_findings(tmp_path, {"src/repro/core/vdes.py": VDES_OK,
                                     "src/repro/core/des.py": des})
    assert rules_of(fs) == ["mirror-stale"]
    assert "_gone_stage" in fs[0].message


# ------------------------------------------------------------- AST: layout

def test_layout_index_triggers_and_named_passes(tmp_path):
    src = """
        CTRL_T_END = 3

        def compile(ctrl):
            ctrl[3] = 1.0          # hard-coded: must trigger
            ctrl[CTRL_T_END] = 1.0  # named: must not
            return ctrl
    """
    fs = fixture_findings(tmp_path, {"src/repro/ops/capacity.py": src})
    hits = [f for f in fs if f.rule == "layout-index"]
    assert len(hits) == 1
    assert "ctrl[3]" in hits[0].snippet


def test_layout_index_shape_access_is_exempt(tmp_path):
    src = "def f(fleet):\n    return fleet.shape[0]\n"
    fs = fixture_findings(tmp_path, {"src/repro/ops/scenario.py": src})
    assert rules_of(fs) == []


def test_layout_index_literal_range_unpack(tmp_path):
    src = "def f(trig):\n    return [trig[i] for i in range(6)]\n"
    fs = fixture_findings(tmp_path, {"src/repro/core/batching.py": src})
    assert rules_of(fs) == ["layout-index"]


def test_layout_redef_triggers_outside_owner(tmp_path):
    src = "TRIG_FIELDS = 7\n"
    fs = fixture_findings(tmp_path / "a", {"src/repro/ops/capacity.py": src})
    assert rules_of(fs) == ["layout-redef"]
    # the owning module may define it
    fs = fixture_findings(tmp_path / "b", {"src/repro/core/des.py": src})
    assert "layout-redef" not in rules_of(fs)


# ---------------------------------------------------------------- AST: fma

def test_engine_fma_triggers_in_engine_file(tmp_path):
    src = "def f(a, b, c):\n    return a - b * c\n"
    fs = fixture_findings(tmp_path, {"src/repro/core/metrics.py": src})
    assert rules_of(fs) == ["engine-fma"]


def test_engine_fma_helper_and_index_arithmetic_pass(tmp_path):
    src = """
        from repro.core.numerics import fma_free_msub

        def f(a, b, c, row, n):
            x = fma_free_msub(a, b, c)     # rounded product: fine
            return x + row[4 * n + 1]      # integer index math: fine
    """
    fs = fixture_findings(tmp_path, {"src/repro/core/metrics.py": src})
    assert rules_of(fs) == []


def test_engine_fma_ignored_outside_engine_files(tmp_path):
    src = "def f(a, b, c):\n    return a - b * c\n"
    fs = fixture_findings(tmp_path, {"src/repro/ops/failures.py": src})
    assert rules_of(fs) == []


# ------------------------------------------------- AST: hot-f64 / defaults

def test_hot_f64_triggers_in_vdes_hot_path(tmp_path):
    src = """
        def simulate(x):
            return float(x)

        def simulate_to_trace(x):
            return float(x)    # host-side conversion: exempt
    """
    fs = fixture_findings(tmp_path, {"src/repro/core/vdes.py": src})
    hits = [f for f in fs if f.rule == "hot-f64"]
    assert len(hits) == 1


def test_mutable_default_triggers(tmp_path):
    src = "def f(a=[]):\n    return a\n\ndef g(a=None):\n    return a\n"
    fs = fixture_findings(tmp_path, {"src/repro/obs/spans.py": src})
    assert rules_of(fs) == ["mutable-default"]


def test_probe_reduce_triggers_in_probe_stage(tmp_path):
    src = """
        import jax.numpy as jnp

        def simulate(x):
            def _probe_stage(s):
                return jnp.sum(s) + jnp.min(s)   # sum: trigger; min: fine
            return _probe_stage(x)

        def elsewhere(s):
            return jnp.sum(s)                    # not probe code: fine
    """
    fs = fixture_findings(tmp_path, {"src/repro/core/vdes.py": src})
    hits = [f for f in fs if f.rule == "probe-reduce"]
    assert len(hits) == 1


def test_bad_pragma_triggers(tmp_path):
    src = "X = 1  # parity: allow(not-a-rule)\n"
    fs = fixture_findings(tmp_path, {"src/repro/core/trace.py": src})
    assert rules_of(fs) == ["bad-pragma"]


def test_pragma_in_docstring_is_not_a_pragma(tmp_path):
    src = '"""Docs show `# parity: allow(bogus-rule)` syntax."""\nX = 1\n'
    fs = fixture_findings(tmp_path, {"src/repro/core/trace.py": src})
    assert rules_of(fs) == []


# ------------------------------------------------------------ jaxpr rules

def _trace(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def test_while_fma_triggers_on_bare_madd(tmp_path):
    def f(x):
        return jax.lax.while_loop(lambda c: c < 10.0,
                                  lambda c: c + c * 0.99, x)

    fs = audit_closed_jaxpr(_trace(f, 1.0), str(tmp_path), "synth")
    assert "while-fma" in rules_of(fs)


def test_while_fma_clean_with_fma_free_helper(tmp_path):
    def f(x):
        return jax.lax.while_loop(
            lambda c: c < 10.0,
            lambda c: fma_free_madd(c, c, 0.99, xp=jnp), x)

    fs = audit_closed_jaxpr(_trace(f, 1.0), str(tmp_path), "synth")
    assert "while-fma" not in rules_of(fs)


def test_loop_reduce_float_triggers_int_passes(tmp_path):
    def f_float(x):
        return jax.lax.while_loop(
            lambda c: c < 10.0,
            lambda c: jnp.sum(jnp.stack([c, c, c])), x)

    def f_int(x):
        return jax.lax.while_loop(
            lambda c: c < 10,
            lambda c: jnp.sum(jnp.stack([c, c]), dtype=jnp.int32), x)

    fs = audit_closed_jaxpr(_trace(f_float, 1.0), str(tmp_path), "synth")
    assert "loop-reduce" in rules_of(fs)
    fs = audit_closed_jaxpr(_trace(f_int, 1), str(tmp_path), "synth")
    assert "loop-reduce" not in rules_of(fs)


def test_unguarded_div_triggers_guarded_passes(tmp_path):
    def bad(x, d):
        return jax.lax.while_loop(lambda c: c < 10.0,
                                  lambda c: c / (d - 1.0), x)

    def good(x, d):
        return jax.lax.while_loop(
            lambda c: c < 10.0,
            lambda c: c / guarded_denominator(d - 1.0, xp=jnp), x)

    # f32 operands, as in the engine: bare Python floats would seed a
    # weak-typed carry, a separate finding (carry-weak-type)
    x, d = jnp.float32(1.0), jnp.float32(3.0)
    fs = audit_closed_jaxpr(_trace(bad, x, d), str(tmp_path), "synth")
    assert rules_of(fs) == ["unguarded-div"]
    fs = audit_closed_jaxpr(_trace(good, x, d), str(tmp_path), "synth")
    assert "unguarded-div" not in rules_of(fs)
    assert rules_of(fs) == []


def test_unguarded_log_triggers_clamped_passes(tmp_path):
    def bad(x):
        return jax.lax.while_loop(lambda c: c < 10.0,
                                  lambda c: c + jnp.log(c), x)

    def good(x):
        return jax.lax.while_loop(
            lambda c: c < 10.0,
            lambda c: c + jnp.log(jnp.maximum(c, 1e-6)), x)

    fs = audit_closed_jaxpr(_trace(bad, 2.0), str(tmp_path), "synth")
    assert "unguarded-log" in rules_of(fs)
    fs = audit_closed_jaxpr(_trace(good, 2.0), str(tmp_path), "synth")
    assert "unguarded-log" not in rules_of(fs)


def test_carry_f64_caught_under_x64(tmp_path):
    def f(x):
        return jax.lax.while_loop(lambda c: c < 10.0, lambda c: c + 1.0, x)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(f)(jnp.float64(0.0))
    fs = audit_carry_only(closed, str(tmp_path), "synth[x64]")
    assert rules_of(fs) == ["carry-f64"]

    closed32 = jax.make_jaxpr(f)(jnp.float32(0.0))
    assert audit_carry_only(closed32, str(tmp_path), "synth") == []


def test_carry_weak_type_caught(tmp_path):
    def f():
        # 0.0 enters the carry as a weak-typed Python scalar
        return jax.lax.while_loop(lambda c: c < 10.0, lambda c: c + 1.0,
                                  0.0)

    fs = audit_carry_only(jax.make_jaxpr(f)(), str(tmp_path), "synth")
    assert rules_of(fs) == ["carry-weak-type"]


def test_f64_const_conversion_caught(tmp_path):
    def f(x):
        return (x.astype(jnp.float64) * 2.0).astype(jnp.float32)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(f)(jnp.float32(1.0))
    fs = audit_closed_jaxpr(closed, str(tmp_path), "synth[x64]")
    assert "f64-const" in rules_of(fs)


# ------------------------------------------- pragmas, baseline, fingerprint

def test_pragma_suppresses_on_line_and_line_above(tmp_path):
    path = tmp_path / "src" / "repro" / "core"
    path.mkdir(parents=True)
    (path / "metrics.py").write_text(
        "def f(a, b, c, d, e, f2):\n"
        "    x = a - b * c  # parity: allow(engine-fma)\n"
        "    # justified false positive  # parity: allow(engine-fma)\n"
        "    y = d - e * f2\n"
        "    return x + y * x\n")
    fs = audit_tree(str(tmp_path))
    active, suppressed = F.split_suppressed(fs, str(tmp_path))
    assert len(suppressed) == 2          # same-line and line-above pragmas
    assert len(active) == 1              # the un-pragma'd return line
    assert active[0].snippet == "return x + y * x"


def test_pragma_for_wrong_rule_does_not_suppress(tmp_path):
    path = tmp_path / "src" / "repro" / "core"
    path.mkdir(parents=True)
    (path / "metrics.py").write_text(
        "def f(a, b, c):\n"
        "    return a - b * c  # parity: allow(layout-index)\n")
    fs = audit_tree(str(tmp_path))
    active, suppressed = F.split_suppressed(fs, str(tmp_path))
    assert [f.rule for f in active] == ["engine-fma"]
    assert suppressed == []


def test_fingerprint_stable_across_line_shifts():
    a = F.Finding(rule="engine-fma", file="src/repro/core/metrics.py",
                  line=10, message="m", snippet="return a - b * c")
    b = F.Finding(rule="engine-fma", file="src/repro/core/metrics.py",
                  line=99, message="m", snippet="return a - b * c")
    c = F.Finding(rule="engine-fma", file="src/repro/core/metrics.py",
                  line=10, message="m", snippet="return a - b * d")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != c.fingerprint


def test_baseline_round_trip(tmp_path):
    f1 = F.Finding(rule="engine-fma", file="x.py", line=1, message="m1",
                   snippet="s1")
    f2 = F.Finding(rule="layout-index", file="y.py", line=2, message="m2",
                   snippet="s2")
    path = str(tmp_path / "baseline.json")

    # new findings fail (empty baseline)
    new, accepted, stale = F.reconcile([f1, f2], F.load_baseline(path))
    assert (len(new), len(accepted), len(stale)) == (2, 0, 0)

    # baselined findings pass
    F.write_baseline(path, [f1, f2])
    new, accepted, stale = F.reconcile([f1, f2], F.load_baseline(path))
    assert (len(new), len(accepted), len(stale)) == (0, 2, 0)

    # a fixed finding leaves a stale entry (warn, not fail)
    new, accepted, stale = F.reconcile([f1], F.load_baseline(path))
    assert (len(new), len(accepted), len(stale)) == (0, 1, 1)
    assert stale[0]["fingerprint"] == f2.fingerprint


def test_baseline_version_mismatch_raises(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError, match="version"):
        F.load_baseline(str(path))


# ------------------------------------------------------------------- CLI

def test_cli_fail_then_baseline_then_stale(tmp_path):
    from repro.analysis.__main__ import main

    write_tree(str(tmp_path), {
        "src/repro/core/metrics.py": "def f(a, b, c):\n    return a - b*c\n",
    })
    baseline = str(tmp_path / "analysis_baseline.json")
    report = str(tmp_path / "artifacts" / "ANALYSIS.json")
    argv = ["--root", str(tmp_path), "--baseline", baseline,
            "--json", report, "--passes", "ast"]

    # new finding -> exit 1, reported in the artifact
    assert main(argv) == 1
    with open(report) as fh:
        rep = json.load(fh)
    assert rep["n_unbaselined"] == 1
    assert rep["counts_by_rule"] == {"engine-fma": 1}

    # accept it -> exit 0, n_unbaselined 0
    assert main(argv + ["--write-baseline"]) == 0
    assert main(argv) == 0
    with open(report) as fh:
        assert json.load(fh)["n_unbaselined"] == 0

    # fix the code -> stale baseline entry warns but passes
    (tmp_path / "src" / "repro" / "core" / "metrics.py").write_text(
        "def f(a, b, c):\n    return a\n")
    assert main(argv) == 0
    with open(report) as fh:
        assert json.load(fh)["n_stale_baseline"] == 1


def test_cli_list_rules(capsys):
    from repro.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in F.RULES:
        assert rule in out


# ------------------------------------------------------- the real tree

def test_clean_tree_ast_audit_is_clean():
    """The checked-in sources carry zero unbaselined AST findings (every
    surviving site is pragma-suppressed with a justification)."""
    fs = audit_tree(REPO_ROOT)
    active, suppressed = F.split_suppressed(fs, REPO_ROOT)
    assert active == [], [f.render() for f in active]
    # probe-reduce: the live_pipelines bool-count i32 sum (order-independent,
    # exact in f32; see vdes._probe_stage)
    assert {f.rule for f in suppressed} <= {"engine-fma", "layout-index",
                                            "probe-reduce"}


def test_clean_tree_jaxpr_audit_is_clean():
    """Tracing the production engine calls yields zero unbaselined jaxpr
    findings — the PR 5 FMA bug class is structurally absent."""
    from repro.analysis.jaxpr_audit import run_jaxpr_audit

    fs = run_jaxpr_audit(REPO_ROOT)
    active, suppressed = F.split_suppressed(fs, REPO_ROOT)
    assert active == [], [f.render() for f in active]
    # the one surviving loop reduction is the pragma'd redeploy-gain
    # segment_sum (numpy mirrors its slot order; see vdes._fleet_stage).
    # The fleet stage runs behind its gate, a control-flow op nested in
    # the wave loop: the walk still reaches it and audits it as in-loop,
    # in the single-replica and in the batched call
    assert {f.rule for f in suppressed} <= {"loop-reduce"}
    gains = [f for f in suppressed if f.file.endswith("core/vdes.py")
             and "segment_sum" in f.snippet]
    assert len(gains) >= 2, [f.render() for f in suppressed]

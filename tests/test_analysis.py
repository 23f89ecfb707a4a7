"""Tests for the repro.analysis gate: lint rules, jaxpr analyzers,
baseline mechanics, dead-seed audit, and the CLI runner.

Two kinds of coverage, per the gate's contract:

* the healthy tree passes every check (the gate lands green with an
  EMPTY baseline), and
* every rule/check demonstrably FAILS on a seeded violation — lint
  rules via the deliberate-violation fixtures in
  ``tests/lint_fixtures/`` (excluded from the real scan), jaxpr checks
  via their injectable overrides (``ops_transform`` / ``apply_fn`` /
  ``policy_fn`` / ``session_factory``).
"""
import dataclasses
import json
import pathlib
import textwrap

import jax.numpy as jnp
import pytest

from repro import analysis, api
from repro.analysis import deadcode, jaxpr_checks, lint
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.findings import (Finding, load_baseline,
                                     split_baselined, write_baseline)
from repro.analysis.rules import ALL_RULES
from repro.analysis.rules import (r1_compat, r2_registry, r3_api,
                                  r4_loop_hygiene)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "lint_fixtures"


def _rule_hits(rule_mod, synthetic_path, source):
    """Run one rule on fixture source under a pretend repo path ->
    sorted (rule, line) pairs (waived findings come back as None and
    are dropped, same as the engine does)."""
    ctx = lint.LintContext(synthetic_path, source)
    return sorted((f.rule, f.line) for f in rule_mod.check(ctx)
                  if f is not None)


def _fixture(name):
    return (FIXTURES / name).read_text()


# --- the gate is green on the healthy tree ---------------------------


def test_lint_clean_on_repo():
    findings = lint.run_lint(REPO_ROOT)
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


def test_fixtures_are_excluded_from_the_real_scan():
    scanned = {p.as_posix() for p in lint.iter_source_files(REPO_ROOT)}
    assert not any("lint_fixtures" in p for p in scanned)
    # ... but the fixture dir itself is populated.
    assert sorted(p.name for p in FIXTURES.glob("r*_*.py")) == [
        "r1_drifted.py", "r2_handwired.py", "r3_shim.py", "r4_loop.py"]


# --- R1: drifted JAX APIs only via repro.compat ----------------------


def test_r1_fires_on_fixture():
    hits = _rule_hits(r1_compat, "src/repro/launch/somefile.py",
                      _fixture("r1_drifted.py"))
    # line 6 flags twice: the drifted module AND the drifted symbol.
    assert hits == [("R1", 6), ("R1", 6), ("R1", 7), ("R1", 13),
                    ("R1", 14), ("R1", 19), ("R1", 23), ("R1", 24)]


def test_r1_kernels_may_import_pallas_but_not_compiler_params():
    hits = _rule_hits(r1_compat, "src/repro/kernels/somefile.py",
                      _fixture("r1_drifted.py"))
    # The plain pallas import (line 7) is allowed in kernels/; the
    # drifted APIs (shard_map, make_mesh, TPUCompilerParams, axis_size)
    # still are not.
    assert ("R1", 7) not in hits
    assert [h for h in hits if h[1] in (13, 14, 19)] == [
        ("R1", 13), ("R1", 14), ("R1", 19)]


def test_r1_compat_module_is_exempt():
    assert _rule_hits(r1_compat, "src/repro/compat.py",
                      _fixture("r1_drifted.py")) == []


# --- R2: operators only via the registry -----------------------------


def test_r2_fires_on_fixture():
    hits = _rule_hits(r2_registry, "src/repro/launch/somefile.py",
                      _fixture("r2_handwired.py"))
    # 6: import of repro.kernels.ops; 7: operator imported by name;
    # 14: ops.apply_dhat_planar_any through the module alias;
    # 16: evenodd.hop_oe.  Line 18 is waived, line 10 (pack) is a
    # codec and never flagged.
    assert hits == [("R2", 6), ("R2", 7), ("R2", 14), ("R2", 16)]


def test_r2_waiver_covers_annotated_and_next_line():
    src = _fixture("r2_handwired.py")
    hits = _rule_hits(r2_registry, "src/repro/launch/somefile.py", src)
    assert ("R2", 18) not in hits
    # Removing the waiver comment resurfaces the finding (one line up,
    # since the file shrank by one line).
    lines = src.splitlines()
    del lines[16]   # the "# repro-lint: allow[R2] ..." line
    hits = _rule_hits(r2_registry, "src/repro/launch/somefile.py",
                      "\n".join(lines))
    assert ("R2", 17) in hits


def test_r2_out_of_scope_paths_are_free():
    src = _fixture("r2_handwired.py")
    for path in ("tests/test_x.py", "benchmarks/bench_x.py",
                 "src/repro/kernels/inner.py", "src/repro/core/x.py",
                 "src/repro/analysis/probe.py"):
        assert _rule_hits(r2_registry, path, src) == []


# --- R3: solve_wilson_eo must not exist ------------------------------


def test_r3_fires_on_fixture():
    hits = _rule_hits(r3_api, "tests/test_other.py",
                      _fixture("r3_shim.py"))
    # import@2, Name call@8, Attribute call@9, re-definition@12.
    assert hits == [("R3", 2), ("R3", 8), ("R3", 9), ("R3", 12)]


def test_r3_formerly_exempt_paths_now_fire():
    """PR 7 deleted the shim at its removal horizon; the old
    containment allowlist (shim home, core re-export, designated parity
    tests) is gone with it — the rule fires everywhere now."""
    src = _fixture("r3_shim.py")
    assert not hasattr(r3_api, "ALLOWED_PATHS")
    for path in ("src/repro/core/solver.py",
                 "src/repro/core/__init__.py",
                 "tests/test_api.py"):
        assert _rule_hits(r3_api, path, src) == [
            ("R3", 2), ("R3", 8), ("R3", 9), ("R3", 12)]


# --- R4: while_loop body hygiene -------------------------------------


def test_r4_fires_on_fixture():
    hits = _rule_hits(r4_loop_hygiene, "src/repro/core/solver.py",
                      _fixture("r4_loop.py"))
    # body(): from_domain@17, device_put@18, to_domain@18; the inline
    # lambda cond: device_put@29.  clean_body never flags.
    assert hits == [("R4", 17), ("R4", 18), ("R4", 18), ("R4", 29)]


def test_r4_only_looks_at_solver_py():
    assert _rule_hits(r4_loop_hygiene, "src/repro/launch/solve.py",
                      _fixture("r4_loop.py")) == []


def test_r4_clean_on_real_solver():
    solver_py = REPO_ROOT / "src" / "repro" / "core" / "solver.py"
    assert _rule_hits(r4_loop_hygiene, "src/repro/core/solver.py",
                      solver_py.read_text()) == []


# --- findings / baseline mechanics -----------------------------------


def test_finding_key_and_render():
    f = Finding(rule="R2", path="src/repro/x.py", line=7, message="m")
    assert f.key() == "R2:src/repro/x.py:7"
    assert f.render() == "src/repro/x.py:7: [R2] m"


def test_baseline_roundtrip_and_split(tmp_path):
    f1 = Finding("R1", "a.py", 1, "one")
    f2 = Finding("R2", "b.py", 2, "two")
    path = tmp_path / "baseline.json"
    write_baseline(path, [f2, f1])
    keys = load_baseline(path)
    assert keys == [f1.key(), f2.key()]

    # f1 stays grandfathered, f3 is fresh, f2's key went stale.
    f3 = Finding("R3", "c.py", 3, "three")
    fresh, old, stale = split_baselined([f1, f3], keys)
    assert fresh == [f3]
    assert old == [f1]
    assert stale == [f2.key()]


def test_baseline_accepts_bare_key_list(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(["R1:a.py:1"]))
    assert load_baseline(path) == ["R1:a.py:1"]


# --- the CLI runner --------------------------------------------------


def _mini_repo(tmp_path, bad=True):
    """A throwaway repo root with one (optionally violating) module."""
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    body = ("from jax.experimental.shard_map import shard_map\n"
            if bad else "X = 1\n")
    (pkg / "mod.py").write_text(body)
    return tmp_path


def test_runner_exits_zero_on_clean_tree(tmp_path, capsys):
    root = _mini_repo(tmp_path, bad=False)
    assert analysis_main(["--root", str(root), "--lint-only"]) == 0
    assert "findings: none" in capsys.readouterr().out


def test_runner_fails_on_fresh_violation(tmp_path, capsys):
    root = _mini_repo(tmp_path, bad=True)
    assert analysis_main(["--root", str(root), "--lint-only"]) == 1
    out = capsys.readouterr().out
    assert "[R1]" in out and "src/repro/mod.py:1" in out


def test_runner_baseline_grandfathers_and_reports_stale(
        tmp_path, capsys):
    root = _mini_repo(tmp_path, bad=True)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps(
        ["R1:src/repro/mod.py:1",          # matches both line-1 findings
         "R9:gone.py:1"]))                 # stale
    assert analysis_main(["--root", str(root), "--lint-only",
                          "--baseline", str(baseline)]) == 0
    out = capsys.readouterr().out
    assert "grandfathered" in out
    assert "stale baseline keys" in out and "R9:gone.py:1" in out


def test_runner_write_baseline_then_gate_green(tmp_path):
    root = _mini_repo(tmp_path, bad=True)
    baseline = tmp_path / "baseline.json"
    assert analysis_main(["--root", str(root), "--lint-only",
                          "--write-baseline", str(baseline)]) == 0
    assert analysis_main(["--root", str(root), "--lint-only",
                          "--baseline", str(baseline)]) == 0


def test_runner_json_artifact(tmp_path):
    root = _mini_repo(tmp_path, bad=True)
    out = tmp_path / "findings.json"
    assert analysis_main(["--root", str(root), "--lint-only",
                          "--json", str(out)]) == 1
    payload = json.loads(out.read_text())
    assert {f["rule"] for f in payload["fresh"]} == {"R1"}
    assert payload["grandfathered"] == []
    assert payload["stale_baseline_keys"] == []


def test_runner_checks_subset(tmp_path):
    root = _mini_repo(tmp_path, bad=True)
    # Only R3 selected: the R1 violation is invisible, gate passes.
    assert analysis_main(["--root", str(root), "--checks", "R3"]) == 0
    # R1 selected: fails.
    assert analysis_main(["--root", str(root), "--checks", "R1"]) == 1
    with pytest.raises(SystemExit):
        analysis_main(["--root", str(root), "--checks", "R1,NOPE"])


def test_runner_list_rules(capsys):
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("R1", "R2", "R3", "R4", "J1", "J2", "J3", "J4", "J5",
                "J6"):
        assert rid in out


def test_package_exports():
    assert analysis.Finding is Finding
    assert {r.RULE_ID for r in ALL_RULES} == {"R1", "R2", "R3", "R4"}


# --- jaxpr analyzers: pass on the healthy tree -----------------------


ROOT = str(REPO_ROOT)


def test_j1_conversion_free_every_backend():
    # The full registry — the invariant is per-backend, so run them all
    # (this is the expensive one: one trace per backend).
    findings = jaxpr_checks.check_conversion_free(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_j2_pallas_counts_healthy():
    findings = jaxpr_checks.check_pallas_counts(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_j3_vmem_model_healthy():
    findings = jaxpr_checks.check_vmem_model(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_j4_retrace_budget_healthy():
    findings = jaxpr_checks.check_retrace_budget(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_j5_overlap_interleave_healthy():
    findings = jaxpr_checks.check_overlap_interleave(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_j6_nonfinite_guard_healthy():
    findings = jaxpr_checks.check_nonfinite_guard(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


# --- jaxpr analyzers: fail on seeded violations ----------------------


def test_j1_catches_precision_roundtrip():
    # Seed the violation J1 exists for: an operator wrapper that
    # round-trips the iterate through a narrower precision each
    # application (downcast + upcast; the downcast is not exempt).
    # Scoped x64 so the complex128 leg of the round-trip is real.
    from repro.compat import enable_x64

    def sabotage(bops):
        inner = bops.apply_dhat_native

        def lossy(v, kappa):
            w = inner(v, kappa)
            other = (jnp.complex64 if w.dtype == jnp.complex128
                     else jnp.complex128)
            return w.astype(other).astype(w.dtype)

        return dataclasses.replace(bops, apply_dhat_native=lossy)

    with enable_x64():
        findings = jaxpr_checks.check_conversion_free(
            ROOT, backends=["jnp"], ops_transform=sabotage)
    assert len(findings) == 1
    assert findings[0].rule == "J1"
    assert "convert_element_type" in findings[0].message
    assert findings[0].path == "src/repro/core/solver.py"
    assert findings[0].line > 1   # anchored at make_native_solve


def test_j2_catches_double_launch():
    from repro.kernels import ops as kops

    def double(u_e_p, u_o_p, src_p, kappa, fused):
        a = kops.apply_dhat_planar_any(
            u_e_p, u_o_p, src_p, kappa, fused=fused, interpret=True)
        b = kops.apply_dhat_planar_any(
            u_e_p, u_o_p, src_p, kappa, fused=fused, interpret=True)
        return a + b

    findings = jaxpr_checks.check_pallas_counts(
        ROOT, apply_fn=double, expected={"resident": 1},
        compressions=("none",))
    assert [f.rule for f in findings] == ["J2"]
    assert "expected exactly 1" in findings[0].message


def test_j2_catches_wrong_expectation():
    # Equivalent seeding from the other side: the healthy kernel vs a
    # wrong declared count — it must fire on every compression axis.
    findings = jaxpr_checks.check_pallas_counts(
        ROOT, expected={"unfused": 1})
    assert [f.rule for f in findings] == ["J2"] * 3
    assert {c for c in ("'none'", "'two_row'", "'minimal'")
            if any(c in f.message for f in findings)} \
        == {"'none'", "'two_row'", "'minimal'"}


def test_j3_catches_lying_policy():
    findings = jaxpr_checks.check_vmem_model(
        ROOT, policy_fn=lambda shape, dtype=jnp.float32: "stream")
    assert findings and all(f.rule == "J3" for f in findings)
    assert any("fused_dhat_policy" in f.message for f in findings)


def test_j3_catches_wrong_ring_model():
    from repro.kernels import wilson_stencil as ws

    def bloated_ring(shape, dtype=jnp.float32, window=None):
        return 2 * ws.stream_ring_bytes(shape, dtype)

    findings = jaxpr_checks.check_vmem_model(ROOT, ring_fn=bloated_ring)
    assert findings and all(f.rule == "J3" for f in findings)
    assert any("stream_ring_bytes" in f.message for f in findings)


def test_j3_catches_wrong_limit():
    # Shrinking the declared budget makes fits/policy disagree with the
    # real estimators at the boundary cases.
    findings = jaxpr_checks.check_vmem_model(
        ROOT, limit_bytes=1 << 20)
    assert findings and all(f.rule == "J3" for f in findings)


def test_j4_catches_cache_defeat():
    Ue, Uo, e, o = jaxpr_checks._tiny_eo()

    def leaky_factory():
        D = api.WilsonMatrix.bind(Ue, Uo, jaxpr_checks._KAPPA,
                                  backend="jnp")
        session = api.SolveSession(D, api.SolveSpec(
            method="cgnr", tol=1e-5, max_iters=25))
        inner = session.solve

        def solve(ee, oo, spec=None):
            session._cache.clear()   # the retrace leak J4 exists for
            return inner(ee, oo, spec)

        session.solve = solve
        return session

    findings = jaxpr_checks.check_retrace_budget(
        ROOT, session_factory=leaky_factory)
    rules = {f.rule for f in findings}
    assert rules == {"J4"}
    assert any("traces" in f.message for f in findings)


def test_j5_catches_serialized_schedule():
    # The fused schedule is the built-in violation: each of its kernels
    # consumes every face exchanged before it (0 faces left in flight),
    # so the per-kernel overlap requirement fails for both hops.
    findings = jaxpr_checks.check_overlap_interleave(ROOT, overlap="fused")
    rules = [f.rule for f in findings]
    assert rules and set(rules) == {"J5"}
    assert any("serialized behind the halo exchange" in f.message
               for f in findings)


def test_j6_catches_guardless_solver():
    # Seed the violation J6 exists for: the same Krylov trace with the
    # divergence guard compiled out (guard=False) — every method, both
    # pipelines, must be flagged as structurally unguarded.
    import jax
    from repro.core import solver

    n = 24
    key = jax.random.PRNGKey(0)
    G = jax.random.normal(key, (n, n), dtype=jnp.float32)
    A = G @ G.T + n * jnp.eye(n, dtype=jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n,),
                          dtype=jnp.float32)

    def unguarded(method, batched):
        rhs = jnp.stack([b, b]) if batched else b
        op = (lambda v: v @ A.T) if batched else (lambda v: A @ v)
        return solver._run_krylov(
            method, op, op, rhs, tol=1e-6, max_iters=8,
            recompute_every=0, batched=batched, guard=False)

    findings = jaxpr_checks.check_nonfinite_guard(ROOT, run_fn=unguarded)
    assert len(findings) == 2 * len(solver.KRYLOV_METHODS)
    assert {f.rule for f in findings} == {"J6"}
    assert all("is_finite" in f.message for f in findings)
    assert findings[0].path == "src/repro/core/solver.py"
    assert findings[0].line > 1   # anchored at _run_krylov


def test_run_jaxpr_checks_validates_ids():
    with pytest.raises(ValueError, match="unknown jaxpr check"):
        jaxpr_checks.run_jaxpr_checks(ROOT, checks=["J9"])


# --- dead-seed audit -------------------------------------------------


def test_dead_code_report_shape():
    report = deadcode.dead_code_report(ROOT)
    assert report["modules_live"] <= report["modules_total"]
    dormant = {d["module"]: d for d in report["dormant"]}
    # The product surface is live ...
    assert "repro.api" not in dormant
    assert "repro.core.solver" not in dormant
    assert "repro.analysis.jaxpr_checks" not in dormant
    # ... the annotated harvest targets are dormant-on-purpose ...
    assert dormant["repro.launch.train"]["intentional"]
    assert "ROADMAP item 5" in dormant["repro.launch.train"]["note"]
    # ... and nothing dormant is unaccounted for: every non-intentional
    # entry is part of the generic LLM seed scaffold.
    for d in report["dormant"]:
        if not d["intentional"]:
            assert d["module"].startswith(("repro.configs.",
                                           "repro.models.",
                                           "repro.optim.")), d


def test_dead_code_sees_function_local_imports(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "api.py").write_text(textwrap.dedent("""
        def f():
            from repro import helper
            return helper
    """))
    (pkg / "helper.py").write_text("X = 1\n")
    (pkg / "orphan.py").write_text("Y = 2\n")
    report = deadcode.dead_code_report(str(tmp_path))
    names = {d["module"] for d in report["dormant"]}
    assert "repro.helper" not in names
    assert "repro.orphan" in names


def test_format_dead_code_report_only():
    report = deadcode.dead_code_report(ROOT)
    text = deadcode.format_dead_code(report)
    assert "report-only" in text

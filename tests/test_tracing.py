"""The in-program recorder (``repro.tracing``) and the spans that
``SolveSession`` opens around each phase of a solve."""
import jax
import pytest

from repro import api, compat, tracing
from repro.launch import problem as problem_mod

CHILDREN = ["repro.solve.encode", "repro.solve.h2d", "repro.solve.exec",
            "repro.solve.d2h", "repro.solve.decode"]


@pytest.fixture
def clean_recorder():
    tracing.reset()
    yield
    tracing.reset()


def test_untraced_solve_records_and_fences_nothing(clean_recorder,
                                                   monkeypatch):
    """(a) With no profiler trace active a solve stores no record and
    waits once, for its result, as it did before spans; ``stats()``
    still times it."""
    assert not compat.profiler_active()
    prob = problem_mod.HostProblem((4, 4, 4, 8), 0.13, seed=5)
    _, ee, eo = prob.source(0)
    session = api.SolveSession(prob.bind(api.BackendSpec("jnp")),
                               api.SolveSpec(method="cgnr", tol=1e-5))
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    session.solve(ee, eo)
    assert tracing.snapshot() == []
    assert len(calls) == 1
    (row,) = session.stats()["keys"].values()
    assert row["solves"] == 1 and row["first_solve_s"] > 0


_TRACED_SOLVE_BODY = """
import sys, tempfile, glob
import jax
from jax.profiler import ProfileData
from repro import api, tracing
from repro.launch import problem

devs = jax.devices()
host, chip = devs[1], devs[0]
problem.host_device = lambda: host          # 'host' = device 1
prob = problem.HostProblem((4, 4, 4, 8), 0.13, seed=3)
eta, ee, eo = prob.source(0, nrhs=2)
m = prob.bind(api.BackendSpec("jnp"))
assert m.devices() == {chip} and ee.sharding.device_set == {host}
session = api.SolveSession(m, api.SolveSpec(method="cgnr", tol=1e-5))
session.solve(ee, eo)                       # compiles, untraced
assert tracing.snapshot() == []
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
session.solve(ee, eo)
jax.profiler.stop_trace()
names = set()
for plane in ProfileData.from_file(
        glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]).planes:
    if plane.name == "/host:CPU":
        names |= {ev.name for line in plane.lines for ev in line.events}
print("RECORDS", repr(tracing.snapshot()))
print("XPLANE", repr(sorted(n for n in names if n.startswith("repro."))))
(row,) = session.stats()["keys"].values()
print("STEADY", repr(row["steady_state_s"]))
"""


def _printed(out, tag):
    import ast
    (line,) = [ln for ln in out.splitlines() if ln.startswith(tag + " ")]
    return ast.literal_eval(line[len(tag) + 1:])


@pytest.fixture(scope="module")
def traced_solve():
    """One two-column solve inside a profiler trace, in a subprocess
    with two host devices: the sources on one, the operator on the
    other."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", ""))
    out = subprocess.run([sys.executable, "-c", _TRACED_SOLVE_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return {tag: _printed(out.stdout, tag)
            for tag in ("RECORDS", "XPLANE", "STEADY")}


def test_traced_solve_records_five_phases_in_order(traced_solve):
    """(b) A host-sourced solve records encode, h2d, exec, d2h and
    decode, in order and apart, inside its ``repro.solve``."""
    records = traced_solve["RECORDS"]
    (root,) = [r for r in records if r["name"] == "repro.solve"]
    assert root["parent"] is None and root["attrs"] == {"columns": 2}
    kids = sorted((r for r in records if r is not root),
                  key=lambda r: r["start_ns"])
    assert [r["name"] for r in kids] == CHILDREN
    assert all(r["parent"] == "repro.solve" and r["solve"] == root["solve"]
               for r in kids)
    assert root["start_ns"] <= kids[0]["start_ns"]
    assert kids[-1]["end_ns"] <= root["end_ns"]
    for a, b in zip(kids, kids[1:]):
        assert a["end_ns"] <= b["start_ns"]
    assert (sum(r["end_ns"] - r["start_ns"] for r in kids)
            <= root["end_ns"] - root["start_ns"])


def test_traced_solve_counts_bytes_moved(traced_solve):
    """(b) ``h2d`` moves both parities of every column, 24 reals of 4
    bytes per site of the half lattice; ``d2h`` moves them back."""
    by_name = {r["name"]: r for r in traced_solve["RECORDS"]}
    half_sites = 4 * 4 * 4 * 8 // 2
    moved = 2 * 2 * half_sites * 24 * 4
    assert by_name["repro.solve.h2d"]["attrs"] == {"bytes": moved}
    assert by_name["repro.solve.d2h"]["attrs"] == {"bytes": moved}


def test_traced_solve_spans_reach_the_profile(traced_solve):
    """(b) The written profile holds the same spans on ``/host:CPU``."""
    assert traced_solve["XPLANE"] == sorted(["repro.solve"] + CHILDREN)


def test_stats_time_is_the_solve_span(traced_solve):
    """``stats()`` reads the ``repro.solve`` span's own clock."""
    (root,) = [r for r in traced_solve["RECORDS"]
               if r["name"] == "repro.solve"]
    assert traced_solve["STEADY"] == (
        (root["end_ns"] - root["start_ns"]) * 1e-9)


def test_profiler_flag_follows_the_trace(tmp_path):
    """(c) The flag is off, on inside a trace, and off after it."""
    assert not compat.profiler_active()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert compat.profiler_active()
    finally:
        jax.profiler.stop_trace()
    assert not compat.profiler_active()


def test_a_solve_is_recorded_whole_or_not(clean_recorder, tmp_path):
    """The decision is the root span's, made at its start."""
    with tracing.span("root"):
        jax.profiler.start_trace(str(tmp_path / "a"))
        try:
            with tracing.span("child"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert tracing.snapshot() == []

    jax.profiler.start_trace(str(tmp_path / "b"))
    try:
        with tracing.span("root", columns=3) as root:
            jax.profiler.stop_trace()
            with tracing.span("child"):
                pass
    finally:
        if compat.profiler_active():
            jax.profiler.stop_trace()
    names = [(r["name"], r["parent"]) for r in tracing.snapshot()]
    assert names == [("child", "root"), ("root", None)]
    assert root.seconds >= 0


def test_snapshot_holds_the_newest_session(clean_recorder, tmp_path):
    for i, name in enumerate(["first", "second"]):
        jax.profiler.start_trace(str(tmp_path / str(i)))
        try:
            with tracing.span(name):
                pass
        finally:
            jax.profiler.stop_trace()
        with tracing.span("untraced"):
            pass
    assert [r["name"] for r in tracing.snapshot()] == ["second"]

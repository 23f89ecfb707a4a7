"""The readers of the program's solve spans, on hand-built snapshots of
``repro.tracing`` and on one solve recorded on the CPU."""
import pytest

from chipbench.bench import Bench

SECONDS = {"api.encode_s": "repro.solve.encode",
           "api.h2d_s": "repro.solve.h2d",
           "krylov.exec_s": "repro.solve.exec",
           "api.d2h_s": "repro.solve.d2h",
           "api.decode_s": "repro.solve.decode"}
BYTES = "api.h2d_bytes_per_rhs"
READERS = list(SECONDS) + [BYTES]


def _solve(i, t0, lengths, columns, moved):
    """Records of solve ``i`` starting at ``t0`` ns: one child per
    phase of ``lengths`` (name -> ns), back to back."""
    out, t = [], t0
    for name, ns in lengths.items():
        attrs = {"bytes": moved} if name.endswith(("h2d", "d2h")) else {}
        out.append({"name": name, "solve": i, "parent": "repro.solve",
                    "start_ns": t, "end_ns": t + ns, "attrs": attrs})
        t += ns
    out.append({"name": "repro.solve", "solve": i, "parent": None,
                "start_ns": t0, "end_ns": t, "attrs": {"columns": columns}})
    return out


def _host_sourced(scale):
    return {"repro.solve.encode": 300_000_000 * scale,
            "repro.solve.h2d": 1_100_000_000 * scale,
            "repro.solve.exec": 570_000_000 * scale,
            "repro.solve.d2h": 200_000_000 * scale,
            "repro.solve.decode": 400_000_000 * scale}


# Three 12-column solves; the second takes twice as long in every phase.
HOST = [r for i, scale in enumerate((1, 2, 1))
        for r in _solve(i + 1, i * 10**10, _host_sourced(scale), 12,
                        301_989_888)]
# Two solves whose sources sit on the operator's device: no transfers.
ON_DEVICE = [r for i in range(2)
             for r in _solve(i + 1, i * 10**10,
                             {"repro.solve.encode": 1000,
                              "repro.solve.exec": 5000,
                              "repro.solve.decode": 2000}, 1, 0)]


@pytest.fixture(scope="module")
def bench():
    return Bench()


def _read(bench, monkeypatch, name, records):
    from repro import tracing
    monkeypatch.setattr(tracing, "snapshot", lambda: list(records))
    return bench.reader(name)({})


@pytest.mark.parametrize("name", list(SECONDS))
def test_seconds_are_the_median_solve(bench, monkeypatch, name):
    expect = _host_sourced(1)[SECONDS[name]] * 1e-9
    assert _read(bench, monkeypatch, name, HOST) == pytest.approx(expect)


def test_bytes_per_column(bench, monkeypatch):
    assert _read(bench, monkeypatch, BYTES, HOST) == 25_165_824


@pytest.mark.parametrize("name", READERS)
def test_nothing_recorded_reads_none(bench, monkeypatch, name):
    assert _read(bench, monkeypatch, name, []) is None


@pytest.mark.parametrize("name", READERS)
def test_sources_on_the_device(bench, monkeypatch, name):
    """No transfer span: the transfer times read None, the bytes 0."""
    value = _read(bench, monkeypatch, name, ON_DEVICE)
    if name in ("api.h2d_s", "api.d2h_s"):
        assert value is None
    elif name == BYTES:
        assert value == 0
    else:
        assert value > 0


@pytest.mark.parametrize("name", READERS)
def test_program_without_recorder_reads_none(bench, monkeypatch, name):
    """A program that has no ``repro.tracing`` gives no value and no
    error."""
    import sys
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr("repro.tracing", raising=False)
    assert bench.reader(name)({}) is None


def test_readers_on_a_recorded_solve(bench, tmp_path):
    """One single-column solve traced on the CPU, with the sources on
    the operator's device: every phase but the transfers is read."""
    import jax

    from repro import api, tracing
    from repro.launch.problem import HostProblem

    prob = HostProblem((4, 4, 4, 8), 0.13, seed=7)
    _, ee, eo = prob.source(0)
    session = api.SolveSession(prob.bind(api.BackendSpec("jnp")),
                               api.SolveSpec(method="cgnr", tol=1e-5))
    session.solve(ee, eo)
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        session.solve(ee, eo)
    finally:
        jax.profiler.stop_trace()
    values = {name: bench.reader(name)({}) for name in READERS}
    assert values["api.h2d_s"] is None and values["api.d2h_s"] is None
    assert values[BYTES] == 0
    for name in ("api.encode_s", "krylov.exec_s", "api.decode_s"):
        assert values[name] > 0
    tracing.reset()

"""Runs with the timed path broken underneath must come out not correct.

Each fault is planted in the program under a tiny cell on the CPU (the
harness's look for a chip is skipped, the rest of a run is the same):

* ``altered``: an answer altered where it is produced (every returned
  solution scaled by 1 + 1e-3);
* ``half``: half of a batch left out (the second half of the columns of
  every batched solve returned as zeros).

The other faults of the benchmark's rules (a step that returns its state
unchanged, the exchange between chips left out) belong to training and to
cells on several chips, which this benchmark has not.
"""
import pytest

from chipbench import run as bench_run


def _altered(xe, xo):
    return xe * (1 + 1e-3), xo


def _half(xe, xo):
    if xe.ndim == 7:
        n = xe.shape[0]
        xe, xo = xe.at[n // 2:].set(0), xo.at[n // 2:].set(0)
    return xe, xo


FAULTS = {"altered": _altered, "half": _half}


@pytest.mark.parametrize("cell,fault", [
    ("tinyprop.tiny", "altered"),
    ("tinyprop.tiny", "half"),
    ("tinysolve.tiny", "altered"),
])
def test_fault_is_not_correct(tiny_bench, monkeypatch, cell, fault):
    from repro.api import SolveSession
    solve = SolveSession.solve

    def broken(self, eta_e, eta_o, spec=None):
        xe, xo, res = solve(self, eta_e, eta_o, spec)
        xe, xo = FAULTS[fault](xe, xo)
        return xe, xo, res

    monkeypatch.setattr(SolveSession, "solve", broken)
    out = bench_run.execute(tiny_bench, cell, seed=77, seconds=1.0)
    assert not out["correct"]
    worst = out["checks"]["worst_true_residual"]
    assert worst["value"] > worst["limit"]


@pytest.mark.parametrize("cell", ["tinyprop.tiny", "tinysolve.tiny"])
def test_lower_precision_control_is_not_correct(tiny_bench, cell):
    """The control: the program's own bf16 path in place of the f32 one
    the configuration states."""
    out = bench_run.execute(tiny_bench, cell, seed=78, seconds=1.0,
                            config_overrides={"dtype": "bf16"})
    assert not out["correct"]
    worst = out["checks"]["worst_true_residual"]
    assert worst["value"] > worst["limit"]

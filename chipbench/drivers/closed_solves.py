"""Closed loop: back-to-back ``SolveSession.solve`` calls, one in flight.

Traffic keys: ``source`` (``point`` or ``gaussian``), ``columns`` per
solve, ``pool`` (distinct sources staged on the host before the window
and used in turn; deflation is off, so their order does not matter) and
``check_solves`` (how many of the window's solves, drawn from the seed,
are checked against the reference).  Each checked column's true residual
is held to the configuration's ``true_residual_limit``.

``rhs_per_s`` counts the columns solved over the time
from the first solve's start to the last one's end; the last solve
started inside ``--seconds`` is allowed to finish.  A column counts when
the program reports it converged and, where it is checked, its true
residual meets the limit; every other column counts in ``failed``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import sources


def _stage(run, i):
    import jax
    t = run.traffic
    eta_e, eta_o = sources.make(t["source"], run.seed, i,
                                run.config["extents"], int(t["columns"]))
    cpu = jax.devices("cpu")[0]
    return (eta_e, eta_o), jax.device_put((eta_e, eta_o), cpu)


def run(run):
    from repro import api

    from chipbench.problem import Problem

    t = run.traffic
    columns = int(t["columns"])
    limit = float(run.config["true_residual_limit"])
    prob = Problem(run.config, run.seed)
    session = api.SolveSession(prob.matrix, prob.solve_spec)
    staged = [_stage(run, i) for i in range(int(t["pool"]))]
    with run.span("warmup"):
        session.solve(*staged[0][1])
    warm_solves = session.stats()["solves"]

    # Reservoir sample of the window's solves, drawn from the seed.
    pick = sources.rng(run.seed, sources.SAMPLE)
    keep = int(t["check_solves"])
    sample = []          # (solve index, xi_e, xi_o)
    solves = []          # (start, end, converged per column)
    error = None
    start = run.start_window()
    i = 0
    while time.perf_counter() - start < run.seconds:
        host = staged[i % len(staged)][1]
        t_a = time.perf_counter()
        try:
            with run.span("solve"):
                xi_e, xi_o, res = session.solve(*host)
        except Exception as exc:  # noqa: BLE001 — a failed solve is reported
            error = repr(exc)
            break
        t_b = time.perf_counter()
        solves.append((t_a, t_b, np.asarray(res.converged).reshape(-1)))
        if len(sample) < keep:
            sample.append((i, xi_e, xi_o))
        else:
            j = int(pick.integers(i + 1))
            if j < keep:
                sample[j] = (i, xi_e, xi_o)
        del xi_e, xi_o, res
        i += 1
    run.end_window()

    stats = session.stats()
    iterations = [it for key in stats["keys"].values()
                  for it in key["iterations"]][warm_solves:]
    degraded = bool(stats["degraded"] or stats["fallbacks"])
    del session, prob.matrix
    gc.collect()

    # Reference checks, after the window, on the host.
    t_check = time.perf_counter()
    ref = prob.reference()
    worst, bad_cols = 0.0, {}
    with run.span("check"):
        for k, xi_e, xi_o in sample:
            eta_e, eta_o = staged[k % len(staged)][0]
            r = ref.true_residuals(eta_e, eta_o, xi_e, xi_o)
            worst = max(worst, float(r.max()))
            bad_cols[k] = r > limit
    check_s = time.perf_counter() - t_check
    ok_cols = 0
    for k, (_, _, conv) in enumerate(solves):
        good = conv.copy()
        if k in bad_cols:
            good &= ~bad_cols[k]
        ok_cols += int(good.sum())
    attempted = columns * len(solves)
    unconverged = int(sum((~c).sum() for _, _, c in solves))
    span = solves[-1][1] - solves[0][0] if solves else float("nan")
    checks = {
        "worst_true_residual": (worst, limit,
                                bool(sample) and worst <= limit),
        "unconverged_columns": (unconverged, 0, unconverged == 0),
        "degraded_or_fallback": (int(degraded), 0, not degraded),
        "solve_errors": (int(error is not None), 0, error is None),
    }
    notes = [f"window: {len(solves)} solves of {columns} columns in "
             f"{span:.6f} s; {len(sample)} solves checked in "
             f"{check_s:.6f} s; mean iterations "
             f"{np.mean(iterations) if iterations else float('nan'):.6f}",
             f"set-up {run.setup_s:.6f} s; compile clock at window "
             f"start {run.compile_setup}"]
    if error:
        notes.append(f"solve failed: {error}")
    return {
        "attempted": attempted,
        "failed": attempted - ok_cols,
        "end_to_end": {"rhs_per_s": ok_cols / span if solves else 0.0},
        "layers": {"iterations": iterations, "columns": columns},
        "checks": checks,
        "notes": notes,
    }

"""krylov.exec_s: median seconds per solve of the program's
``repro.solve.exec`` span (the cached solve executable, dispatch to
outputs ready), over the window's solves, from
``repro.tracing.snapshot()``. None where the program records no such
span (a program without ``repro.tracing``, or no traced solve). Moves
``rhs_per_s``."""
import statistics

SPAN = "repro.solve.exec"


def from_records(records):
    per_solve = {}
    for r in records:
        if r["name"] == SPAN:
            s = (r["end_ns"] - r["start_ns"]) * 1e-9
            per_solve[r["solve"]] = per_solve.get(r["solve"], 0.0) + s
    return statistics.median(per_solve.values()) if per_solve else None


def read(rec):
    try:
        from repro import tracing
    except ImportError:
        return None
    return from_records(tracing.snapshot())

"""api.h2d_bytes_per_rhs: bytes moved to the operator's device per
column solved, over the window: the ``bytes`` of every
``repro.solve.h2d`` span over the ``columns`` of every ``repro.solve``
span, from ``repro.tracing.snapshot()``.  0 where the sources were
already on the operator's device; None where the program records no
solve (a program without ``repro.tracing``, or no traced solve).
Moves ``rhs_per_s``."""


def from_records(records):
    moved = sum(r["attrs"]["bytes"] for r in records
                if r["name"] == "repro.solve.h2d")
    columns = sum(r["attrs"]["columns"] for r in records
                  if r["name"] == "repro.solve")
    return moved / columns if columns else None


def read(rec):
    try:
        from repro import tracing
    except ImportError:
        return None
    return from_records(tracing.snapshot())

"""device_idle.solve: share of the traced window in which no operation
ran on the chip, 100 * (1 - busy / window), in the closed-loop solve
cells.  Moves ``rhs_per_s``."""


def read(rec):
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None

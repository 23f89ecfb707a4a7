"""krylov_iters: mean Krylov iterations per solve in the window, as
``SolveSession.stats()`` counts them (a block solve counts its block's
iterations once).  Moves ``rhs_per_s``."""


def read(rec):
    its = rec["layers"].get("iterations")
    return sum(its) / len(its) if its else None

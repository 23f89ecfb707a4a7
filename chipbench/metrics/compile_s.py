"""compile_s: seconds JAX spent tracing, lowering and compiling (or
loading from the persistent cache) during set-up, from ``jax.monitoring``
(``chipbench/compile_clock.py``).  Moves ``setup_s``."""


def read(rec):
    s = rec["compile"]["seconds"]
    return s["trace"] + s["lower"] + s["compile"]

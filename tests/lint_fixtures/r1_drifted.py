"""Deliberate R1 violations (linter test fixture — never imported).

This directory is excluded from the real gate (SKIP_DIR_NAMES); the
tests feed these sources to the rules directly, with synthetic paths.
"""
from jax.experimental.shard_map import shard_map          # line 6: R1
from jax.experimental import pallas as pl                 # line 7: R1 (outside kernels/)

import jax


def build(mesh):
    mesh2 = jax.make_mesh((2,), ("x",))                   # line 13: R1
    params = pl.tpu.TPUCompilerParams()                   # line 14: R1
    return shard_map, mesh, mesh2, params


def sizes():
    return jax.lax.axis_size("x")                         # line 19: R1


def x64():
    from jax.experimental import enable_x64               # line 23: R1
    return enable_x64(), jax.enable_x64(True)             # line 24: R1

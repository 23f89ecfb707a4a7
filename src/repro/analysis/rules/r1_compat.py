"""R1 — version-drifted JAX APIs go through ``repro.compat`` only.

``shard_map`` (and its ``check_rep``/``check_vma`` kwarg),
``jax.make_mesh`` / ``jax.sharding.AxisType``, ``jax.lax.axis_size``,
the Pallas TPU compiler-params class and the scoped x64 context
(``jax.experimental.enable_x64``, now ``jax.enable_x64``) all moved
between JAX releases.  Touching any of them directly re-introduces the
breakage the compat layer exists to contain.  ``src/repro/compat.py`` is the one place allowed to; the
Pallas kernels under ``src/repro/kernels/`` may additionally import
``jax.experimental.pallas`` (plain ``pallas as pl`` / ``tpu as
pltpu``) — but even they must take compiler params via
``compat.tpu_compiler_params``.
"""
from __future__ import annotations

import ast
from typing import Iterable

RULE_ID = "R1"
DESCRIPTION = ("version-drifted JAX APIs (shard_map, make_mesh/AxisType, "
               "axis_size, Pallas compiler params, enable_x64) only via "
               "repro.compat")

# The one module allowed to touch everything below.
COMPAT_PATH = "src/repro/compat.py"
# Package additionally allowed to import jax.experimental.pallas.
KERNELS_PREFIX = "src/repro/kernels/"

# Fully-resolved dotted paths that drifted across the supported range.
DRIFTED_PATHS = frozenset({
    "jax.shard_map",
    "jax.experimental.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.make_mesh",
    "jax.sharding.AxisType",
    "jax.lax.axis_size",
    "jax.core.axis_frame",
    "jax.experimental.pallas.tpu.TPUCompilerParams",
    "jax.experimental.pallas.tpu.CompilerParams",
    "jax.experimental.enable_x64",
    "jax.enable_x64",
})

# Module prefixes whose *import* is restricted to kernels/ (+ compat).
PALLAS_PREFIX = "jax.experimental.pallas"


def _imported_modules(node):
    if isinstance(node, ast.Import):
        for a in node.names:
            yield a.name
    elif isinstance(node, ast.ImportFrom) and node.level == 0:
        mod = node.module or ""
        yield mod
        for a in node.names:
            if a.name != "*":
                yield f"{mod}.{a.name}"


def check(ctx) -> Iterable:
    if ctx.path == COMPAT_PATH:
        return
    in_kernels = ctx.path.startswith(KERNELS_PREFIX)

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for mod in _imported_modules(node):
                if mod in DRIFTED_PATHS:
                    yield ctx.finding(
                        RULE_ID, node,
                        f"direct import of drifted API {mod!r}: use the "
                        "repro.compat wrapper instead")
                elif (mod == PALLAS_PREFIX
                      or mod.startswith(PALLAS_PREFIX + ".")):
                    if not in_kernels:
                        yield ctx.finding(
                            RULE_ID, node,
                            f"import of {mod!r} outside src/repro/kernels/"
                            ": Pallas entry points live in the kernels "
                            "package; compiler params via "
                            "repro.compat.tpu_compiler_params")
        elif isinstance(node, ast.Attribute):
            resolved = ctx.resolve(node)
            if resolved in DRIFTED_PATHS:
                yield ctx.finding(
                    RULE_ID, node,
                    f"direct use of drifted API {resolved!r}: use the "
                    "repro.compat wrapper instead")

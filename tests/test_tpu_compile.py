"""Compile the Wilson kernels for a described TPU v5e, at real sizes.

Nothing here runs: the TPU compiler builds each kernel for one chip of a
described ``v5e:2x2`` topology, which refuses what interpret mode cannot
see — blocks not aligned to the (8, 128) tiling, more VMEM than a kernel
may use.  The cases cover the paper's Table-1 volumes 16^4 and 32^3x64 in
f32: both hopping blocks with and without the fused axpy, the unfused
Dhat, and whatever ``fused_dhat_policy`` picks, at nrhs 1 and 12; plus
the small shapes where the policy does pick a fused kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import wilson_stencil as ws

TABLE1 = {"16x16x16x16": (16, 16, 16, 16), "32x32x32x64": (32, 32, 32, 64)}
KAPPA = 0.13


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip can be written to the persistent
    # cache but not read back; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, extents, nrhs=1, dtype=jnp.float32):
    T, Z, Y, X = extents
    Xh = X // 2
    u = jax.ShapeDtypeStruct((4, T, Z, 18, Y, Xh), dtype, sharding=one_chip)
    lead = (nrhs,) if nrhs > 1 else ()
    v = jax.ShapeDtypeStruct(lead + (T, Z, 24, Y, Xh), dtype,
                             sharding=one_chip)
    return u, v


def _compile(fn, *args):
    """Compiled executable's HLO text; raises what the compiler raises."""
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("axpy", [False, True], ids=["plain", "axpy"])
@pytest.mark.parametrize("parity", [0, 1], ids=["eo", "oe"])
@pytest.mark.parametrize("lattice", sorted(TABLE1))
def test_hop_block_compiles(one_chip, lattice, parity, axpy):
    u, v = _shapes(one_chip, TABLE1[lattice])

    def hop(u, v):
        return ws.hop_block_planar(u, u, v, parity, interpret=False,
                                   axpy=(0.5, v) if axpy else None)

    assert _compile(hop, u, v).count("tpu_custom_call") == 1


@pytest.mark.parametrize("lattice", sorted(TABLE1))
def test_unfused_dhat_compiles(one_chip, lattice):
    u, v = _shapes(one_chip, TABLE1[lattice])

    def dhat(u, v):
        return ops.apply_dhat_planar_any(u, u, v, KAPPA, fused="unfused",
                                         interpret=False)

    assert _compile(dhat, u, v).count("tpu_custom_call") == 2


# (extents, nrhs, dtype, variant the policy picks there)
POLICY_CASES = {
    "16x16x16x16-nrhs1": (TABLE1["16x16x16x16"], 1, jnp.float32, "unfused"),
    "16x16x16x16-nrhs12": (TABLE1["16x16x16x16"], 12, jnp.float32,
                           "unfused"),
    "32x32x32x64-nrhs1": (TABLE1["32x32x32x64"], 1, jnp.float32, "unfused"),
    "32x32x32x64-nrhs12": (TABLE1["32x32x32x64"], 12, jnp.float32,
                           "unfused"),
    "8x8x8x8-nrhs1": ((8, 8, 8, 8), 1, jnp.float32, "resident"),
    "8x8x8x8-nrhs2": ((8, 8, 8, 8), 2, jnp.float32, "stream"),
    "16x16x16x16-bf16": (TABLE1["16x16x16x16"], 1, jnp.bfloat16, "stream"),
}


@pytest.mark.parametrize("case", sorted(POLICY_CASES))
def test_policy_choice_compiles(one_chip, case):
    """Whatever the VMEM policy picks, the TPU compiler accepts."""
    extents, nrhs, dtype, variant = POLICY_CASES[case]
    u, v = _shapes(one_chip, extents, nrhs, dtype)
    assert ws.fused_dhat_policy(v.shape, dtype) == variant

    def dhat(u, v):
        return ops.apply_dhat_planar_any(u, u, v, KAPPA, interpret=False)

    kernels = 2 if variant == "unfused" else 1
    assert _compile(dhat, u, v).count("tpu_custom_call") == kernels

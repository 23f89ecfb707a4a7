"""Cross-backend parity: every registered backend x dtype x nrhs on an
odd lattice, and the distributed ``overlap="interior"`` schedule, against
the ``jnp`` reference (see ``test_parity_matrix.py`` for the whole
matrix)."""
import pytest
from parity_harness import (DTYPES, NRHS, ODD_LATTICE, all_backends,
                            check_parity, x64_ctx)


@pytest.mark.parametrize("nrhs", NRHS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", all_backends())
def test_backend_parity_odd_lattice(name, dtype, nrhs):
    with x64_ctx(dtype):
        check_parity(name, ODD_LATTICE, dtype, nrhs)


# --- distributed comms/compute overlap -------------------------------


@pytest.mark.parametrize("dtype,nrhs", [("f32", 1), ("f32", 4),
                                        ("f64", 1)])
def test_distributed_interior_overlap_parity(dtype, nrhs):
    """The interior/boundary split schedule is numerically identical to
    the fused schedule (ODD_LATTICE has Tl=3: a one-plane-thick interior
    — the thinnest legal overlap region).  The f64 leg runs nrhs=1 only:
    the x64 compile of the split schedule dominates the suite and the
    batched path is already covered at f32."""
    with x64_ctx(dtype):
        check_parity("distributed", ODD_LATTICE, dtype, nrhs,
                      overlap="interior")

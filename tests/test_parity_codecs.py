"""Cross-backend parity of compressed gauge links: two_row and minimal
links reproduce the same backend's uncompressed Dhat (see
``test_parity_matrix.py`` for the whole matrix)."""
import numpy as np
import pytest
from parity_harness import (COMPRESSIONS, DTYPES, ODD_LATTICE, all_backends,
                            bind, fields, x64_ctx)

from repro import backends


# --- compressed gauge links ------------------------------------------

# atol vs the *same backend uncompressed* — isolates the codec error
# from the backend-vs-reference error the matrix above already bounds.
_C_ATOL = {("two_row", "f32"): 1e-5, ("two_row", "f64"): 1e-12,
           ("minimal", "f32"): 1e-5, ("minimal", "f64"): 1e-9}


@pytest.mark.parametrize("dtype", DTYPES)
def test_compressed_gauge_parity(dtype):
    """Every backend that advertises a compressed link representation
    reproduces its own uncompressed Dhat within the codec round-trip
    error (capability-gated over the live registry; the uncompressed
    reference is bound once per backend and shared across codecs)."""
    kappa = 0.13
    ran = 0
    with x64_ctx(dtype):
        Ue, Uo, e = fields(ODD_LATTICE, dtype, 1)
        for name in all_backends():
            caps = backends.backend_info(name)
            modes = [c for c in COMPRESSIONS
                     if c in caps.gauge_compressions]
            if not modes:
                continue
            plain = bind(name, Ue, Uo, dtype)
            want = np.asarray(plain.apply_dhat(e[0], kappa))
            for compression in modes:
                comp = bind(name, Ue, Uo, dtype,
                             gauge_compression=compression)
                np.testing.assert_allclose(
                    np.asarray(comp.apply_dhat(e[0], kappa)), want,
                    atol=_C_ATOL[compression, dtype],
                    err_msg=f"{name} {compression} {dtype}")
                ran += 1
    assert ran >= 8   # pallas x3 + distributed, two codecs each

"""Cross-backend parity property-test matrix.

One suite locking down that EVERY registered operator backend — the
pure-XLA reference, both Pallas stencil variants, the streaming
plane-window fused kernel, and the shard_map'd distributed operator —
computes the *same* ``Dhat`` / ``Dhat^dag`` / batched-``Dhat`` map as the
``jnp`` reference, across

* dtype  in {f32, f64}  (planar compute dtype; complex64/128 interface),
* nrhs   in {1, 4}      (batched native ops, leading RHS axis),
* odd lattice extents    (odd T/Z/Y and odd Xh stress every periodic
  wrap: the modular BlockSpec index maps, the scratch-ring boundary rows
  of the streaming kernel, and the parity-masked x-roll).

Two further axes ride on the same harness: ``gauge_compression``
(two_row / minimal compressed links must reproduce the uncompressed
output of the *same* backend within the codec round-trip error) and the
distributed ``overlap="interior"`` schedule (comms/compute overlap must
be numerically invisible).

The matrix is spread over several files so the multi-worker test run
can place its legs on different workers, with the shared harness in
``parity_harness.py``: the backend x dtype x nrhs matrix and the
overlap schedule in ``test_parity_backends.py``, the codec legs in
``test_parity_codecs.py`` and the hypothesis
layer (which widens the lattice/seed space when hypothesis is installed)
in ``test_parity_hypothesis.py``.  This file keeps the registry-coverage
check, the chaos legs and the overlap-with-compression leg.
"""
import jax
import numpy as np
import pytest
from parity_harness import (ATOL, COMPRESSIONS, ODD_LATTICE, all_backends,
                            bind, fields)

from repro import backends


def _chaos_cases():
    """Every backend, with and without each gauge codec it declares."""
    cases = []
    for name in all_backends():
        caps = backends.backend_info(name)
        cases += [(name, c) for c in ("none",) + tuple(
            c for c in COMPRESSIONS if c in caps.gauge_compressions)]
    return cases


CHAOS_CASES = _chaos_cases()


def test_matrix_covers_every_registered_backend():
    """The matrix below parametrizes over the LIVE registry — a new
    backend is locked down the moment it registers (and the streaming
    backend is registered)."""
    assert "pallas_fused_stream" in all_backends()
    assert "jnp" in all_backends()
    assert len(CHAOS_CASES) >= 8   # every backend, plus each codec


# --- chaos legs: NaN-column containment across backends x codecs -----


@pytest.mark.parametrize("name,compression", CHAOS_CASES,
                         ids=[f"{n}-{c}" for n, c in CHAOS_CASES])
def test_chaos_nan_column_containment_matrix(name, compression):
    """A NaN injected into one RHS column of the batched solve stays in
    that column on EVERY backend and every supported gauge codec: the
    poisoned column exits ``diverged`` and the healthy columns are
    BIT-EXACT with the uninjected run (per-column Krylov scalars and a
    column-local operator never mix columns — the containment property
    the divergence guard's per-column freeze relies on)."""
    from repro.core import solver
    from repro.resilience import nan_spinor_column

    kappa = 0.13
    nrhs = 3
    Ue, Uo, e = fields(ODD_LATTICE, "f32", nrhs)
    e_bad = nan_spinor_column(e, 1)
    extra = ({} if compression == "none"
             else {"gauge_compression": compression})
    bops = bind(name, Ue, Uo, "f32", **extra)
    run = jax.jit(solver.make_native_solve(
        bops, kappa, method="cgnr", tol=1e-3, max_iters=12,
        batched=True))
    v_o = bops.to_domain_batched(e)
    _, _, clean = run(bops.to_domain_batched(e), v_o)
    _, _, res = run(bops.to_domain_batched(e_bad), v_o)
    tag = f"{name}/{compression}"
    assert bool(res.diverged[1]), tag
    assert not bool(res.converged[1]), tag
    for col in (0, 2):
        assert np.array_equal(np.asarray(res.x[col]),
                              np.asarray(clean.x[col])), \
            (f"{tag}: healthy column {col} perturbed by the "
             "injected NaN column")


# --- distributed comms/compute overlap, with compression ------------


def test_distributed_interior_compressed_parity():
    """Overlap and compression compose: the interior schedule shipping
    two_row links still matches the jnp reference (one Dhat application
    — the dagger/batched legs are covered by the two tests above, and
    each extra leg is another ~30s compile of the split schedule)."""
    kappa = 0.13
    Ue, Uo, e = fields(ODD_LATTICE, "f32", 1)
    want = backends.make_wilson_ops("jnp", Ue, Uo).apply_dhat(e[0], kappa)
    bops = bind("distributed", Ue, Uo, "f32", overlap="interior",
                 gauge_compression="two_row")
    np.testing.assert_allclose(np.asarray(bops.apply_dhat(e[0], kappa)),
                               np.asarray(want), atol=ATOL["f32"])

"""Hypothesis layer of the cross-backend parity matrix: random odd-extent
lattices and seeds, every backend against the ``jnp`` reference (see
``test_parity_matrix.py`` for the whole matrix).  Runs only when
hypothesis is installed (CI installs it via requirements-dev.txt)."""
from parity_harness import DTYPES, NRHS, all_backends, check_parity, x64_ctx

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # the deterministic matrix still runs without it
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    settings.register_profile("parity", max_examples=5, deadline=None)
    settings.load_profile("parity")

    odd_dim = st.sampled_from([2, 3, 5])

    @given(T=odd_dim, Z=odd_dim, Y=st.sampled_from([2, 3]),
           Xh=st.sampled_from([2, 3]),
           dtype=st.sampled_from(DTYPES),
           nrhs=st.sampled_from(NRHS),
           seed=st.integers(0, 2 ** 12))
    def test_backend_parity_hypothesis(T, Z, Y, Xh, dtype, nrhs, seed):
        """Random odd-extent lattices: every backend agrees with the
        reference on Dhat / Dhat^dag / batched Dhat."""
        shape = (T, Z, Y, 2 * Xh)
        with x64_ctx(dtype):
            for name in all_backends():
                check_parity(name, shape, dtype, nrhs, seed=seed)

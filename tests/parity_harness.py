"""Shared harness of the cross-backend parity tests.

Every registered operator backend must compute the same ``Dhat`` /
``Dhat^dag`` / batched-``Dhat`` map as the ``jnp`` reference.  The tests
that use this harness are spread over several ``test_parity_*.py`` files
so that the multi-worker test run can place them on different workers.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import backends
from repro.core import evenodd, su3

DTYPES = ("f32", "f64")
NRHS = (1, 4)
# Odd T/Z/Y; X=6 gives odd Xh=3 — every axis wraps mid-parity-pattern.
ODD_LATTICE = (3, 5, 3, 6)

PLANAR = {"f32": jnp.float32, "f64": jnp.float64}
COMPLEX = {"f32": jnp.complex64, "f64": jnp.complex128}
ATOL = {"f32": 5e-5, "f64": 1e-10}


def all_backends():
    return backends.available_backends()


def bind(name, Ue, Uo, dtype, **extra):
    opts = {"dtype": PLANAR[dtype]} if name != "jnp" else {}
    if name.startswith("pallas") and jax.default_backend() != "tpu":
        opts["interpret"] = True
    opts.update(extra)
    ops = backends.make_wilson_ops(name, Ue, Uo, **opts)
    if name == "distributed":
        # Eager shard_map dispatches the body op-by-op (minutes per
        # Dhat); jit the entry points the matrix exercises.
        import dataclasses
        ops = dataclasses.replace(
            ops,
            apply_dhat=jax.jit(ops.apply_dhat, static_argnums=1),
            apply_dhat_dagger=jax.jit(ops.apply_dhat_dagger,
                                      static_argnums=1),
            apply_dhat_native_batched=jax.jit(
                ops.apply_dhat_native_batched, static_argnums=1))
    return ops


def fields(shape, dtype, nrhs, seed=0):
    cdt = COMPLEX[dtype]
    # Generate the gauge at the target precision: compressed-link
    # reconstruction relies on unitarity *at that precision*, and an
    # f32-generated field upcast to f64 is only unitary to ~1e-7.
    U = su3.random_gauge(jax.random.PRNGKey(seed), shape, dtype=cdt)
    k = jax.random.PRNGKey(seed + 1)
    bshape = (nrhs, *shape, 4, 3)
    psi = (jax.random.normal(k, bshape)
           + 1j * jax.random.normal(jax.random.fold_in(k, 1), bshape)
           ).astype(cdt)
    e, _ = jax.vmap(evenodd.pack)(psi)
    Ue, Uo = evenodd.pack_gauge(U)
    return Ue, Uo, e


def check_parity(name, shape, dtype, nrhs, seed=0, **bind_opts):
    """Dhat / Dhat^dag / batched-Dhat of ``name`` vs the jnp reference."""
    kappa = 0.13
    atol = ATOL[dtype]
    Ue, Uo, e = fields(shape, dtype, nrhs, seed=seed)
    ref = backends.make_wilson_ops("jnp", Ue, Uo)
    bops = bind(name, Ue, Uo, dtype, **bind_opts)

    want = jnp.stack([ref.apply_dhat(e[n], kappa) for n in range(nrhs)])

    # Unbatched ops (Dhat and its dagger), column by column — the
    # nrhs=1 leg of the matrix carries these; the nrhs>1 legs would
    # repeat byte-identical work and only re-exercise cached kernels.
    if nrhs == 1:
        want_dag = jnp.stack(
            [ref.apply_dhat_dagger(e[n], kappa) for n in range(nrhs)])
        for n in range(nrhs):
            np.testing.assert_allclose(
                np.asarray(bops.apply_dhat(e[n], kappa)),
                np.asarray(want[n]), atol=atol,
                err_msg=f"{name} Dhat col {n} {shape} {dtype}")
            np.testing.assert_allclose(
                np.asarray(bops.apply_dhat_dagger(e[n], kappa)),
                np.asarray(want_dag[n]), atol=atol,
                err_msg=f"{name} Dhat^dag col {n} {shape} {dtype}")

    # Batched native op, whole block at once.
    v = bops.to_domain_batched(e)
    got = bops.from_domain_batched(
        bops.apply_dhat_native_batched(v, kappa)).astype(e.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol,
                               err_msg=f"{name} batched Dhat {shape} "
                                       f"{dtype} nrhs={nrhs}")


def x64_ctx(dtype):
    import contextlib

    from repro.compat import enable_x64
    return enable_x64() if dtype == "f64" else contextlib.nullcontext()


# Compressed link representations the codec legs sweep.
COMPRESSIONS = ("two_row", "minimal")

"""Seeded Wilson problems that are made and checked on the host CPU.

A TPU tiles the two minor dims of every array; in complex64 the complex
layout's (3, 3) link and (4, 3) spinor blocks each pad to a (4, 128) tile
of 4096 bytes, so at 32x32x32x64 the complex gauge field alone would take
34,359,738,368 B of a 16 GB v5e.  The planar layout the kernels iterate
on does fit.  So the gauge field and the sources are made on the host
CPU, the operator is bound there and moved to the accelerator in its
planar form, and :class:`repro.api.SolveSession` sends host sources to
the chip as lane-dense views of their bytes, which tile unpadded, or as
native vectors encoded on the host
(:meth:`repro.api.WilsonMatrix.encode`).  The true residual is checked
on the host by the plain full-system operator of ``repro.core.wilson``,
independent of the backend under test.

Where JAX's default device is the host CPU all of this runs in place.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import api
from repro.core import evenodd, su3, wilson


def host_device():
    return jax.devices("cpu")[0]


class HostProblem:
    """A random SU(3) gauge field on ``extents`` (T, Z, Y, X), made from
    ``seed``, its sources, and the true-residual check, all on the host.

    Source ``i`` is drawn from ``fold_in(PRNGKey(seed), 100 + i)``.
    """

    def __init__(self, extents: Sequence[int], kappa: float, seed: int):
        self.extents = tuple(extents)
        self.kappa = float(kappa)
        self.key = jax.random.PRNGKey(seed)
        self.host = host_device()
        with jax.default_device(self.host):
            U = su3.random_gauge(self.key, self.extents)
            # Committed to the host: every computation on these arrays
            # (binding's layout conversion included) runs there.
            self.U = jax.device_put(U, self.host)
            self.Ue, self.Uo = jax.device_put(evenodd.pack_gauge(U),
                                              self.host)

    def bind(self, backend, **bind_kw) -> api.WilsonMatrix:
        """Bind ``backend`` on the host, then move the bound (native
        layout) gauge to JAX's default device unless the backend placed
        it itself (the distributed backend shards it over its mesh)."""
        matrix = api.WilsonMatrix.bind(self.Ue, self.Uo, self.kappa,
                                       backend=backend, **bind_kw)
        device = jax.devices()[0]
        if matrix.devices() == {self.host} and device != self.host:
            matrix = matrix.to_device(device)
        return matrix

    def source(self, i: int, nrhs: Optional[int] = None,
               dtype=jnp.complex64):
        """Full-lattice source ``eta`` and its even/odd halves, on the
        host; ``nrhs`` adds a leading column axis."""
        k = jax.random.fold_in(self.key, 100 + i)
        shape = (() if nrhs is None else (nrhs,)) + self.extents + (4, 3)
        with jax.default_device(self.host):
            eta = (jax.random.normal(k, shape)
                   + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                            shape)).astype(dtype)
            ee, eo = (evenodd.pack(eta) if nrhs is None
                      else jax.vmap(evenodd.pack)(eta))
            return jax.device_put((eta, ee, eo), self.host)

    def true_residual(self, eta, xe, xo) -> float:
        """Largest per-column ``|eta - D_W xi| / |eta|`` of the full
        Wilson system, computed on the host."""
        kappa = self.kappa

        def one(e, a, b):
            # repro-lint: allow[R2] independent full-system residual
            r = e - wilson.apply_wilson(self.U, evenodd.unpack(a, b), kappa)
            return jnp.linalg.norm(r) / jnp.linalg.norm(e)

        xe, xo = jax.device_put((xe, xo), self.host)
        with jax.default_device(self.host):
            if eta.ndim == 6:
                return float(one(eta, xe, xo))
            return float(jnp.max(jax.vmap(one)(eta, xe, xo)))

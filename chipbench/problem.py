"""The system under test, bound from the seed: a Haar-random SU(3)
gauge field made on the host CPU, bound through the public API and moved
to the chip.

At 32^3x64 a complex gauge field tiled on a TPU would take 34 GB
(its (3, 3) minor dims pad to (4, 128)), so the field is made on the host
and only the planar operator the backend binds goes to the chip: the
path ``WilsonMatrix.bind`` + ``to_device`` that users of the API take.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, sources


def gauge_key(seed: int):
    words = np.random.SeedSequence(
        [int(seed) % 2**64, sources.GAUGE]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _haar(key, shape):
    """Haar-random SU(3) matrices ``(*shape, 3, 3)``: QR of a complex
    Gaussian matrix, the phases of ``diag(r)`` and of the determinant
    divided out."""
    kr, ki = jax.random.split(key)
    m = (jax.random.normal(kr, (*shape, 3, 3))
         + 1j * jax.random.normal(ki, (*shape, 3, 3))).astype(jnp.complex64)
    q, r = jnp.linalg.qr(m)
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / jnp.abs(d))[..., None, :]
    det = jnp.linalg.det(q)
    return q * (det[..., None, None] ** (-1.0 / 3.0))


def _haar_halves(key, extents):
    U = _haar(key, (4, *extents))
    halves = [reference.pack(U[mu]) for mu in range(4)]
    return (U, jnp.stack([h[0] for h in halves]),
            jnp.stack([h[1] for h in halves]))


class Problem:
    """Gauge field from ``seed`` on the host; ``matrix`` is the operator
    bound on the backend the configuration names, on JAX's default
    device."""

    def __init__(self, config: dict, seed: int):
        from repro import api
        from repro.kernels.wilson_stencil import resolve_interpret

        self.extents = tuple(int(e) for e in config["extents"])
        self.kappa = float(config["kappa"])
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            key = jax.device_put(gauge_key(seed), cpu)
            fn = jax.jit(_haar_halves, static_argnums=1)
            self.U, Ue, Uo = jax.device_put(fn(key, self.extents), cpu)
            spec = api.BackendSpec(config["backend"], dtype=config["dtype"],
                                   interpret=config["interpret"])
            matrix = api.WilsonMatrix.bind(
                Ue, Uo, self.kappa, backend=spec,
                fallback=bool(config["fallback"]))
        del Ue, Uo
        device = jax.devices()[0]
        if device != cpu:
            matrix = matrix.to_device(device)
        if matrix.backend.name != config["backend"] or matrix.degraded:
            raise RuntimeError(f"bound {matrix.backend.name}, degraded="
                               f"{matrix.degraded}")
        if resolve_interpret(matrix.backend.interpret) != config["interpret"]:
            raise RuntimeError("the Pallas interpreter is not as the "
                               "configuration states")
        self.matrix = matrix
        self.solve_spec = api.SolveSpec(method=config["method"],
                                        tol=float(config["tol"]),
                                        max_iters=int(config["max_iters"]))

    def reference(self) -> reference.Reference:
        return reference.Reference(self.U, self.kappa)

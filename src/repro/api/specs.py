"""Validated spec dataclasses — the public configuration surface.

Three frozen (hashable) dataclasses replace the ~10-kwarg sprawl that
the CLI, examples, and benchmarks each used to hand-wire into the old
one-shot solver entry point:

* :class:`LatticeSpec`   — the lattice geometry (extents, even-odd
  half-extent) and the shapes derived from it;
* :class:`BackendSpec`   — which operator backend, at which compute
  dtype, with which knobs — validated against the registry's
  per-backend :class:`~repro.backends.BackendCapabilities`;
* :class:`SolveSpec`     — the Krylov configuration (method, tolerance,
  batching, mixed-precision refinement).

Being frozen and hashable, the specs double as cache keys: a
:class:`~repro.api.SolveSession` keys its compiled solves on
``(SolveSpec, rhs shape/dtype)``, and a :class:`~repro.api.WilsonMatrix`
carries its ``LatticeSpec``/``BackendSpec`` as static pytree aux data,
so two same-shape matrices hit the same jit cache entry.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro import backends
from repro.core import solver as _solver
from repro.kernels.wilson_stencil import resolve_interpret

__all__ = ["LatticeSpec", "BackendSpec", "SolveSpec"]


@dataclasses.dataclass(frozen=True)
class LatticeSpec:
    """Lattice geometry: full extents ``(T, Z, Y, X)``.

    The even-odd layout packs x in half (``Xh = X // 2``); all public
    arrays are shaped from these extents, so the spec is the single
    source for shape validation (see :meth:`spinor_eo_shape`).
    """

    extents: Tuple[int, int, int, int]

    def __post_init__(self):
        ext = tuple(int(e) for e in self.extents)
        object.__setattr__(self, "extents", ext)
        if len(ext) != 4 or any(e <= 0 for e in ext):
            raise ValueError(
                f"LatticeSpec.extents must be 4 positive ints (T, Z, Y, "
                f"X); got {self.extents!r}")
        if ext[3] % 2:
            raise ValueError(
                f"X extent must be even for the even-odd packing; got "
                f"X={ext[3]}")

    @property
    def T(self):
        return self.extents[0]

    @property
    def Z(self):
        return self.extents[1]

    @property
    def Y(self):
        return self.extents[2]

    @property
    def X(self):
        return self.extents[3]

    @property
    def Xh(self):
        """Packed (even-odd) x half-extent."""
        return self.extents[3] // 2

    @property
    def volume(self):
        T, Z, Y, X = self.extents
        return T * Z * Y * X

    @classmethod
    def from_eo_gauge(cls, U_e) -> "LatticeSpec":
        """Infer the spec from an even-half gauge field
        ``(4, T, Z, Y, Xh, 3, 3)``."""
        if U_e.ndim != 7 or U_e.shape[0] != 4 or U_e.shape[-2:] != (3, 3):
            raise ValueError(
                f"expected even-odd gauge half (4, T, Z, Y, Xh, 3, 3); "
                f"got shape {U_e.shape}")
        T, Z, Y, Xh = U_e.shape[1:5]
        return cls((T, Z, Y, 2 * Xh))

    def spinor_eo_shape(self, nrhs: Optional[int] = None):
        """Shape of one even/odd spinor half; with ``nrhs`` a leading
        RHS batch axis is prepended."""
        base = (self.T, self.Z, self.Y, self.Xh, 4, 3)
        return base if nrhs is None else (int(nrhs),) + base


_DTYPE_ALIASES = {
    "f32": "f32", "float32": "f32",
    "bf16": "bf16", "bfloat16": "bf16",
    "f64": "f64", "float64": "f64",
}
_DTYPE_JNP = {"f32": jnp.float32, "bf16": jnp.bfloat16, "f64": jnp.float64}


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Which operator backend to bind, and how.

    ``name`` is a registry name (:func:`repro.backends.available_backends`)
    or ``"auto"`` (``pallas_fused`` on TPU, ``jnp`` elsewhere);
    ``dtype`` the planar compute dtype (``"f32"``/``"bf16"``/``"f64"``)
    for backends that take one; ``interpret`` forces/disables the Pallas
    interpreter (``None`` = auto off-TPU); ``gauge_compression`` selects
    the stored SU(3) link representation (``"none"`` | ``"two_row"`` |
    ``"minimal"`` — 18/12/8 real planes per link, reconstructed
    in-register by the kernels); ``opts`` is a tuple of extra
    ``(key, value)`` pairs forwarded verbatim to the factory (values
    must be hashable — the spec is jit-cache aux data).

    :meth:`validated` resolves ``"auto"`` and checks every knob against
    the backend's registered :class:`~repro.backends.BackendCapabilities`,
    so a bad combination fails at spec time with the capability listing
    in the error, not deep inside a bind.
    """

    name: str = "auto"
    dtype: Optional[str] = None
    interpret: Optional[bool] = None
    gauge_compression: str = "none"
    opts: Tuple[Tuple[str, object], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "opts", tuple(
            (str(k), v) for k, v in self.opts))
        gc = str(self.gauge_compression or "none")
        if gc not in ("none", "two_row", "minimal"):
            raise ValueError(
                f"unknown gauge_compression {self.gauge_compression!r}; "
                "choose from ('none', 'two_row', 'minimal')")
        object.__setattr__(self, "gauge_compression", gc)
        if self.dtype is not None:
            norm = _DTYPE_ALIASES.get(str(self.dtype).lower())
            if norm is None:
                raise ValueError(
                    f"unknown compute dtype {self.dtype!r}; choose from "
                    f"{sorted(set(_DTYPE_ALIASES.values()))}")
            object.__setattr__(self, "dtype", norm)

    @classmethod
    def coerce(cls, value) -> "BackendSpec":
        """Accept a BackendSpec, a registry name string, or None."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(name=value)
        raise TypeError(
            f"backend must be a BackendSpec or a registry name string; "
            f"got {type(value).__name__}")

    def resolve_name(self) -> str:
        if self.name != "auto":
            return self.name
        import jax
        return "pallas_fused" if jax.default_backend() == "tpu" else "jnp"

    def validated(self) -> "BackendSpec":
        """Resolve ``"auto"`` and validate against the backend's
        capability metadata; returns the concrete spec."""
        name = self.resolve_name()
        caps = backends.backend_info(name)   # raises with the listing
        if self.dtype is not None and self.dtype not in caps.dtypes:
            if not caps.dtypes:
                raise ValueError(
                    f"backend {name!r} takes no compute dtype (it "
                    f"follows the gauge dtype); drop BackendSpec.dtype "
                    f"[capabilities: {caps}]")
            raise ValueError(
                f"backend {name!r} does not support dtype "
                f"{self.dtype!r}; supported: {caps.dtypes} "
                f"[capabilities: {caps}]")
        if self.interpret is not None and not caps.supports_interpret:
            raise ValueError(
                f"backend {name!r} has no interpret mode; drop "
                f"BackendSpec.interpret [capabilities: {caps}]")
        if (self.dtype in caps.interpret_only_dtypes
                and not resolve_interpret(self.interpret)):
            raise ValueError(
                f"backend {name!r} has no compiled {self.dtype} kernel "
                f"for the TPU; {self.dtype} runs only in the Pallas "
                f"interpreter (interpret=True), or choose the jnp "
                f"backend [capabilities: {caps}]")
        if (self.gauge_compression != "none"
                and self.gauge_compression not in caps.gauge_compressions):
            raise ValueError(
                f"backend {name!r} does not support gauge_compression "
                f"{self.gauge_compression!r}; supported: "
                f"{caps.gauge_compressions} [capabilities: {caps}]")
        return dataclasses.replace(self, name=name)

    @property
    def capabilities(self) -> backends.BackendCapabilities:
        return backends.backend_info(self.resolve_name())

    def factory_opts(self) -> dict:
        """The kwargs this spec hands the backend factory."""
        out = dict(self.opts)
        if self.dtype is not None:
            out["dtype"] = _DTYPE_JNP[self.dtype]
        if self.interpret is not None:
            out["interpret"] = self.interpret
        if self.gauge_compression != "none":
            out["gauge_compression"] = self.gauge_compression
        return out


@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """One Krylov solve configuration.

    ``method`` comes from :data:`repro.core.solver.KRYLOV_METHODS` — the
    CLI's ``--method`` choices are *derived* from that tuple through
    this class, never duplicated.  ``nrhs`` is optional: ``None`` means
    "infer from the source block" (a leading batch axis on the sources
    selects the batched pipeline); when set, the sources are validated
    against it.  ``inner_dtype`` switches to mixed-precision iterative
    refinement (inner Krylov in that dtype, outer f64 true-residual loop
    — needs jax x64).

    Resilience knobs: ``guard`` enables the in-loop divergence guards
    (non-finite freeze + stagnation restart — see
    :mod:`repro.core.solver`), tuned by ``stagnation_window`` /
    ``max_restarts``; ``escalate`` lets a stalling refined solve climb
    the inner-dtype precision ladder
    (:data:`repro.core.solver.ESCALATION_LADDER`).

    Deflation knobs (:mod:`repro.core.deflate`): ``deflate_rank > 0``
    turns on low-mode deflation of the normal operator for the
    normal-equations methods (:data:`repro.core.solver.DEFLATABLE_METHODS`)
    — the subspace is computed once per bound gauge and cached on the
    :class:`~repro.api.WilsonMatrix`.  ``deflate_mode`` picks how the
    subspace is built: ``"lanczos"`` pays an up-front eigensolve;
    ``"recycle"`` starts empty and harvests converged solutions from
    the request stream, so per-solve iteration counts drop as the
    stream proceeds (watch ``SolveSession.stats()``).
    ``deflate_iters`` caps the Lanczos step count (``None`` = auto;
    raise it when the low spectrum is degenerate — single-vector
    Lanczos resolves one copy of a degenerate cluster per ~cluster
    revisit, so finding all of them needs more steps than the
    default).
    ``deflate_checkpoint`` names a directory where the basis is
    persisted (:class:`repro.resilience.BasisSnapshot`) and restored
    from on a later bind of the same gauge.

    ``donate_rhs`` marks the solve's (encoded) source buffers as
    donated to the compiled executable — the serving hot path's knob:
    a request batch assembled by the coalescing daemon is a temporary
    the caller never reads again, so XLA may reuse its bytes for the
    solution block instead of allocating a fresh one.  The caller MUST
    NOT touch the source arrays after the solve (for backends whose
    native domain is the complex layout the encoded vector aliases the
    caller's array).  Plain (non-refined) solves only; some platforms
    (CPU) may decline donation with a warning and run correctly
    without the reuse.
    """

    METHODS = _solver.KRYLOV_METHODS
    DEFLATE_MODES = ("lanczos", "recycle")

    method: str = "cgnr"
    tol: float = 1e-6
    max_iters: int = 2000
    recompute_every: int = 0
    nrhs: Optional[int] = None
    inner_dtype: Optional[str] = None
    inner_tol: float = 1e-4
    max_outer: int = 25
    guard: bool = True
    stagnation_window: int = _solver.STAGNATION_WINDOW
    max_restarts: int = _solver.MAX_RESTARTS
    escalate: bool = True
    deflate_rank: int = 0
    deflate_mode: str = "lanczos"
    deflate_iters: Optional[int] = None
    deflate_checkpoint: Optional[str] = None
    donate_rhs: bool = False

    def __post_init__(self):
        if self.method not in self.METHODS:
            raise ValueError(
                f"unknown method {self.method!r}; choose from "
                f"{self.METHODS}")
        if not (self.tol > 0):
            raise ValueError(f"tol must be > 0; got {self.tol}")
        if self.max_iters < 1:
            raise ValueError(
                f"max_iters must be >= 1; got {self.max_iters}")
        if self.recompute_every < 0:
            raise ValueError(
                f"recompute_every must be >= 0 (0 = never); got "
                f"{self.recompute_every}")
        if self.nrhs is not None and self.nrhs < 1:
            raise ValueError(f"nrhs must be >= 1; got {self.nrhs}")
        if self.inner_dtype is not None:
            # normalizes spelling and raises on unknown dtypes
            _solver.resolve_inner_dtype(self.inner_dtype)
        if not (self.inner_tol > 0):
            raise ValueError(
                f"inner_tol must be > 0; got {self.inner_tol}")
        if self.max_outer < 1:
            raise ValueError(
                f"max_outer must be >= 1; got {self.max_outer}")
        if self.stagnation_window < 2:
            raise ValueError(
                f"stagnation_window must be >= 2; got "
                f"{self.stagnation_window}")
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0; got {self.max_restarts}")
        if self.deflate_rank < 0:
            raise ValueError(
                f"deflate_rank must be >= 0 (0 = no deflation); got "
                f"{self.deflate_rank}")
        if self.deflate_mode not in self.DEFLATE_MODES:
            raise ValueError(
                f"unknown deflate_mode {self.deflate_mode!r}; choose "
                f"from {self.DEFLATE_MODES}")
        if self.deflate_iters is not None and self.deflate_iters < 1:
            raise ValueError(
                f"deflate_iters must be >= 1 (None = auto); got "
                f"{self.deflate_iters}")
        if self.deflate_rank > 0:
            if self.method not in _solver.DEFLATABLE_METHODS:
                raise ValueError(
                    f"deflation applies to the normal-equations methods "
                    f"{_solver.DEFLATABLE_METHODS}, not "
                    f"{self.method!r}")
            if self.inner_dtype is not None:
                raise ValueError(
                    "deflation and mixed-precision refinement "
                    "(inner_dtype) are not combinable yet: the deflation "
                    "basis lives on the native normal operator, which "
                    "the refined solve rebuilds per escalation rung")
        if self.donate_rhs and self.inner_dtype is not None:
            raise ValueError(
                "donate_rhs applies to plain solves only: the refined "
                "outer loop re-reads the f64 source every pass, so its "
                "buffers cannot be donated")

    def validate_rhs(self, eta_e, eta_o, lattice: LatticeSpec) -> bool:
        """Check a source pair against the lattice and ``nrhs``;
        returns whether the solve is batched."""
        if eta_e.shape != eta_o.shape:
            raise ValueError(
                f"even/odd sources disagree: {eta_e.shape} vs "
                f"{eta_o.shape}")
        batched = eta_e.ndim == 7
        want = lattice.spinor_eo_shape(eta_e.shape[0] if batched
                                       else None)
        if eta_e.shape != want:
            raise ValueError(
                f"source shape {eta_e.shape} does not match lattice "
                f"{lattice.extents} (expected {want}; a leading axis "
                "would select the batched multi-RHS pipeline)")
        got_nrhs = eta_e.shape[0] if batched else 1
        if self.nrhs is not None and self.nrhs != got_nrhs:
            raise ValueError(
                f"SolveSpec.nrhs={self.nrhs} but the source block has "
                f"nrhs={got_nrhs}")
        return batched

    def cache_token(self) -> str:
        """Compact human-readable form used in session stats keys.

        Covers every field (defaults elided where unambiguous) so two
        distinct specs can never collide onto one stats row."""
        parts = [self.method, f"tol{self.tol:g}", f"mi{self.max_iters}"]
        if self.recompute_every:
            parts.append(f"re{self.recompute_every}")
        if self.nrhs is not None:
            parts.append(f"nrhs{self.nrhs}")
        if self.inner_dtype is not None:
            parts.append(f"inner-{self.inner_dtype}"
                         f"@{self.inner_tol:g}x{self.max_outer}")
            if not self.escalate:
                parts.append("noesc")
        if not self.guard:
            parts.append("noguard")
        else:
            if self.stagnation_window != _solver.STAGNATION_WINDOW:
                parts.append(f"sw{self.stagnation_window}")
            if self.max_restarts != _solver.MAX_RESTARTS:
                parts.append(f"mr{self.max_restarts}")
        if self.deflate_rank:
            parts.append(f"defl{self.deflate_rank}-{self.deflate_mode}")
            if self.deflate_iters is not None:
                parts.append(f"li{self.deflate_iters}")
        if self.donate_rhs:
            parts.append("donate")
        return ":".join(parts)

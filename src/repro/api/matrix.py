"""Bind-once Wilson operator object, registered as a JAX pytree.

A :class:`WilsonMatrix` binds ``(gauge, kappa, BackendSpec)`` exactly
once: layout conversion (complex -> planar re/im planes), sharding
placement, and backend/policy selection all happen at construction, and
every subsequent application reuses the bound state.  The pytree
registration makes the *gauge arrays the leaves* and the specs/kappa
static aux data, so

* ``jax.jit(lambda D, psi: D(psi))`` compiles once per gauge
  *shape+spec*, not per gauge *value* — a second same-shape matrix hits
  the cache;
* ``jax.tree_util.tree_flatten`` / ``tree_map`` work (the operators are
  rebuilt from the mapped leaves on unflatten, via the backend's
  registered native factory — no layout conversion happens again);
* solves can close over a matrix (the :class:`~repro.api.SolveSession`
  pattern) without retracing per call.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import backends
from repro.kernels import layout

from .specs import BackendSpec, LatticeSpec

__all__ = ["WilsonMatrix"]


class _Opaque:
    """Identity-hashed wrapper for non-hashable bind kwargs (meshes,
    partitions) carried through pytree aux data.  Two matrices bound
    with separate opaque opts never share a jit cache entry — by
    design: we cannot prove their unhashable knobs equal."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


class WilsonMatrix:
    """The even-odd preconditioned Wilson operator, bound to one gauge
    configuration.

    Construct with :meth:`bind` (from complex even/odd gauge halves and
    a :class:`~repro.api.BackendSpec`) or wrap an existing
    :class:`~repro.backends.WilsonOps` with :meth:`from_ops`.  Apply it
    like a function::

        D = WilsonMatrix.bind(U_e, U_o, kappa=0.13,
                              backend=BackendSpec("pallas_fused"))
        out  = D(psi_e)            # Dhat psi      (complex interface)
        outd = D.dagger(psi_e)     # Dhat^dag psi
        outn = D.normal(psi_e)     # Dhat^dag Dhat psi

    Sources with a leading ``nrhs`` axis run the batched kernels.  The
    native-domain boundary is exposed as :meth:`encode` / :meth:`decode`
    / :meth:`apply_native` / :meth:`dagger_native` for callers that
    iterate natively (the Krylov solvers do).
    """

    def __init__(self, gauge: Tuple, kappa: float, lattice: LatticeSpec,
                 backend: BackendSpec, *, gauge_form: str = "complex",
                 rebuild: str = "native", opaque=None, ops=None):
        self._gauge = tuple(gauge)
        self.kappa = float(kappa)
        self.lattice = lattice
        self.backend = backend
        self._gauge_form = gauge_form
        self._rebuild = rebuild
        self._opaque = opaque
        self._ops = ops
        # Exact complex gauge halves as passed to bind/from_ops; NOT a
        # pytree leaf (an unflattened matrix loses it and falls back to
        # reconstructing from the — possibly dtype-rounded — leaves).
        self._exact_gauge = None
        # Resilience state (set by bind/fallback_next; defaults keep
        # from_ops/unflatten construction paths untouched).
        self.fallback_enabled = False
        self.fallback_events: Tuple[Tuple[str, str], ...] = ()
        self.requested_backend = backend.name if backend else None
        self.gauge_audit = None
        # Deflation subspaces, keyed (rank, mode) — computed once per
        # bound gauge by ensure_deflation and shared by every session /
        # spec that asks for the same knobs.
        self._deflation = {}

    # --- construction -------------------------------------------------

    @classmethod
    def bind(cls, U_e, U_o, kappa: float, backend="auto",
             validate: str = "none", fallback: bool = False,
             **bind_opts) -> "WilsonMatrix":
        """Bind the named backend to complex even/odd gauge halves.

        ``backend`` is a :class:`~repro.api.BackendSpec` or a registry
        name; it is validated against the backend's capability metadata
        here.  ``bind_opts`` are extra factory kwargs that cannot live
        in the (hashable) spec — e.g. a ``mesh``/``partition`` for the
        distributed backend.  All expensive bind-once work (layout
        conversion, device placement) happens in this call.

        ``validate`` audits the gauge for SU(3) unitarity / finiteness
        before binding: ``"none"`` skips, ``"warn"`` emits a
        ``RuntimeWarning`` on defects, ``"repair"`` projects defective
        links back onto SU(3) (identity-replacing non-finite ones) so
        that even *compressed* codecs pack the repaired links.  The
        audit report lands on ``.gauge_audit``.

        ``fallback=True`` arms graceful degradation: if binding fails,
        the declared fallback chain (see
        :func:`repro.resilience.fallback_chain`) is walked here; if a
        *solve* later fails, :class:`~repro.api.SolveSession` walks it
        via :meth:`fallback_next`.  Degradation is recorded on
        ``.fallback_events`` and the ``.degraded`` flag.
        """
        if validate not in ("none", "warn", "repair"):
            raise ValueError(
                f"validate must be 'none'|'warn'|'repair', "
                f"got {validate!r}")
        audit = None
        if validate != "none":
            from repro.resilience import validate as _rv
            if validate == "repair":
                U_e, U_o, audit = _rv.repair_gauge(U_e, U_o)
            else:
                audit = _rv.audit_gauge(U_e, U_o)
                if not audit.ok:
                    import warnings
                    warnings.warn(
                        f"gauge fails SU(3) audit: {audit}; bind with "
                        "validate='repair' to project links back onto "
                        "the group", RuntimeWarning, stacklevel=2)

        spec = BackendSpec.coerce(backend).validated()
        requested = spec.name
        if fallback:
            from repro.resilience import adapt_spec, fallback_chain
            events = []
            last_exc: Optional[BaseException] = None
            for i, name in enumerate(fallback_chain(spec.name)):
                try_spec = spec if i == 0 else adapt_spec(spec, name)
                try_opts = bind_opts if i == 0 else {}
                try:
                    m = cls._bind_one(U_e, U_o, kappa, try_spec,
                                      try_opts)
                    break
                except Exception as exc:      # noqa: BLE001 — chain
                    events.append((name, repr(exc)))
                    last_exc = exc
            else:
                raise last_exc
            m.fallback_events = tuple(events)
        else:
            m = cls._bind_one(U_e, U_o, kappa, spec, bind_opts)
        m.fallback_enabled = bool(fallback)
        m.requested_backend = requested
        m.gauge_audit = audit
        return m

    @classmethod
    def _bind_one(cls, U_e, U_o, kappa, spec, bind_opts):
        lattice = LatticeSpec.from_eo_gauge(U_e)
        opts = {**spec.factory_opts(), **bind_opts}
        gauge = backends.prepare_gauge(spec.name, U_e, U_o, **opts)
        ops = backends.bind_native(spec.name, gauge, **opts)
        caps = backends.backend_info(spec.name)
        m = cls(gauge, kappa, lattice, spec,
                gauge_form=caps.gauge_form, rebuild="native",
                opaque=_Opaque(bind_opts) if bind_opts else None,
                ops=ops)
        # Keep the exact complex gauge for refined solves: the planar
        # leaves are rounded to the compute dtype (bf16 leaves deviate
        # by ~1e-3), so reconstructing the f64 reference operator from
        # them would make the "true residual" target the wrong gauge.
        m._exact_gauge = (U_e, U_o)
        return m

    # --- graceful degradation ------------------------------------------

    @property
    def degraded(self) -> bool:
        """True when this matrix is not running on the backend it was
        asked for (a fallback fired at bind or solve time)."""
        return bool(self.fallback_events) or (
            self.requested_backend is not None
            and self.requested_backend != self.backend.name)

    def fallback_next(self, reason: str = "") -> Optional["WilsonMatrix"]:
        """Rebind this matrix onto the next backend in its declared
        fallback chain, recording ``(failed_backend, reason)``.

        Returns ``None`` when the chain is exhausted (or the matrix
        was wrapped from bare ops and cannot rebind).  Used by
        :class:`~repro.api.SolveSession` to recover from solve-time
        failures without losing the gauge or the session."""
        if self._exact_gauge is None:
            return None
        from repro.resilience import adapt_spec, fallback_chain
        chain = fallback_chain(self.backend.name)
        if len(chain) < 2:
            return None
        spec = adapt_spec(self.backend, chain[1])
        U_e, U_o = self._exact_gauge
        m = self._bind_one(U_e, U_o, self.kappa, spec, {})
        m.fallback_enabled = self.fallback_enabled
        m.requested_backend = self.requested_backend
        m.gauge_audit = self.gauge_audit
        m.fallback_events = self.fallback_events + (
            (self.backend.name, reason),)
        # Rebinding ran where the exact gauge lives (the host, for a
        # matrix moved by to_device); the fallback serves from where
        # this matrix did.
        here = self.devices()
        if len(here) == 1 and m.devices() != here:
            m = m.to_device(next(iter(here)))
        return m

    # --- placement ------------------------------------------------------

    def devices(self) -> set:
        """The devices the bound gauge leaves live on (empty for a
        matrix without leaves, or one whose leaves are being traced)."""
        out = set()
        for leaf in self._gauge:
            if (isinstance(leaf, jax.core.Tracer)
                    or not hasattr(leaf, "sharding")):
                return set()
            out |= leaf.sharding.device_set
        return out

    def to_device(self, device) -> "WilsonMatrix":
        """This matrix with its gauge leaves moved to ``device``.  The
        bind-time state (resilience flags, audit, and the exact complex
        gauge, which stays where it was) carries over."""
        m = jax.device_put(self, device)
        m._exact_gauge = self._exact_gauge
        m.fallback_enabled = self.fallback_enabled
        m.fallback_events = self.fallback_events
        m.requested_backend = self.requested_backend
        m.gauge_audit = self.gauge_audit
        return m

    def foreign_home(self, psi):
        """The device ``psi`` is committed to, when that is a device the
        bound operator is not on (a host-resident source for an
        accelerator-resident matrix); else ``None``.

        Complex spinors from such a home are encoded there and only the
        native vectors cross to the operator: see :meth:`encode`."""
        if (isinstance(psi, jax.core.Tracer)
                or not getattr(psi, "committed", False)):
            return None
        home = psi.sharding.device_set
        here = self.devices()
        if len(home) != 1 or not here or home & here:
            return None
        return next(iter(home))

    def place(self, v):
        """Native vector ``v`` moved to the operator's device, where the
        operator sits on one device and ``v`` is not there."""
        here = self.devices()
        if len(here) == 1 and v.sharding.device_set != here:
            return jax.device_put(v, next(iter(here)))
        return v

    @classmethod
    def from_ops(cls, ops, kappa: float, gauge=None,
                 backend: Optional[BackendSpec] = None) -> "WilsonMatrix":
        """Wrap an already-bound :class:`~repro.backends.WilsonOps`.

        ``gauge`` (the complex even/odd halves) becomes the pytree
        leaves when given.  If ``ops.backend`` is a registered name the
        matrix stays tree-transformable (operators are rebuilt through
        the registry factory on unflatten); otherwise the bound ops ride
        along as aux data and the leaves must not be substituted.
        """
        leaves = tuple(gauge) if gauge is not None else ()
        lattice = (LatticeSpec.from_eo_gauge(leaves[0])
                   if leaves else None)
        spec = backend or BackendSpec(name=ops.backend)
        try:
            backends.backend_info(ops.backend)
            rebuild = "factory" if leaves else "pinned"
        except ValueError:
            rebuild = "pinned"
        m = cls(leaves, kappa, lattice, spec, gauge_form="complex",
                rebuild=rebuild,
                opaque=_Opaque(ops) if rebuild == "pinned" else None,
                ops=ops)
        if leaves:
            m._exact_gauge = leaves
        return m

    # --- bound operators ----------------------------------------------

    @property
    def ops(self):
        """The bound :class:`~repro.backends.WilsonOps` (rebuilt lazily
        from the gauge leaves after a pytree unflatten)."""
        if self._ops is None:
            if self._rebuild == "native":
                opts = {**self.backend.factory_opts(),
                        **(self._opaque.value if self._opaque else {})}
                # dtype is baked into prepared gauge leaves; rebinding
                # must not try to re-convert.
                self._ops = backends.bind_native(
                    self.backend.name, self._gauge, **opts)
            elif self._rebuild == "factory":
                self._ops = backends.make_wilson_ops(
                    self.backend.name, *self._gauge,
                    **self.backend.factory_opts())
            else:
                raise ValueError(
                    f"WilsonMatrix over unregistered backend "
                    f"{self.backend.name!r} cannot rebuild its "
                    "operators from substituted leaves")
        return self._ops

    @property
    def domain(self) -> str:
        return self.ops.domain

    def _batched(self, psi) -> bool:
        return psi.ndim == 7

    # complex-spinor interface ------------------------------------------

    def apply(self, psi):
        """``Dhat psi`` on complex even-half spinors; a leading ``nrhs``
        axis selects the batched kernels."""
        return self._complex_op(psi, self.ops.apply_dhat_native,
                                self.ops.apply_dhat_native_batched)

    __call__ = apply

    def dagger(self, psi):
        """``Dhat^dag psi`` (gamma5-hermiticity adjoint)."""
        return self._complex_op(psi, self.ops.apply_dhat_dagger_native,
                                self.ops.apply_dhat_dagger_native_batched)

    def normal(self, psi):
        """``Dhat^dag Dhat psi`` — the normal-equations operator the
        ``cg``/``cgnr`` methods iterate on."""
        return self.dagger(self.apply(psi))

    def _complex_op(self, psi, fn, fn_batched):
        ops = self.ops
        home = self.foreign_home(psi)
        v = self.encode(psi)
        out = (fn_batched if self._batched(psi) else fn)(v, self.kappa)
        if home is not None:
            out = jax.device_put(out, home)
        out = (ops.from_domain_batched(out) if self._batched(psi)
               else ops.from_domain(out))
        return out.astype(psi.dtype) if hasattr(psi, "dtype") else out

    # native-domain boundary --------------------------------------------

    def encode(self, psi):
        """Complex spinor -> native vector (batched by a leading axis).

        A source committed to a device the operator is not on (see
        :meth:`foreign_home`) is converted there; only the native vector
        moves to the operator's device (:meth:`place`)."""
        v = (self.ops.to_domain_batched(psi) if self._batched(psi)
             else self.ops.to_domain(psi))
        return v if self.foreign_home(psi) is None else self.place(v)

    def decode(self, v, dtype=jnp.complex64):
        """Native vector -> complex spinor."""
        batched = v.ndim == (7 if self.ops.domain == "complex" else 6)
        out = (self.ops.from_domain_batched(v) if batched
               else self.ops.from_domain(v))
        return out.astype(dtype)

    def _native_batched(self, v) -> bool:
        return v.ndim == (7 if self.ops.domain == "complex" else 6)

    def apply_native(self, v):
        fn = (self.ops.apply_dhat_native_batched
              if self._native_batched(v) else self.ops.apply_dhat_native)
        return fn(v, self.kappa)

    def dagger_native(self, v):
        fn = (self.ops.apply_dhat_dagger_native_batched
              if self._native_batched(v)
              else self.ops.apply_dhat_dagger_native)
        return fn(v, self.kappa)

    # deflation ---------------------------------------------------------

    def ensure_deflation(self, rank: int, mode: str = "lanczos", *,
                         checkpoint: Optional[str] = None,
                         lanczos_iters: Optional[int] = None):
        """The bound gauge's deflation state for ``(rank, mode)``,
        building it on first request and caching it on the matrix.

        ``mode="lanczos"`` runs the once-per-gauge Lanczos pass over the
        normal operator ``Dhat^dag Dhat`` (seeded deterministically from
        the lattice shape, so rebinding the same gauge reproduces the
        same basis); ``mode="recycle"`` starts empty and grows from
        harvested solutions (:meth:`repro.core.deflate.DeflationState.
        harvest_column`, driven by :class:`~repro.api.SolveSession`).
        ``checkpoint`` names a :class:`repro.resilience.BasisSnapshot`
        directory: a basis found there (matching shapes) is restored
        instead of rebuilt, and recycle harvests persist as they land.
        """
        rank = int(rank)
        if rank < 1:
            raise ValueError(f"deflation rank must be >= 1; got {rank}")
        key = (rank, str(mode), lanczos_iters)
        state = self._deflation.get(key)
        if state is not None:
            return state
        from repro.core import deflate as _defl
        ops = self.ops
        kappa = self.kappa

        def normal(v):
            return ops.apply_dhat_dagger_native(
                ops.apply_dhat_native(v, kappa), kappa)

        def normal_batched(v):
            return ops.apply_dhat_dagger_native_batched(
                ops.apply_dhat_native_batched(v, kappa), kappa)

        # Deterministic unit-norm start vector through the backend's
        # own encoder — native domain, fixed seed.
        psi = jax.random.normal(
            jax.random.PRNGKey(20240331),
            self.lattice.spinor_eo_shape() + (2,)).astype(jnp.float32)
        psi = jax.lax.complex(psi[..., 0], psi[..., 1])
        v0 = ops.to_domain(psi)

        snap = None
        if checkpoint is not None:
            from repro.resilience import BasisSnapshot
            snap = BasisSnapshot(checkpoint)
        template = _defl.empty_basis(rank, v0)
        restored = snap.resume(template) if snap is not None else None
        if mode == "lanczos":
            if restored is not None and _defl.DeflationBasis(
                    *restored).count() > 0:
                basis = _defl.DeflationBasis(*restored)
            else:
                basis = _defl.lanczos_basis(
                    normal, v0, rank, iters=lanczos_iters,
                    op_batched=normal_batched)
                if snap is not None:
                    snap.save(basis.count(), basis)
            state = _defl.DeflationState(basis, "lanczos", snapshot=snap)
        elif mode == "recycle":
            raw = (_defl.DeflationBasis(*restored)
                   if restored is not None else template)
            refine = _defl.make_ritz_refine(_defl.RECYCLE_QUALITY)
            basis = (_defl.DeflationBasis(*refine(raw))
                     if raw.count() > 0 else raw)
            # Top-of-spectrum estimate scales the Chebyshev harvest
            # filter (see make_recycle_update) — a dozen applies, once
            # per basis.
            lam = _defl.estimate_lambda_max(normal, v0)
            state = _defl.DeflationState(
                basis, "recycle",
                update_fn=_defl.make_recycle_update(
                    normal, lam_max=1.1 * lam),
                refine_fn=refine, snapshot=snap, raw=raw)
        else:
            raise ValueError(
                f"unknown deflation mode {mode!r}; choose 'lanczos' or "
                "'recycle'")
        self._deflation[key] = state
        return state

    # refined solves need the complex gauge back ------------------------

    def gauge_complex(self, dtype=jnp.complex128):
        """The complex even/odd gauge halves: the exact arrays the
        matrix was bound from when available, else reconstructed from
        the bound leaves.  The distinction matters for mixed-precision
        refined solves — leaves are rounded to the compute dtype (bf16
        planes deviate from the true gauge by ~1e-3), and the f64
        reference operator must target the *true* gauge, not the
        rounded one."""
        if self._exact_gauge is not None:
            U_e, U_o = self._exact_gauge
            return U_e.astype(dtype), U_o.astype(dtype)
        if not self._gauge:
            raise ValueError(
                "this WilsonMatrix was wrapped from bare ops without "
                "gauge arrays; pass gauge=(U_e, U_o) to from_ops (or "
                "use WilsonMatrix.bind) to enable refined solves")
        if self._gauge_form == "complex":
            U_e, U_o = self._gauge
            return U_e.astype(dtype), U_o.astype(dtype)
        u_e_p, u_o_p = self._gauge
        return (layout.gauge_from_planar(u_e_p, dtype),
                layout.gauge_from_planar(u_o_p, dtype))

    # --- pytree protocol ----------------------------------------------

    def tree_flatten(self):
        aux = (self.kappa, self.lattice, self.backend, self._gauge_form,
               self._rebuild, self._opaque)
        return self._gauge, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        kappa, lattice, backend, gauge_form, rebuild, opaque = aux
        ops = opaque.value if rebuild == "pinned" and opaque else None
        return cls(tuple(leaves), kappa, lattice, backend,
                   gauge_form=gauge_form, rebuild=rebuild, opaque=opaque,
                   ops=ops)

    def __repr__(self):
        lat = self.lattice.extents if self.lattice else None
        return (f"WilsonMatrix(backend={self.backend.name!r}, "
                f"kappa={self.kappa}, lattice={lat})")


jax.tree_util.register_pytree_node(
    WilsonMatrix,
    lambda m: m.tree_flatten(),
    WilsonMatrix.tree_unflatten)

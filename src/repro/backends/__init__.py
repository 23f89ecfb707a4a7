"""Unified operator-backend registry.

Every implementation of the even-odd Wilson hopping blocks — pure-XLA
complex arithmetic, the planar Pallas kernel, the fused single-kernel
Dhat, the shard_map'd distributed operator — registers here under a
string name and exposes the same bound-operator interface:

    bops = backends.make_wilson_ops("pallas_fused", U_e, U_o)
    psi_o = bops.hop_oe(psi_e)
    out   = bops.apply_dhat(psi_e, kappa)

so backend choice is a config/CLI string instead of hand-wired
callables.

Every backend declares its **native vector domain** — the layout its
kernels actually eat — and exposes an encode/decode boundary plus
native-domain operators:

    v    = bops.to_domain(psi)           # complex spinor -> native vector
    w    = bops.apply_dhat_native(v, kappa)
    psi2 = bops.from_domain(w)           # native vector -> complex spinor

``"jnp"`` is native in the complex even-odd interface (spinors
``(T, Z, Y, Xh, 4, 3)`` complex64, encode/decode are identity); the
Pallas backends are native in the planar re/im layout
(``(T, Z, 24, Y, Xh)`` float32, :mod:`repro.kernels.layout`); the
``distributed`` backend's domain is a *sharded* planar vector, placed on
the device mesh by ``to_domain`` so it stays there across calls.  Krylov solvers
(:func:`repro.core.solver.make_native_solve`, driven by
:class:`repro.api.SolveSession`) encode once at solve entry, iterate
entirely in the native domain, and decode once at exit — no
per-iteration layout churn or re-placement.

The complex-interface methods (``hop_oe``/``hop_eo``/``apply_dhat``/
``apply_dhat_dagger``) remain as thin ``from_domain . native . to_domain``
wrappers for backward compatibility; gauge preprocessing and gauge
placement still happen once at bind time inside the factory.

Built-in entries (see :mod:`repro.backends.wilson`):

* ``"jnp"``          — reference pure-XLA path (:mod:`repro.core.evenodd`);
* ``"pallas"``       — planar Pallas stencil, one kernel per hopping block;
* ``"pallas_fused"`` — Dhat as ONE kernel, intermediate VMEM-resident
  (three-way auto policy: falls to the streaming plane-window kernel
  when the resident scratch exceeds the budget, then to the two-kernel
  path);
* ``"pallas_fused_stream"`` — the plane-window kernel, forced: the VMEM
  scratch is a 4-row ring of odd-intermediate t-planes, so the local
  volume is never capped by T;
* ``"distributed"``  — shard_map over a device mesh.

Third parties extend via :func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

__all__ = ["WilsonOps", "BackendCapabilities", "register_backend",
           "get_backend", "available_backends", "backend_info",
           "make_wilson_ops", "prepare_gauge", "bind_native"]


def _identity(v):
    return v


def _vmap1(fn):
    """Batched fallback: vmap over the leading RHS axis (lazy import)."""
    def batched(v):
        import jax
        return jax.vmap(fn)(v)
    return batched


def _vmap1_kappa(fn):
    def batched(v, kappa):
        import jax
        return jax.vmap(fn, in_axes=(0, None))(v, kappa)
    return batched


@dataclasses.dataclass(frozen=True)
class WilsonOps:
    """Hopping-block operators bound to one gauge configuration.

    ``hop_oe`` / ``hop_eo`` map a complex even/odd spinor to the opposite
    parity; ``apply_dhat(psi_e, kappa)`` is the even-odd preconditioned
    operator ``(1 - kappa^2 H_eo H_oe) psi_e``; ``apply_dhat_dagger`` its
    adjoint (gamma5-hermiticity).

    ``domain`` names the backend's native vector layout;
    ``to_domain``/``from_domain`` encode/decode between the complex
    even-odd spinor interface and that layout, and the ``*_native``
    operators work directly on native vectors.  Backends constructed the
    pre-domain way (complex ops only) get an identity domain, so existing
    third-party factories keep working unchanged.

    **Multi-RHS batching:** the ``*_batched`` fields are the batched
    counterparts — a batched vector is the native vector with a *leading*
    ``nrhs`` axis (batched complex spinor: ``(nrhs, T, Z, Y, Xh, 4, 3)``).
    Backends with genuinely batched kernels (the Pallas stencils, which
    load each gauge plane once per grid step for the whole block; the
    distributed operator, which does one batched halo exchange) provide
    them; everyone else gets a correct-but-unamortized ``jax.vmap``
    fallback automatically, so batched solves work on any backend.
    """

    backend: str
    hop_oe: Callable        # psi_e -> psi_o
    hop_eo: Callable        # psi_o -> psi_e
    apply_dhat: Callable    # (psi_e, kappa) -> psi_e
    apply_dhat_dagger: Callable
    # --- native vector domain (encode once, iterate natively) ---------
    domain: str = "complex"
    to_domain: Callable = None      # psi -> v
    from_domain: Callable = None    # v -> psi
    hop_oe_native: Callable = None
    hop_eo_native: Callable = None
    apply_dhat_native: Callable = None
    apply_dhat_dagger_native: Callable = None
    # --- batched (multi-RHS) counterparts; leading nrhs axis ----------
    to_domain_batched: Callable = None
    from_domain_batched: Callable = None
    hop_oe_native_batched: Callable = None
    hop_eo_native_batched: Callable = None
    apply_dhat_native_batched: Callable = None
    apply_dhat_dagger_native_batched: Callable = None

    def __post_init__(self):
        # Legacy construction: complex interface IS the native domain.
        defaults = {"to_domain": _identity, "from_domain": _identity,
                    "hop_oe_native": self.hop_oe,
                    "hop_eo_native": self.hop_eo,
                    "apply_dhat_native": self.apply_dhat,
                    "apply_dhat_dagger_native": self.apply_dhat_dagger}
        given = [f for f in defaults if getattr(self, f) is not None]
        if given and len(given) < len(defaults):
            # A half-native construction would silently route complex
            # ops into the native iteration path; fail loudly instead.
            missing = sorted(set(defaults) - set(given))
            raise ValueError(
                f"backend {self.backend!r}: partial native-domain "
                f"construction — also provide {missing} (or none of "
                "the domain fields, for an identity domain); "
                "WilsonOps.from_native builds a consistent set")
        for field, default in defaults.items():
            if getattr(self, field) is None:
                object.__setattr__(self, field, default)
        # Batched fallbacks: identity encodes stay identity (they are
        # already shape-polymorphic); everything else vmaps the
        # unbatched native op over the leading RHS axis.  Individually
        # overridable — a backend with a truly batched kernel supplies
        # its own (see WilsonOps.from_native / repro.backends.wilson).
        batched_defaults = {
            "to_domain_batched": (
                self.to_domain if self.to_domain is _identity
                else _vmap1(self.to_domain)),
            "from_domain_batched": (
                self.from_domain if self.from_domain is _identity
                else _vmap1(self.from_domain)),
            "hop_oe_native_batched": _vmap1(self.hop_oe_native),
            "hop_eo_native_batched": _vmap1(self.hop_eo_native),
            "apply_dhat_native_batched": _vmap1_kappa(self.apply_dhat_native),
            "apply_dhat_dagger_native_batched":
                _vmap1_kappa(self.apply_dhat_dagger_native),
        }
        for field, default in batched_defaults.items():
            if getattr(self, field) is None:
                object.__setattr__(self, field, default)

    @classmethod
    def from_native(cls, backend: str, *, domain: str,
                    to_domain: Callable, from_domain: Callable,
                    hop_oe: Callable, hop_eo: Callable,
                    apply_dhat: Callable,
                    apply_dhat_dagger: Callable,
                    to_domain_batched: Callable = None,
                    from_domain_batched: Callable = None,
                    hop_oe_batched: Callable = None,
                    hop_eo_batched: Callable = None,
                    apply_dhat_batched: Callable = None,
                    apply_dhat_dagger_batched: Callable = None
                    ) -> "WilsonOps":
        """Build from native-domain operators; the complex-interface
        methods become thin encode/op/decode wrappers.

        The optional ``*_batched`` operators take/return native vectors
        with a leading ``nrhs`` axis; omitted ones fall back to a
        ``jax.vmap`` of the unbatched op (correct, but without the
        gauge-traffic amortization a truly batched kernel gives).
        """

        def wrap_hop(fn):
            def wrapped(psi):
                out = from_domain(fn(to_domain(psi)))
                # preserve the caller's complex dtype (e.g. complex128
                # under x64): the planar decode defaults to complex64
                return out.astype(psi.dtype) if hasattr(psi, "dtype") else out
            return wrapped

        def wrap_dhat(fn):
            def wrapped(psi, kappa):
                out = from_domain(fn(to_domain(psi), kappa))
                return out.astype(psi.dtype) if hasattr(psi, "dtype") else out
            return wrapped

        return cls(
            backend=backend,
            hop_oe=wrap_hop(hop_oe), hop_eo=wrap_hop(hop_eo),
            apply_dhat=wrap_dhat(apply_dhat),
            apply_dhat_dagger=wrap_dhat(apply_dhat_dagger),
            domain=domain, to_domain=to_domain, from_domain=from_domain,
            hop_oe_native=hop_oe, hop_eo_native=hop_eo,
            apply_dhat_native=apply_dhat,
            apply_dhat_dagger_native=apply_dhat_dagger,
            to_domain_batched=to_domain_batched,
            from_domain_batched=from_domain_batched,
            hop_oe_native_batched=hop_oe_batched,
            hop_eo_native_batched=hop_eo_batched,
            apply_dhat_native_batched=apply_dhat_batched,
            apply_dhat_dagger_native_batched=apply_dhat_dagger_batched)


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """Introspectable per-backend metadata on the registry.

    Consumed by :class:`repro.api.BackendSpec` validation, the CLI's
    ``--backend help`` listing, and :class:`repro.api.WilsonMatrix`
    (which uses ``gauge_form`` to decide what the pytree gauge leaves
    look like and how to rebuild operators from them).

    * ``domain`` — the native vector domain (``"complex"`` / ``"planar"``
      / ``"planar_sharded"``).
    * ``gauge_form`` — layout of the bound gauge arrays the backend's
      kernels actually read (``"complex"`` even/odd halves, or
      ``"planar"`` re/im component planes, possibly mesh-placed).
    * ``batched_kernels`` — True when the ``*_batched`` ops are genuinely
      batched kernels (gauge loaded once per grid step / one halo
      exchange per block) rather than the automatic ``jax.vmap``
      fallback.
    * ``dtypes`` — planar compute dtypes the factory's ``dtype=`` knob
      accepts; empty means the backend has no dtype knob (it follows the
      gauge dtype, like ``"jnp"``).
    * ``supports_interpret`` — whether the factory takes ``interpret=``
      (Pallas interpreter off-TPU).
    * ``interpret_only_dtypes`` — dtypes among ``dtypes`` that only the
      interpreter runs: the TPU compiler builds no kernel for them
      (f64 on the Pallas backends).
    * ``policies`` — the fused-Dhat execution paths the backend can take
      per application (policy introspection; ``"auto"`` means it picks
      among the others by VMEM footprint).
    * ``gauge_compressions`` — SU(3) link storage representations the
      factory's ``gauge_compression=`` knob accepts (``"none"`` full
      18-real links; ``"two_row"`` 12-real; ``"minimal"`` 8-real —
      compressed planes are expanded in-register by the kernels).
    * ``fallback`` — name of the next-best backend to rebind onto when
      this one fails to bind or compile (``None`` ends the chain).
      Declared here so the degradation order is registry data;
      :func:`repro.resilience.fallback_chain` walks the links and
      ``WilsonMatrix.bind(fallback=True)`` / ``SolveSession`` take
      them.
    """

    name: str
    domain: str = "complex"
    gauge_form: str = "complex"
    batched_kernels: bool = False
    dtypes: tuple = ()
    supports_interpret: bool = False
    interpret_only_dtypes: tuple = ()
    policies: tuple = ()
    gauge_compressions: tuple = ("none",)
    fallback: "str | None" = None
    description: str = ""


@dataclasses.dataclass(frozen=True)
class _BackendEntry:
    factory: Callable                    # (U_e, U_o, **opts) -> WilsonOps
    capabilities: BackendCapabilities
    # (gauge_leaves_tuple, **opts) -> WilsonOps, where the leaves are the
    # backend's *bound* gauge arrays (``capabilities.gauge_form``) — the
    # rebind path repro.api.WilsonMatrix uses so pytree-unflattened
    # matrices (jit arguments, tree_map results) reconstruct their
    # operators from leaves without re-doing layout conversion.
    native_factory: Callable = None
    # (U_e, U_o, **opts) -> gauge_leaves_tuple: the bind-once conversion
    # (layout packing, sharding placement) split out of ``factory``.
    prepare_gauge: Callable = None


# name -> _BackendEntry
_REGISTRY: Dict[str, _BackendEntry] = {}


def _default_prepare(U_e, U_o, **_opts):
    return (U_e, U_o)


def register_backend(name: str, factory: Callable, *,
                     capabilities: BackendCapabilities = None,
                     native_factory: Callable = None,
                     prepare_gauge: Callable = None,
                     overwrite: bool = False) -> None:
    """Register ``factory(U_e, U_o, **opts) -> WilsonOps`` under ``name``.

    ``capabilities`` (a :class:`BackendCapabilities`) is optional but
    recommended; without it the backend is assumed legacy-style (complex
    identity domain, no dtype/interpret knobs, vmap-batched).  The
    optional ``prepare_gauge`` / ``native_factory`` pair splits the
    factory into its bind-once gauge conversion and an operator build
    from already-converted gauge; backends that omit them default to
    complex gauge leaves rebuilt through ``factory`` itself, which keeps
    plain third-party factories fully usable from :mod:`repro.api`.
    """
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         "(pass overwrite=True to replace)")
    caps = capabilities or BackendCapabilities(name=name)
    _REGISTRY[name] = _BackendEntry(
        factory=factory, capabilities=caps,
        native_factory=native_factory or (
            lambda gauge, **opts: factory(*gauge, **opts)),
        prepare_gauge=prepare_gauge or _default_prepare)


def available_backends():
    """Registered backend names, **sorted** (stable across registration
    order, so CLI choices / docs / cache keys don't depend on import
    order)."""
    return sorted(_REGISTRY)


def _entry(name: str) -> _BackendEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{available_backends()} (see backend_info(name) for "
            "per-backend capabilities)") from None


def get_backend(name: str) -> Callable:
    """Resolve a backend factory by name."""
    return _entry(name).factory


def backend_info(name: str) -> BackendCapabilities:
    """Capability metadata for a registered backend."""
    return _entry(name).capabilities


def make_wilson_ops(name: str, U_e, U_o, **opts) -> WilsonOps:
    """Bind the named backend to a gauge configuration."""
    return get_backend(name)(U_e, U_o, **opts)


def prepare_gauge(name: str, U_e, U_o, **opts):
    """Run the named backend's bind-once gauge conversion (layout
    packing, sharding placement), returning the tuple of bound gauge
    arrays — the pytree leaves of a :class:`repro.api.WilsonMatrix`."""
    return tuple(_entry(name).prepare_gauge(U_e, U_o, **opts))


def bind_native(name: str, gauge, **opts) -> WilsonOps:
    """Build the named backend's operators from already-prepared gauge
    arrays (the output of :func:`prepare_gauge`); no layout conversion
    or placement happens here, so this is safe to call with tracers."""
    return _entry(name).native_factory(tuple(gauge), **opts)


# Built-in backends self-register on import.
from . import wilson as _wilson  # noqa: E402,F401

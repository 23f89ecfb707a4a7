"""Built-in Wilson operator backends: jnp / pallas / pallas_fused /
pallas_fused_stream / distributed, all bound through
:func:`repro.backends.register_backend`.

Factories take the complex even/odd gauge halves ``(4, T, Z, Y, Xh, 3, 3)``
and do their layout conversion / sharding once at bind time.  Each backend
declares its native vector domain (:class:`~repro.backends.WilsonOps`):

* ``"jnp"``          — native domain ``"complex"``; encode/decode are
  identity.
* ``"pallas"`` / ``"pallas_fused"`` / ``"pallas_fused_stream"`` — native
  domain ``"planar"``: the
  re/im-separated ``(T, Z, 24, Y, Xh)`` float layout the kernel eats
  (:mod:`repro.kernels.layout`).  The dagger acts on the planar
  spin-component planes directly (gamma5 = sign flip of planes 12..23),
  so native callers never touch complex arithmetic at all.
* ``"distributed"``  — native domain ``"planar_sharded"``: a planar
  vector placed on the device mesh by ``to_domain``; the operators run
  on already-placed arrays, so a solver iterating natively pays zero
  per-call ``device_put``/layout conversion.

The complex-interface methods remain as encode/op/decode wrappers, so a
solver written against one backend still runs on any of them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import evenodd, gamma
from repro.core.lattice import EXACT
from repro.kernels import layout, ops

from . import BackendCapabilities, WilsonOps, register_backend


def _dagger_via_gamma5(apply_dhat):
    """``Dhat^dag = g5 Dhat g5`` on the complex spinor interface."""
    g5 = jnp.asarray(gamma.GAMMA5)

    def fn(psi_e, kappa):
        gp = jnp.einsum("ij,...jc->...ic", g5, psi_e, precision=EXACT)
        return jnp.einsum("ij,...jc->...ic", g5, apply_dhat(gp, kappa),
                          precision=EXACT)

    return fn


def _dagger_via_gamma5_planar(apply_dhat_native):
    """``Dhat^dag = g5 Dhat g5`` natively on planar component planes."""
    def fn(v, kappa):
        return layout.gamma5_planar(
            apply_dhat_native(layout.gamma5_planar(v), kappa))

    return fn


def make_jnp_backend(U_e, U_o, **_unused) -> WilsonOps:
    """Pure-XLA reference path (complex arithmetic end to end)."""
    def apply_dhat(psi_e, kappa):
        return evenodd.apply_dhat(U_e, U_o, psi_e, kappa)

    return WilsonOps(
        backend="jnp",
        hop_oe=lambda psi_e: evenodd.hop_oe(U_e, U_o, psi_e),
        hop_eo=lambda psi_o: evenodd.hop_eo(U_e, U_o, psi_o),
        apply_dhat=apply_dhat,
        apply_dhat_dagger=_dagger_via_gamma5(apply_dhat),
        domain="complex")


def _pallas_prepare_gauge(U_e, U_o, *, dtype=jnp.float32,
                          gauge_compression: str = "none", **_unused):
    """Bind-once layout conversion of the pallas-family backends.

    ``gauge_compression`` selects the stored link representation ("none"
    | "two_row" | "minimal"); the compressed planes are what lives in
    the ``WilsonMatrix`` pytree leaves (~33%/55% fewer gauge bytes) and
    the kernels expand them in-register.
    """
    return ops.make_planar_fields(U_e, U_o, dtype=dtype,
                                  compression=gauge_compression)


def _make_pallas_from_planar(u_e_p, u_o_p, *, fused,
                             interpret: Optional[bool] = None,
                             name: str) -> WilsonOps:
    # ``fused``: None (three-way auto policy), True/"resident",
    # "stream", or False/"unfused" — forwarded per call to
    # ops.apply_dhat_planar_any so the policy sees the actual
    # (possibly batched) vector shape.
    def to_domain(psi):
        return layout.spinor_to_planar(psi, dtype=u_e_p.dtype)

    def from_domain(v):
        return layout.spinor_from_planar(v)

    def hop_oe(v):
        return ops.hop_block(u_o_p, u_e_p, v, out_parity=evenodd.ODD,
                             interpret=interpret)

    def hop_eo(v):
        return ops.hop_block(u_e_p, u_o_p, v, out_parity=evenodd.EVEN,
                             interpret=interpret)

    def apply_dhat(v, kappa):
        return ops.apply_dhat_planar_any(u_e_p, u_o_p, v, kappa,
                                         fused=fused, interpret=interpret)

    dagger = _dagger_via_gamma5_planar(apply_dhat)
    # The planar kernels (and the layout codecs) are batch-polymorphic:
    # a leading nrhs axis runs ONE kernel with each gauge plane loaded
    # once per grid step, so the batched ops ARE the unbatched ops.
    return WilsonOps.from_native(
        name, domain="planar",
        to_domain=to_domain, from_domain=from_domain,
        hop_oe=hop_oe, hop_eo=hop_eo, apply_dhat=apply_dhat,
        apply_dhat_dagger=dagger,
        to_domain_batched=to_domain, from_domain_batched=from_domain,
        hop_oe_batched=hop_oe, hop_eo_batched=hop_eo,
        apply_dhat_batched=apply_dhat, apply_dhat_dagger_batched=dagger)


def _make_pallas(U_e, U_o, *, fused, interpret: Optional[bool] = None,
                 name: str, dtype=jnp.float32,
                 gauge_compression: str = "none") -> WilsonOps:
    u_e_p, u_o_p = _pallas_prepare_gauge(
        U_e, U_o, dtype=dtype, gauge_compression=gauge_compression)
    return _make_pallas_from_planar(u_e_p, u_o_p, fused=fused,
                                    interpret=interpret, name=name)


def _pallas_native_factory(fused, name):
    """Rebind a pallas-family backend from already-planar gauge leaves
    (``dtype`` is baked into the leaves, so it is accepted and ignored)."""
    def native(gauge, *, interpret=None, dtype=None, **_unused):
        del dtype
        return _make_pallas_from_planar(*gauge, fused=fused,
                                        interpret=interpret, name=name)
    return native


def make_pallas_backend(U_e, U_o, *, interpret=None, dtype=jnp.float32,
                        gauge_compression="none", **_unused) -> WilsonOps:
    """Planar Pallas stencil, one ``pallas_call`` per hopping block.

    ``dtype`` sets the planar compute dtype (f32 default; bf16 for the
    mixed-precision inner solve).  ``gauge_compression`` stores 12-real
    (two_row) or 8-real (minimal) links, expanded in-register.
    """
    return _make_pallas(U_e, U_o, fused=False, interpret=interpret,
                        name="pallas", dtype=dtype,
                        gauge_compression=gauge_compression)


def make_pallas_fused_backend(U_e, U_o, *, interpret=None,
                              dtype=jnp.float32, gauge_compression="none",
                              **_unused) -> WilsonOps:
    """Dhat as a single fused kernel; intermediate never touches HBM.

    Auto-selects the three-way fused policy (``fused=None`` in
    :func:`repro.kernels.ops.apply_dhat_planar_any`): the VMEM-resident
    single kernel when the whole (batched) intermediate fits the scratch
    budget — sized by the actual compute ``dtype`` and the RHS batch —
    the streaming plane-window kernel when only its t-plane ring does,
    and the two-kernel path as the last silent-correct fallback.
    """
    return _make_pallas(U_e, U_o, fused=None, interpret=interpret,
                        name="pallas_fused", dtype=dtype,
                        gauge_compression=gauge_compression)


def make_pallas_fused_stream_backend(U_e, U_o, *, interpret=None,
                                     dtype=jnp.float32,
                                     gauge_compression="none",
                                     **_unused) -> WilsonOps:
    """Streaming plane-window fused Dhat, forced (no auto-policy).

    One kernel per application whose VMEM scratch is a
    :data:`~repro.kernels.wilson_stencil.STREAM_WINDOW_ROWS`-row ring of
    odd-intermediate t-planes — the working set is independent of T, so
    there is no resident-scratch local-volume cap.  Selecting this
    backend by name pins the streaming kernel even for lattices the
    resident scratch could hold (useful for benchmarking the window
    overhead); the ``pallas_fused`` backend auto-picks between the two.
    """
    return _make_pallas(U_e, U_o, fused="stream", interpret=interpret,
                        name="pallas_fused_stream", dtype=dtype,
                        gauge_compression=gauge_compression)


def _normalize_overlap(overlap):
    """Accept the boolean comms/compute-overlap knob.

    ``True`` means "overlap the halo exchange with interior compute" —
    the ``"interior"`` mode of :mod:`repro.distributed.qcd`; ``False``
    means the serialized batched exchange (``"fused"``).  String modes
    pass through.
    """
    if overlap is True:
        return "interior"
    if overlap is False:
        return "fused"
    return overlap


def make_distributed_backend(U_e, U_o, *, partition=None, mesh=None,
                             local_backend: str = "jnp_planar",
                             overlap: str = "fused",
                             interpret: Optional[bool] = None,
                             dtype=jnp.float32,
                             gauge_compression: str = "none",
                             **_unused) -> WilsonOps:
    """shard_map'd operator over a device mesh.

    Accepts an explicit :class:`repro.distributed.qcd.QCDPartition` (or a
    mesh to derive one from); defaults to all local devices on a
    ``(data, model)`` mesh.  The gauge field is planarized and placed with
    the partition's sharding once, here.  The native domain is a *sharded
    planar* spinor: ``to_domain`` planarizes and places onto the mesh,
    after which the native operators run with no per-call conversion or
    ``device_put`` — a natively-iterating solver keeps the field resident
    on the mesh for the whole solve.  (The complex-interface methods
    re-encode per call, as before.)

    ``local_backend`` defaults to ``"jnp_planar"`` — the planar-native
    pure-XLA stencil — so the per-rank compute is conversion-free too;
    ``"jnp"`` (complex round-trip inside the shard, the old default) and
    ``"pallas"`` remain selectable.

    ``overlap`` picks the halo/stencil schedule: ``"fused"`` (default,
    one batched exchange serialized against the full stencil),
    ``"interior"`` (issue the exchange first and run the interior
    stencil while it is in flight — the comms/compute-overlap mode; also
    selectable as ``overlap=True``), or ``"split"`` (legacy recompute
    split).  ``gauge_compression`` stores AND ships compressed links:
    the halo exchange moves the compressed planes, so gauge halo traffic
    shrinks with the storage (~33% two_row / ~55% minimal).
    """
    overlap = _normalize_overlap(overlap)
    u_e_p, u_o_p = _distributed_prepare_gauge(
        U_e, U_o, partition=partition, mesh=mesh,
        local_backend=local_backend, overlap=overlap,
        interpret=interpret, dtype=dtype,
        gauge_compression=gauge_compression)
    return _make_distributed_from_planar(
        u_e_p, u_o_p, partition=partition, mesh=mesh,
        local_backend=local_backend, overlap=overlap, interpret=interpret)


# A bind resolves its partition twice (prepare_gauge places the gauge,
# the native factory builds the shard_map'd operators); memoize so both
# get the SAME partition object and the mesh/sharding setup runs once.
_PARTITION_MEMO = {}


def _resolve_partition(partition, mesh, local_backend, overlap, interpret):
    from repro.distributed import qcd  # local import: shard_map machinery

    if partition is not None:
        return partition
    overlap = _normalize_overlap(overlap)
    key = (mesh if mesh is not None else ("default", jax.device_count()),
           local_backend, overlap, interpret)
    if key not in _PARTITION_MEMO:
        m = mesh
        if m is None:
            m = compat.make_mesh((jax.device_count(), 1),
                                 ("data", "model"))
        _PARTITION_MEMO[key] = qcd.QCDPartition.for_mesh(
            m, backend=local_backend, overlap=overlap,
            interpret=interpret)
    return _PARTITION_MEMO[key]


def _distributed_prepare_gauge(U_e, U_o, *, partition=None, mesh=None,
                               local_backend: str = "jnp_planar",
                               overlap: str = "fused", interpret=None,
                               dtype=jnp.float32,
                               gauge_compression: str = "none",
                               **_unused):
    """Bind-once gauge work of the distributed backend: planarize,
    optionally compress, AND place on the device mesh.

    Compression happens *before* placement, so the mesh-resident leaves
    — and every halo exchange of gauge planes derived from them — carry
    the compressed representation.
    """
    partition = _resolve_partition(partition, mesh, local_backend,
                                   overlap, interpret)
    u_e_p, u_o_p = ops.make_planar_fields(U_e, U_o, dtype=dtype,
                                          compression=gauge_compression)
    u_e_p = jax.device_put(u_e_p, partition.gauge_sharding())
    u_o_p = jax.device_put(u_o_p, partition.gauge_sharding())
    return u_e_p, u_o_p


def _make_distributed_from_planar(u_e_p, u_o_p, *, partition=None,
                                  mesh=None,
                                  local_backend: str = "jnp_planar",
                                  overlap: str = "fused",
                                  interpret=None, dtype=None,
                                  **_unused) -> WilsonOps:
    """Operators from already-planarized-and-placed gauge fields (the
    rebind path; no placement happens here)."""
    del dtype  # baked into the planar leaves
    from repro.distributed import qcd

    overlap = _normalize_overlap(overlap)
    partition = _resolve_partition(partition, mesh, local_backend,
                                   overlap, interpret)
    sp_shard = partition.spinor_sharding()
    bsp_shard = partition.batched_spinor_sharding()

    hop_fns = {(p, b): jax.jit(qcd.make_hop_fn(partition, p, batched=b))
               for p in (evenodd.EVEN, evenodd.ODD)
               for b in (False, True)}
    dhat_cache = {}

    def to_domain(psi):
        return jax.device_put(
            layout.spinor_to_planar(psi, dtype=u_e_p.dtype), sp_shard)

    def from_domain(v):
        return layout.spinor_from_planar(v)

    def to_domain_batched(psi):
        # One placement for the whole RHS block.
        return jax.device_put(
            layout.spinor_to_planar(psi, dtype=u_e_p.dtype), bsp_shard)

    def hop_oe(v):
        # H_oe reads even-parity gauge links as u_in, writes odd sites.
        return hop_fns[evenodd.ODD, False](u_o_p, u_e_p, v)

    def hop_eo(v):
        return hop_fns[evenodd.EVEN, False](u_e_p, u_o_p, v)

    def hop_oe_batched(v):
        return hop_fns[evenodd.ODD, True](u_o_p, u_e_p, v)

    def hop_eo_batched(v):
        return hop_fns[evenodd.EVEN, True](u_e_p, u_o_p, v)

    def _dhat(v, kappa, batched):
        k = (float(kappa), batched)
        if k not in dhat_cache:
            dhat_cache[k] = jax.jit(
                qcd.make_dhat_fn(partition, k[0], batched=batched))
        return dhat_cache[k](u_e_p, u_o_p, v)

    def apply_dhat(v, kappa):
        return _dhat(v, kappa, False)

    def apply_dhat_batched(v, kappa):
        # The batched operator's halo exchange moves the whole RHS block
        # in one ppermute per face — not nrhs exchanges.
        return _dhat(v, kappa, True)

    return WilsonOps.from_native(
        "distributed", domain="planar_sharded",
        to_domain=to_domain, from_domain=from_domain,
        hop_oe=hop_oe, hop_eo=hop_eo, apply_dhat=apply_dhat,
        apply_dhat_dagger=_dagger_via_gamma5_planar(apply_dhat),
        to_domain_batched=to_domain_batched,
        from_domain_batched=from_domain,
        hop_oe_batched=hop_oe_batched, hop_eo_batched=hop_eo_batched,
        apply_dhat_batched=apply_dhat_batched,
        apply_dhat_dagger_batched=_dagger_via_gamma5_planar(
            apply_dhat_batched))


_GAUGE_COMPRESSIONS = ("none", "two_row", "minimal")

_PALLAS_DTYPES = ("f32", "bf16", "f64")

register_backend(
    "jnp", make_jnp_backend,
    capabilities=BackendCapabilities(
        name="jnp", domain="complex", gauge_form="complex",
        batched_kernels=False, dtypes=(), supports_interpret=False,
        policies=(),
        description="pure-XLA complex reference path (compute dtype "
                    "follows the gauge dtype; batched ops are a vmap "
                    "fallback)"),
    native_factory=lambda gauge, **opts: make_jnp_backend(*gauge),
    prepare_gauge=lambda U_e, U_o, **_: (U_e, U_o))
register_backend(
    "pallas", make_pallas_backend,
    capabilities=BackendCapabilities(
        name="pallas", domain="planar", gauge_form="planar",
        batched_kernels=True, dtypes=_PALLAS_DTYPES,
        supports_interpret=True, interpret_only_dtypes=("f64",),
        policies=("unfused",),
        gauge_compressions=_GAUGE_COMPRESSIONS, fallback="jnp",
        description="planar Pallas stencil, one kernel per hopping "
                    "block (two kernels per Dhat)"),
    native_factory=_pallas_native_factory(False, "pallas"),
    prepare_gauge=_pallas_prepare_gauge)
register_backend(
    "pallas_fused", make_pallas_fused_backend,
    capabilities=BackendCapabilities(
        name="pallas_fused", domain="planar", gauge_form="planar",
        batched_kernels=True, dtypes=_PALLAS_DTYPES,
        supports_interpret=True, interpret_only_dtypes=("f64",),
        policies=("auto", "resident", "stream", "unfused"),
        gauge_compressions=_GAUGE_COMPRESSIONS, fallback="pallas",
        description="Dhat as ONE kernel; three-way auto policy sized by "
                    "dtype and nrhs (resident VMEM scratch -> streaming "
                    "plane window -> two-kernel fallback)"),
    native_factory=_pallas_native_factory(None, "pallas_fused"),
    prepare_gauge=_pallas_prepare_gauge)
register_backend(
    "pallas_fused_stream", make_pallas_fused_stream_backend,
    capabilities=BackendCapabilities(
        name="pallas_fused_stream", domain="planar", gauge_form="planar",
        batched_kernels=True, dtypes=_PALLAS_DTYPES,
        supports_interpret=True, interpret_only_dtypes=("f64",),
        policies=("stream",),
        gauge_compressions=_GAUGE_COMPRESSIONS,
        fallback="pallas_fused",
        description="streaming plane-window fused Dhat, forced: VMEM "
                    "holds a 4-row ring of odd-intermediate t-planes "
                    "(no T-dependent volume cap)"),
    native_factory=_pallas_native_factory("stream", "pallas_fused_stream"),
    prepare_gauge=_pallas_prepare_gauge)
register_backend(
    "distributed", make_distributed_backend,
    capabilities=BackendCapabilities(
        name="distributed", domain="planar_sharded",
        gauge_form="planar_sharded", batched_kernels=True,
        dtypes=_PALLAS_DTYPES, supports_interpret=True,
        policies=("local:jnp_planar", "local:jnp", "local:pallas",
                  "overlap:fused", "overlap:interior",
                  "overlap:split"),
        gauge_compressions=_GAUGE_COMPRESSIONS, fallback="jnp",
        description="shard_map over a device mesh with z/t halo "
                    "exchange; gauge placed once at bind, one batched "
                    "exchange per RHS block (overlappable with the "
                    "interior stencil; links shippable compressed)"),
    native_factory=lambda gauge, **opts: _make_distributed_from_planar(
        *gauge, **opts),
    prepare_gauge=_distributed_prepare_gauge)

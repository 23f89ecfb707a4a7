"""Jaxpr-level invariant analyzers (the "J" checks).

Where :mod:`repro.analysis.lint` reads the *source*, these checks read
the *traces*: they build tiny-lattice operators through the public
registry/API, trace them with ``jax.make_jaxpr``, and assert the
properties the performance story depends on but no numeric tolerance
can see:

* **J1 — conversion-free native iterate.**  The traced native-domain
  solve pipeline of every registered backend contains no
  ``convert_element_type`` on spinor-sized operands — except the
  compensated-reduction upcasts (narrow float → f32/f64), which are the
  point of :data:`repro.core.solver.COMPENSATED_REDUCTIONS`.
* **J2 — exact pallas_call counts.**  One Dhat application traces to
  exactly 1 ``pallas_call`` on the ``resident`` and ``stream`` fused
  branches and exactly 2 on the ``unfused`` branch.  A refactor that
  silently un-fuses (or double-launches) shows up here, not in any
  parity test.
* **J3 — VMEM model cross-check.**  The static scratch-byte estimates
  (:func:`~repro.kernels.wilson_stencil.fused_dhat_fits`,
  :func:`~repro.kernels.wilson_stencil.stream_ring_bytes`,
  :func:`~repro.kernels.wilson_stencil.fused_dhat_policy`,
  :func:`~repro.kernels.wilson_stencil.dhat_stream_traffic_model`)
  agree with an independently-computed count of the tiled VMEM the
  TPU compiler allocates, switch exactly at the per-kernel limit, and
  the stream ring is T-independent.
* **J4 — retrace budget.**  A replayed :class:`repro.api.SolveSession`
  scenario (repeat solves, a shape change, a spec change) performs
  exactly as many traces as distinct cache keys — the bind-once
  contract expressed as a hard number.
* **J6 — divergence guard in every Krylov loop.**  Every
  ``while_loop`` traced out of :func:`repro.core.solver._run_krylov`
  (all methods, batched and unbatched) carries an ``is_finite``
  primitive in its *cond* jaxpr — the structural footprint of the
  non-finite divergence guard.  A refactor that drops the guard turns
  a single SDC-corrupted residual back into max_iters of silent NaN
  iterations; it shows up here, not in any healthy-path test.
* **J5 — comms/compute overlap schedule.**  The distributed operator
  traced with ``overlap="interior"`` keeps its interior kernels
  *independent* of the in-flight halo exchange: inside the
  ``shard_map`` body, exactly 2 ``pallas_call``s (one interior pass per
  hopping block) whose inputs are NOT data-dependent on any
  ``ppermute`` result (checked by taint propagation over the body
  jaxpr), with all four face exchanges per hop actually present.  A
  refactor that re-serializes the exchange before the main kernel
  shows up here, not in any timing noise.

Every check takes injectable overrides (a wrapped ops factory, a
replacement policy function, a sabotaged session factory) so the test
suite can demonstrate each one *failing* on a seeded violation, not
just passing on the healthy tree.

All checks run on a 4x4x4x8 lattice and only *trace* (no kernel
executes except J4's interpret-mode solves), so the whole layer is
CI-cheap.
"""
from __future__ import annotations

import math
from typing import Callable, Iterable, List, Optional, Sequence

from .findings import Finding

# Findings are anchored at the definition site of the invariant's
# subject so ``file:line`` in the report jumps somewhere actionable.
_ANCHORS = {
    "J1": ("src/repro/core/solver.py", "def make_native_solve"),
    "J2": ("src/repro/kernels/ops.py", "def apply_dhat_planar_any"),
    "J3": ("src/repro/kernels/wilson_stencil.py", "def fused_dhat_policy"),
    "J4": ("src/repro/api/session.py", "class SolveSession"),
    "J5": ("src/repro/distributed/qcd.py", "def make_dhat_fn"),
    "J6": ("src/repro/core/solver.py", "def _run_krylov"),
}

ALL_JAXPR_CHECKS = ("J1", "J2", "J3", "J4", "J5", "J6")

_LATTICE = (4, 4, 4, 8)          # (X, Y, Z, T) — matches the test suite
_KAPPA = 0.13


def _anchor(root: str, check: str):
    """(path, line) of the invariant's subject, by source search."""
    import os
    rel, needle = _ANCHORS[check]
    path = os.path.join(root, rel)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            for i, text in enumerate(fh, start=1):
                if needle in text:
                    return rel, i
    except OSError:
        pass
    return rel, 1


def _finding(root, check, message) -> Finding:
    rel, line = _anchor(root, check)
    return Finding(rule=check, path=rel, line=line, message=message)


# --- shared tiny-lattice fixtures ------------------------------------


def _tiny_eo(seed: int = 2):
    import jax
    import jax.numpy as jnp
    from repro.core import evenodd, su3

    U = su3.random_gauge(jax.random.PRNGKey(seed), _LATTICE)
    k1, k2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
    psi = (jax.random.normal(k1, (*_LATTICE, 4, 3))
           + 1j * jax.random.normal(k2, (*_LATTICE, 4, 3))
           ).astype(jnp.complex64)
    e, o = evenodd.pack(psi)
    Ue, Uo = evenodd.pack_gauge(U)
    return Ue, Uo, e, o


def _bind(name: str, Ue, Uo):
    """Registry bind, interpret-mode for Pallas backends off-TPU."""
    import jax
    from repro import backends

    opts = ({"interpret": True} if name.startswith("pallas")
            and jax.default_backend() != "tpu" else {})
    return backends.make_wilson_ops(name, Ue, Uo, **opts)


def _walk_eqns(jaxpr):
    """Depth-first over every eqn of a jaxpr and all nested sub-jaxprs
    (while bodies, pjit calls, pallas_call kernels, ...).

    Deliberately NOT deduplicated by sub-jaxpr identity: two call sites
    of one cached pjit share the same ClosedJaxpr object, and J2 must
    count each *launch*, not each distinct kernel body.  Jaxprs are
    acyclic, so per-reference traversal terminates.
    """
    from jax.extend import core as jcore

    agenda = [jaxpr]
    while agenda:
        jx = agenda.pop()
        if isinstance(jx, jcore.ClosedJaxpr):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            yield eqn
            for val in eqn.params.values():
                for sub in _as_jaxprs(val):
                    agenda.append(sub)


def _as_jaxprs(val):
    from jax.extend import core as jcore

    if isinstance(val, (jcore.Jaxpr, jcore.ClosedJaxpr)):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _as_jaxprs(item)


# --- J1: conversion-free native iterate ------------------------------

# Operands at or above this many elements are "spinor-sized"; scalars
# and per-iteration reduction results stay exempt.
_J1_MIN_ELEMENTS = 1024

_FLOAT_WIDTH = {"bfloat16": 16, "float16": 16, "float32": 32,
                "float64": 64}


def _is_compensated_upcast(old_dtype, new_dtype) -> bool:
    """Narrow-float → wider-float: the compensated-reduction pattern."""
    ow = _FLOAT_WIDTH.get(str(old_dtype))
    nw = _FLOAT_WIDTH.get(str(new_dtype))
    return ow is not None and nw is not None and nw > ow


def check_conversion_free(root: str, *,
                          backends: Optional[Sequence[str]] = None,
                          ops_transform: Optional[Callable] = None,
                          method: str = "cgnr") -> List[Finding]:
    """J1: the traced native solve has no layout/precision churn.

    ``ops_transform(bops) -> bops`` lets the self-tests seed a
    violation (e.g. wrap ``apply_dhat_native`` in a bf16 round-trip).
    """
    import jax
    from repro.core import solver

    if backends is None:
        from repro import backends as breg
        backends = breg.available_backends()

    Ue, Uo, e, o = _tiny_eo()
    findings: List[Finding] = []
    for name in backends:
        bops = _bind(name, Ue, Uo)
        if ops_transform is not None:
            bops = ops_transform(bops)
        solve = solver.make_native_solve(bops, _KAPPA, method=method,
                                         tol=1e-6, max_iters=8)
        v_e, v_o = bops.to_domain(e), bops.to_domain(o)
        jaxpr = jax.make_jaxpr(solve)(v_e, v_o)
        for eqn in _walk_eqns(jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            operand = eqn.invars[0].aval
            if math.prod(operand.shape) < _J1_MIN_ELEMENTS:
                continue
            new_dtype = eqn.params.get("new_dtype")
            if _is_compensated_upcast(operand.dtype, new_dtype):
                continue
            findings.append(_finding(
                root, "J1",
                f"backend {name!r} ({method}): convert_element_type "
                f"{operand.dtype} -> {new_dtype} on a "
                f"{tuple(operand.shape)} operand inside the native "
                "solve trace — the Krylov iterate must stay in the "
                "backend's native layout/precision (only "
                "compensated-reduction float upcasts are exempt)"))
            break   # one finding per backend is enough signal
    return findings


# --- J2: exact pallas_call counts per fused branch -------------------

EXPECTED_PALLAS_CALLS = {"resident": 1, "stream": 1, "unfused": 2}


GAUGE_COMPRESSION_AXES = ("none", "two_row", "minimal")


def check_pallas_counts(root: str, *,
                        apply_fn: Optional[Callable] = None,
                        expected: Optional[dict] = None,
                        compressions: Optional[Sequence[str]] = None,
                        ) -> List[Finding]:
    """J2: each fused-policy branch launches its exact kernel count.

    The counts must hold for every stored gauge representation (18/12/8
    real link planes): in-register reconstruction may not add kernel
    launches.  ``apply_fn(u_e_p, u_o_p, src_p, kappa, fused=...)``
    overrides the traced entry point so the self-tests can seed a
    double launch (``compressions`` narrows the sweep for those).
    """
    import jax
    from repro.kernels import layout
    from repro.kernels import ops as kops

    if expected is None:
        expected = EXPECTED_PALLAS_CALLS
    if compressions is None:
        compressions = GAUGE_COMPRESSION_AXES
    if apply_fn is None:
        def apply_fn(u_e_p, u_o_p, src_p, kappa, fused):
            return kops.apply_dhat_planar_any(
                u_e_p, u_o_p, src_p, kappa, fused=fused, interpret=True)

    Ue, Uo, e, _ = _tiny_eo()
    u_e_18, u_o_18 = layout.gauge_to_planar(Ue), layout.gauge_to_planar(Uo)
    src_p = layout.spinor_to_planar(e)

    findings: List[Finding] = []
    for compression in compressions:
        if compression == "none":
            u_e_p, u_o_p = u_e_18, u_o_18
        else:
            u_e_p = layout.gauge_compress_planar(u_e_18, compression)
            u_o_p = layout.gauge_compress_planar(u_o_18, compression)
        for branch, want in sorted(expected.items()):
            jaxpr = jax.make_jaxpr(
                lambda s: apply_fn(u_e_p, u_o_p, s, _KAPPA, branch))(src_p)
            got = sum(1 for eqn in _walk_eqns(jaxpr)
                      if eqn.primitive.name == "pallas_call")
            if got != want:
                findings.append(_finding(
                    root, "J2",
                    f"fused={branch!r} (gauge_compression="
                    f"{compression!r}): one Dhat application traced to "
                    f"{got} pallas_call(s), expected exactly {want} — a "
                    "silent un-fusing (or double launch) changes the "
                    "HBM traffic story without failing any parity "
                    "test"))
    return findings


# --- J3: static VMEM estimates cross-checked -------------------------


def check_vmem_model(root: str, *,
                     fits_fn: Optional[Callable] = None,
                     ring_fn: Optional[Callable] = None,
                     policy_fn: Optional[Callable] = None,
                     headroom_fn: Optional[Callable] = None,
                     limit_bytes: Optional[int] = None) -> List[Finding]:
    """J3: the policy's byte math agrees with an independent estimate.

    The estimate here recounts what the TPU compiler allocates: every
    ``(Y, Xh)`` plane padded to whole (sublane, 128-lane) tiles, the
    double-buffered pipeline blocks read off each fused kernel's specs,
    and the body spills, against the per-kernel VMEM limit.  The
    override hooks substitute any one estimator so the self-tests can
    seed an inconsistency (e.g. a policy that streams too early).
    """
    import jax.numpy as jnp
    from repro.kernels import wilson_stencil as ws

    fits = fits_fn or ws.fused_dhat_fits
    ring = ring_fn or ws.stream_ring_bytes
    policy = policy_fn or ws.fused_dhat_policy
    limit = ws._VMEM_LIMIT_BYTES if limit_bytes is None else limit_bytes
    window = ws.STREAM_WINDOW_ROWS
    findings: List[Finding] = []

    def tiled_plane(Y, Xh, itemsize):
        rows = 8 * (4 // itemsize) if itemsize < 4 else 8
        return (itemsize * ((Y + rows - 1) // rows * rows)
                * ((Xh + 127) // 128 * 128))

    # (spinor blocks, gauge plane-sets) per grid step, from the kernels'
    # specs: resident = 5 psi views + out, u_out + z/t views per parity;
    # stream = 6 psi views + out, 4 quartets + 4 shifted views.
    blocks = {"resident": (6, 12), "stream": (7, 20)}

    def budget(kernel, shape, itemsize, gc=18):
        nrhs = shape[0] if len(shape) == 6 else 1
        Y, Xh = shape[-2], shape[-1]
        spin, sets = blocks[kernel]
        pipeline = 2 * (spin * nrhs * 24 + sets * gc) * tiled_plane(
            Y, Xh, itemsize)
        spill = ws._BODY_SPILL_PLANES * nrhs * tiled_plane(
            Y, Xh, max(itemsize, 4))
        return limit - pipeline - spill

    def planes(shape):
        # Tiled planes of the (possibly batched) planar intermediate and
        # of one of its t-rows: everything except (Y, Xh), and also T.
        lead = 1 if len(shape) == 6 else 0
        total = math.prod(shape[:-2])
        return total, total // shape[lead]

    def estimate(shape, itemsize, gc=18):
        total, row = planes(shape)
        plane = tiled_plane(shape[-2], shape[-1], itemsize)
        resident, ringsz = total * plane, window * row * plane
        res_ok = resident <= budget("resident", shape, itemsize, gc)
        ring_ok = ringsz <= budget("stream", shape, itemsize, gc)
        want = ("resident" if res_ok else
                "stream" if ring_ok else "unfused")
        return resident, ringsz, res_ok, want

    # Shapes straddling the budget: resident fits / only the ring fits /
    # nothing fits, plus exact-boundary rows for the <= vs < distinction.
    row4 = 4 * 24 * tiled_plane(4, 4, 4)             # f32 (T,4,24,4,4)
    T_at_limit = budget("resident", (1, 4, 24, 4, 4), 4) // row4
    cases = [
        (4, 4, 24, 4, 2), (8, 8, 24, 8, 4),
        (T_at_limit, 4, 24, 4, 4),          # last t-row that fits
        (T_at_limit + 1, 4, 24, 4, 4),      # one row over
        (4096, 8, 24, 8, 4),                # huge T: stream territory
        (2, 4096, 24, 64, 64),              # huge plane: unfused
        (8, 4, 4, 24, 4, 2),                # batched nrhs=8
    ]
    for shape in cases:
        for dtype in (jnp.float32, jnp.bfloat16):
            itemsize = jnp.dtype(dtype).itemsize
            resident, ringsz, res_ok, want_policy = estimate(
                shape, itemsize)

            if fits(shape, dtype) != res_ok:
                findings.append(_finding(
                    root, "J3",
                    f"fused_dhat_fits({shape}, {jnp.dtype(dtype).name}) "
                    f"disagrees with the independent estimate "
                    f"{resident}B vs budget "
                    f"{budget('resident', shape, itemsize)}B"))
            got_ring = ring(shape, dtype)
            if got_ring != ringsz:
                findings.append(_finding(
                    root, "J3",
                    f"stream_ring_bytes({shape}, "
                    f"{jnp.dtype(dtype).name}) = {got_ring}, "
                    f"independent estimate {ringsz} "
                    f"({window} rows x {planes(shape)[1]} tiled planes)"))
            got_policy = policy(shape, dtype)
            if got_policy != want_policy:
                findings.append(_finding(
                    root, "J3",
                    f"fused_dhat_policy({shape}, "
                    f"{jnp.dtype(dtype).name}) = {got_policy!r}, but "
                    f"the byte math (resident {resident}B, ring "
                    f"{ringsz}B, limit {limit}B) says {want_policy!r}"))

    # The cap-lift itself: the ring must not grow with T.
    if ring((8, 8, 24, 8, 4)) != ring((4096, 8, 24, 8, 4)):
        findings.append(_finding(
            root, "J3",
            "stream_ring_bytes grew with T — the plane-window ring is "
            "supposed to be T-independent (that is the VMEM cap-lift)"))

    # Compressed-gauge headroom: storing 12/8 of 18 real link planes
    # frees VMEM in the double-buffered gauge window (12 plane-sets in
    # flight per grid step of the resident kernel); fits/policy must
    # extend the scratch budget by exactly that headroom — and
    # gauge_comps=18 must be a strict no-op on every boundary above.
    headroom = headroom_fn or ws.gauge_headroom_bytes
    for gc in (18, 12, 8):
        for dtype in (jnp.float32, jnp.bfloat16):
            Y, Xh = 4, 4
            itemsize = jnp.dtype(dtype).itemsize
            want_head = (18 - gc) * 12 * 2 * tiled_plane(Y, Xh, itemsize)
            got_head = headroom(Y, Xh, itemsize, gauge_comps=gc)
            if got_head != want_head:
                findings.append(_finding(
                    root, "J3",
                    f"gauge_headroom_bytes(Y={Y}, Xh={Xh}, "
                    f"itemsize={itemsize}, gauge_comps={gc}) = "
                    f"{got_head}, independent estimate {want_head} "
                    f"((18-{gc}) tiled planes x 12 plane-sets x 2 "
                    "buffers)"))
            row = 4 * 24 * tiled_plane(Y, Xh, itemsize)   # one (Z=4) row
            T_gc = budget("resident", (1, 4, 24, Y, Xh), itemsize,
                          gc) // row
            for T in (T_gc, T_gc + 1):
                shape = (T, 4, 24, Y, Xh)
                resident, ringsz, res_ok, want_policy = estimate(
                    shape, itemsize, gc)
                got_fits = ws.fused_dhat_fits(shape, dtype,
                                              gauge_comps=gc)
                if got_fits != res_ok:
                    findings.append(_finding(
                        root, "J3",
                        f"fused_dhat_fits({shape}, "
                        f"{jnp.dtype(dtype).name}, gauge_comps={gc}) = "
                        f"{got_fits}, but resident {resident}B against "
                        f"the budget at {gc} planes says {res_ok}"))
                got_policy = ws.fused_dhat_policy(shape, dtype,
                                                  gauge_comps=gc)
                if got_policy != want_policy:
                    findings.append(_finding(
                        root, "J3",
                        f"fused_dhat_policy({shape}, "
                        f"{jnp.dtype(dtype).name}, gauge_comps={gc}) = "
                        f"{got_policy!r}, but the byte math (resident "
                        f"{resident}B, ring {ringsz}B at {gc} planes) "
                        f"says {want_policy!r}"))

    # The traffic model reports the same scratch numbers it budgets by.
    model = ws.dhat_stream_traffic_model(16, 8, 8, 4, nrhs=2)
    mring = ring((2, 16, 8, 24, 8, 4))
    if model["vmem_ring_bytes"] != mring:
        findings.append(_finding(
            root, "J3",
            f"dhat_stream_traffic_model reports vmem_ring_bytes="
            f"{model['vmem_ring_bytes']} but stream_ring_bytes says "
            f"{mring} for the same (T=16, Z=8, Y=8, Xh=4, nrhs=2)"))
    if model["vmem_resident_bytes"] != 2 * 16 * 8 * 24 * tiled_plane(8, 4,
                                                                     4):
        findings.append(_finding(
            root, "J3",
            "dhat_stream_traffic_model's vmem_resident_bytes disagrees "
            "with the tiled plane count of the intermediate"))
    return findings


# --- J4: retrace detector --------------------------------------------


def check_retrace_budget(root: str, *,
                         session_factory: Optional[Callable] = None,
                         ) -> List[Finding]:
    """J4: a replayed serving scenario traces once per distinct key.

    Scenario: 3 solves on one (spec, shape) key, 2 on a second shape
    (batched nrhs=2), 1 on a second spec — 6 solves, 3 keys, so the
    declared budget is exactly 3 traces / 3 misses / 3 hits.

    ``session_factory() -> SolveSession`` lets the self-tests seed a
    cache-defeating session (e.g. one that clears its cache per solve).
    """
    import jax.numpy as jnp
    from repro import api

    Ue, Uo, e, o = _tiny_eo()
    if session_factory is None:
        def session_factory():
            D = api.WilsonMatrix.bind(Ue, Uo, _KAPPA, backend="jnp")
            return api.SolveSession(D, api.SolveSpec(
                method="cgnr", tol=1e-5, max_iters=25))

    session = session_factory()
    spec2 = api.SolveSpec(method="bicgstab", tol=1e-5, max_iters=25)
    eb = jnp.stack([e, e])
    ob = jnp.stack([o, o])

    session.solve(e, o)
    session.solve(e, o)
    session.solve(e, o)
    session.solve(eb, ob)       # new shape key (batched pipeline)
    session.solve(eb, ob)
    session.solve(e, o, spec2)  # new spec key

    stats = session.stats()
    budget = {"solves": 6, "traces": 3,
              "cache_misses": 3, "cache_hits": 3}
    findings: List[Finding] = []
    for key, want in budget.items():
        got = stats.get(key)
        if got != want:
            findings.append(_finding(
                root, "J4",
                f"SolveSession scenario: {key} = {got}, declared "
                f"budget {want} (6 solves over 3 distinct "
                "(spec, shape) keys must compile exactly once each — "
                "anything more is a retrace leak, anything less means "
                "the trace counter stopped counting)"))
    return findings


# --- J5: comms/compute overlap schedule ------------------------------

# One Dhat = two hopping blocks; each exchanges 4 spinor faces and
# (without gauge hoisting) 4 gauge faces.
_J5_MIN_PPERMUTES = 8
_J5_EXPECTED_INTERIOR_KERNELS = 2


def check_overlap_interleave(root: str, *,
                             overlap: str = "interior",
                             partition_factory: Optional[Callable] = None,
                             ) -> List[Finding]:
    """J5: ``overlap='interior'`` really decouples kernels from comms.

    Traces the distributed Dhat (pallas local backend, 1-device mesh)
    and inspects the ``shard_map`` body jaxpr: the halo ``ppermute``s
    must be present (>= 8 — four faces per hopping block), exactly 2
    ``pallas_call``s must launch (one interior pass per hopping block),
    and every ``pallas_call`` must have at least 4 *already-issued*
    ``ppermute``s it is NOT data-dependent on — the faces genuinely in
    flight while it runs (established by forward dependency propagation
    over the body's equations).  The per-kernel formulation matters:
    the second hop's interior kernel legitimately depends on the FIRST
    hop's exchange (through the hopping-block chain) — what it must not
    depend on is its own.  The fused schedule fails (each kernel
    consumes every face exchanged before it), which is the
    seeded-violation self-test.

    ``partition_factory() -> QCDPartition`` overrides the traced
    configuration.
    """
    import jax
    from jax.extend import core as jcore
    from repro import compat
    from repro.distributed import qcd
    from repro.kernels import layout

    Ue, Uo, e, _ = _tiny_eo()
    u_e_p, u_o_p = layout.gauge_to_planar(Ue), layout.gauge_to_planar(Uo)
    src_p = layout.spinor_to_planar(e)

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    if partition_factory is None:
        def partition_factory():
            return qcd.QCDPartition.for_mesh(
                mesh, backend="pallas", overlap=overlap, interpret=True)
    part = partition_factory()
    fn = qcd.make_dhat_fn(part, _KAPPA)
    jaxpr = jax.make_jaxpr(fn)(u_e_p, u_o_p, src_p)

    body = None
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name == "shard_map":
            subs = [s for v in eqn.params.values() for s in _as_jaxprs(v)]
            if subs:
                body = subs[0]
                break
    if body is None:
        return [_finding(
            root, "J5",
            "no shard_map equation in the traced distributed Dhat — "
            "the operator is expected to run under shard_map")]
    if isinstance(body, jcore.ClosedJaxpr):
        body = body.jaxpr

    def _counts(eqn):
        """(ppermutes, pallas_calls) inside one equation (recursively)."""
        pp = pc = 0
        stack = [eqn]
        while stack:
            e_ = stack.pop()
            if e_.primitive.name == "ppermute":
                pp += 1
            elif e_.primitive.name == "pallas_call":
                pc += 1
            for val in e_.params.values():
                for sub in _as_jaxprs(val):
                    sj = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) \
                        else sub
                    stack.extend(sj.eqns)
        return pp, pc

    # Forward dependency propagation: deps[var] = set of ppermute ids
    # (issue-ordered ints) the value is data-dependent on.
    deps = {}
    n_ppermute = 0
    n_kernels = 0
    serialized = []                 # (kernel index, overlapped faces)
    for eqn in body.eqns:
        in_deps = set()
        for v in eqn.invars:
            if getattr(v, "count", None) is not None:
                in_deps |= deps.get(v, frozenset())
        pp, pc = _counts(eqn)
        for _ in range(pc):
            # Faces already in flight that this kernel does NOT wait
            # on: every earlier-issued ppermute outside its dep set.
            overlapped = n_ppermute - len(in_deps)
            if overlapped < 4:
                serialized.append((n_kernels, overlapped))
            n_kernels += 1
        if pp:
            in_deps = in_deps | set(range(n_ppermute, n_ppermute + pp))
            n_ppermute += pp
        if in_deps:
            frozen = frozenset(in_deps)
            for v in eqn.outvars:
                deps[v] = frozen

    findings: List[Finding] = []
    if n_ppermute < _J5_MIN_PPERMUTES:
        findings.append(_finding(
            root, "J5",
            f"overlap={overlap!r}: only {n_ppermute} ppermute(s) in the "
            f"shard_map body, expected >= {_J5_MIN_PPERMUTES} (4 faces "
            "per hopping block, 2 hopping blocks per Dhat) — the halo "
            "exchange went missing"))
    if serialized:
        detail = ", ".join(f"kernel {i}: {n} face(s) in flight"
                           for i, n in serialized)
        findings.append(_finding(
            root, "J5",
            f"overlap={overlap!r}: {len(serialized)} pallas_call(s) "
            "have fewer than 4 already-issued ppermutes outside their "
            f"dependency set ({detail}) — the main kernel is "
            "serialized behind the halo exchange instead of "
            "overlapping with it"))
    if n_kernels != _J5_EXPECTED_INTERIOR_KERNELS:
        findings.append(_finding(
            root, "J5",
            f"overlap={overlap!r}: {n_kernels} pallas_call(s) in the "
            f"shard_map body, expected exactly "
            f"{_J5_EXPECTED_INTERIOR_KERNELS} (one interior pass per "
            "hopping block)"))
    return findings


# --- J6: divergence guard present in every Krylov while_loop ---------


def check_nonfinite_guard(root: str, *,
                          run_fn: Optional[Callable] = None,
                          methods: Optional[Sequence[str]] = None,
                          ) -> List[Finding]:
    """J6: every Krylov ``while_loop`` carries the non-finite guard.

    Traces :func:`repro.core.solver._run_krylov` for every method,
    batched and unbatched, over a dense SPD operator, and asserts each
    ``while`` equation's *cond* jaxpr contains an ``is_finite``
    primitive — the structural footprint of the divergence guard
    (``jnp.isfinite(rr)`` in the loop condition).  Without it a single
    corrupted residual runs the full ``max_iters`` of NaN arithmetic
    and exits looking merely "not converged".

    ``run_fn(method, batched) -> SolveResult`` overrides the traced
    entry so the self-tests can seed a guard-free solver
    (``guard=False``).
    """
    import jax
    import jax.numpy as jnp
    from repro.core import solver

    if methods is None:
        methods = solver.KRYLOV_METHODS

    n = 24
    key = jax.random.PRNGKey(0)
    G = jax.random.normal(key, (n, n), dtype=jnp.float32)
    A = G @ G.T + n * jnp.eye(n, dtype=jnp.float32)
    b1 = jax.random.normal(jax.random.fold_in(key, 1), (n,),
                           dtype=jnp.float32)
    bb = jax.random.normal(jax.random.fold_in(key, 2), (3, n),
                           dtype=jnp.float32)

    if run_fn is None:
        def run_fn(method, batched):
            # A is symmetric, so op == op^dag; batched operands carry a
            # leading rhs axis (the solvers reduce per column).
            if batched:
                return solver._run_krylov(
                    method, lambda v: v @ A.T, lambda v: v @ A.T, bb,
                    tol=1e-6, max_iters=8, recompute_every=0,
                    batched=True)
            return solver._run_krylov(
                method, lambda v: A @ v, lambda v: A @ v, b1,
                tol=1e-6, max_iters=8, recompute_every=0,
                batched=False)

    findings: List[Finding] = []
    for method in methods:
        for batched in (False, True):
            jaxpr = jax.make_jaxpr(
                lambda m=method, b=batched: run_fn(m, b))()
            whiles = 0
            unguarded = 0
            for eqn in _walk_eqns(jaxpr):
                if eqn.primitive.name != "while":
                    continue
                whiles += 1
                cond = eqn.params.get("cond_jaxpr")
                if not any(e.primitive.name == "is_finite"
                           for e in _walk_eqns(cond)):
                    unguarded += 1
            label = f"method {method!r} ({'batched' if batched else 'single'})"
            if whiles == 0:
                findings.append(_finding(
                    root, "J6",
                    f"{label}: no while_loop in the traced Krylov solve "
                    "— the iteration is expected to lower to "
                    "lax.while_loop (did the trace entry change?)"))
            elif unguarded:
                findings.append(_finding(
                    root, "J6",
                    f"{label}: {unguarded} of {whiles} while_loop(s) "
                    "have no is_finite primitive in their cond jaxpr — "
                    "the non-finite divergence guard is structurally "
                    "absent, so a corrupted residual would run the "
                    "full iteration budget as silent NaN arithmetic"))
    return findings


# --- runner entry -----------------------------------------------------

_CHECK_FNS = {
    "J1": check_conversion_free,
    "J2": check_pallas_counts,
    "J3": check_vmem_model,
    "J4": check_retrace_budget,
    "J5": check_overlap_interleave,
    "J6": check_nonfinite_guard,
}


def run_jaxpr_checks(root: str,
                     checks: Optional[Iterable[str]] = None
                     ) -> List[Finding]:
    """Run the selected (default: all) jaxpr invariant checks."""
    selected = tuple(checks) if checks is not None else ALL_JAXPR_CHECKS
    findings: List[Finding] = []
    for name in selected:
        try:
            fn = _CHECK_FNS[name]
        except KeyError:
            raise ValueError(
                f"unknown jaxpr check {name!r}; "
                f"choose from {ALL_JAXPR_CHECKS}") from None
        findings.extend(fn(root))
    return sorted(findings)

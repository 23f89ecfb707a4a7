"""Serving-loop solve session: compiled-solve caching + observability.

A :class:`SolveSession` holds one bound :class:`~repro.api.WilsonMatrix`
plus a cache of jitted solve executables keyed on
``(SolveSpec, rhs shape, rhs dtype)``.  The first solve of a given key
traces and compiles the full native-domain pipeline (Eq. 4 RHS build,
Krylov ``while_loop``, Eq. 5 reconstruction); the second and every later
same-shape solve reuses the executable and skips tracing entirely —
the property a serving system handling heavy repeated solve traffic
needs, and the one the paper buys on A64FX by packing the gauge layout
once outside the hot loop.

``session.stats()`` is the observability hook: trace counts (compiles),
cache hits/misses, per-key first-solve vs steady-state wall times,
per-solve Krylov iteration counts in call order (the surface where a
recycle-deflated key shows its iterations *dropping* across a request
stream), and the resilience ledger — backend fallbacks taken, the
``degraded`` flag, and per-refined-key outer-iteration /
precision-escalation histories.

Each solve is a ``repro.solve`` span (:mod:`repro.tracing`), and the
per-key times are that span's lengths.  While a profiler trace is
active, a plain solve also records its phases as child spans: encode,
``h2d`` (sources on another device only), ``exec``, ``d2h`` and decode.

Host sources of a planar operator on one device cross as lane-dense
float32 views of their own complex64 bytes
(:func:`repro.kernels.layout.spinor_lanes`): the codec's transposes run
inside the cached executable, on the operator's device, and the host
only reinterprets bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import backends, tracing
from repro.core import solver as _solver
from repro.kernels import layout
from repro.kernels.wilson_stencil import resolve_interpret

from .matrix import WilsonMatrix
from .specs import SolveSpec

__all__ = ["SolveSession"]


def _nbytes(*trees) -> int:
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(trees))


class _CacheEntry:
    __slots__ = ("fn", "lanes_fn", "kind", "times", "outer", "escalations",
                 "iterations", "col_iterations", "deflation")

    def __init__(self, fn, kind, deflation=None, lanes_fn=None):
        self.fn = fn
        self.lanes_fn = lanes_fn  # plain: the same solve on lane-dense views
        self.kind = kind          # "plain" | "refined"
        self.times = []           # repro.solve span seconds, in call order
        self.outer = []           # refined: outer iterations per solve
        self.escalations = []     # refined: dtype rungs climbed per solve
        self.iterations = []      # plain: max Krylov iterations per solve
        self.col_iterations = []  # plain batched: per-column counts
        self.deflation = deflation  # DeflationState driving this key


class SolveSession:
    """Bind once, solve many: compiled solves cached per
    ``(SolveSpec, rhs shape/dtype)``.

    ::

        D = WilsonMatrix.bind(U_e, U_o, kappa, backend="pallas_fused")
        session = SolveSession(D, SolveSpec(method="bicgstab", tol=1e-6))
        xe, xo, res = session.solve(eta_e, eta_o)      # traces + compiles
        xe, xo, res = session.solve(eta2_e, eta2_o)    # cache hit: no trace
        print(session.stats())

    Plain solves are jitted whole (encode/decode stay outside the
    executable, at the native-domain boundary, except for host sources
    that cross as lane-dense views, whose transposes run inside it:
    ``stats()["lane_dense_solves"]`` counts those); the trace counter in
    :meth:`stats` increments inside the traced function, so it counts
    *actual* retraces, including any the cache failed to prevent.
    Mixed-precision refined solves (``SolveSpec.inner_dtype``) cache a
    refined runner whose f64 operator and inner-Krylov jit caches are
    built once per key; their Python-level outer loop runs per solve
    (data-dependent exit), so refined keys count one trace at build.
    """

    def __init__(self, matrix: WilsonMatrix,
                 spec: Optional[SolveSpec] = None):
        if not isinstance(matrix, WilsonMatrix):
            raise TypeError(
                f"SolveSession needs a WilsonMatrix; got "
                f"{type(matrix).__name__} (wrap bound ops with "
                "WilsonMatrix.from_ops, or build with WilsonMatrix.bind)")
        self.matrix = matrix
        self.default_spec = spec if spec is not None else SolveSpec()
        self._cache = {}
        self._counters = {"solves": 0, "traces": 0, "cache_hits": 0,
                          "cache_misses": 0, "fallbacks": 0,
                          "lane_dense_solves": 0}

    # --- solve --------------------------------------------------------

    def solve(self, eta_e, eta_o, spec: Optional[SolveSpec] = None):
        """Solve ``D_W xi = eta`` for one source pair (or a leading-axis
        RHS block); returns ``(xi_e, xi_o, result)``.

        When the bound matrix was created with ``fallback=True``, a
        solve-time failure (kernel compile error, backend fault) walks
        the declared fallback chain: the matrix is rebound onto the
        next backend, the compiled-solve cache is flushed (it belonged
        to the failed backend), and the solve retries — recorded in
        ``stats()["fallbacks"]`` and the matrix's ``fallback_events``.
        """
        spec = self.default_spec if spec is None else spec
        while True:
            try:
                return self._solve_once(eta_e, eta_o, spec)
            except Exception as exc:   # noqa: BLE001 — chain walk
                if not getattr(self.matrix, "fallback_enabled", False):
                    raise
                nxt = self.matrix.fallback_next(repr(exc))
                if nxt is None:
                    raise
                self.matrix = nxt
                self._cache.clear()
                self._counters["fallbacks"] += 1

    def _solve_once(self, eta_e, eta_o, spec: SolveSpec):
        if self.matrix.lattice is not None:
            batched = spec.validate_rhs(eta_e, eta_o, self.matrix.lattice)
        else:
            batched = eta_e.ndim == 7
        key = (spec, tuple(eta_e.shape), str(eta_e.dtype))

        columns = int(eta_e.shape[0]) if batched else 1
        with tracing.span("repro.solve", columns=columns) as solve_span:
            entry = self._cache.get(key)
            hit = entry is not None
            if entry is None:
                entry = self._build(spec, batched, key[1])

            x_native = None
            lane_home = None
            if entry.kind == "refined":
                xi_e, xi_o, res = entry.fn(eta_e, eta_o)
            else:
                lane_home = self._lane_home(eta_e, eta_o)
                if lane_home is None:
                    xi_e, xi_o, res, x_native = self._run_plain(
                        entry, eta_e, eta_o, batched)
                else:
                    xi_e, xi_o, res, x_native = self._run_lanes(
                        entry, eta_e, eta_o, lane_home)
            jax.block_until_ready((xi_e, xi_o))

        # Commit cache + counters only after the run succeeded: a build
        # or execution failure (refined spec without x64, an injected
        # kernel fault) must leave both untouched so a fallback retry —
        # or a later successful call — isn't double-counted.
        self._cache[key] = entry
        self._counters["cache_hits" if hit else "cache_misses"] += 1
        self._counters["solves"] += 1
        self._counters["lane_dense_solves"] += lane_home is not None
        if entry.kind == "refined":
            entry.outer.append(int(res.outer_iterations))
            entry.escalations.append(tuple(res.escalations))
        else:
            entry.iterations.append(int(jnp.max(res.iterations)))
            if getattr(res.iterations, "ndim", 0) >= 1:
                # Per-column counts of the batched solve, for the
                # serving layer's split-back observability: each
                # coalesced request's own iteration cost is visible,
                # not just the batch maximum.
                entry.col_iterations.append(
                    [int(i) for i in res.iterations])
        entry.times.append(solve_span.seconds)
        self._maybe_harvest(entry, x_native, res, batched)
        return xi_e, xi_o, res

    def _run_plain(self, entry, eta_e, eta_o, batched):
        """Encode, move, solve, move back and decode, each phase a span
        of the enclosing ``repro.solve``; returns ``(xi_e, xi_o, res,
        x_native)``."""
        matrix, ops = self.matrix, self.matrix.ops
        # Sources committed to another device than the operator's (the
        # host, for an accelerator-resident matrix) are encoded and
        # decoded there; only native vectors cross.
        home = matrix.foreign_home(eta_e)
        to_dom = ops.to_domain_batched if batched else ops.to_domain
        with tracing.span("repro.solve.encode") as sp:
            v_e, v_o = to_dom(eta_e), to_dom(eta_o)
            sp.fence(v_e, v_o)
        if home is not None:
            with tracing.span("repro.solve.h2d",
                              bytes=_nbytes(v_e, v_o)) as sp:
                v_e, v_o = matrix.place(v_e), matrix.place(v_o)
                sp.fence(v_e, v_o)
        args = (v_e, v_o)
        if entry.deflation is not None:
            args += (entry.deflation.basis,)
        with tracing.span("repro.solve.exec") as sp:
            x, v_xi_o, res = entry.fn(*args)
            sp.fence(x, v_xi_o, res)
        x_native = x
        if home is not None:
            with tracing.span("repro.solve.d2h",
                              bytes=_nbytes(x, v_xi_o)) as sp:
                x, v_xi_o = jax.device_put((x, v_xi_o), home)
                sp.fence(x, v_xi_o)
        from_dom = ops.from_domain_batched if batched else ops.from_domain
        with tracing.span("repro.solve.decode") as sp:
            # Decode keeps the caller's spinor dtype (c128 under x64).
            xi_e = from_dom(x).astype(eta_e.dtype)
            xi_o = from_dom(v_xi_o).astype(eta_o.dtype)
            sp.fence(xi_e, xi_o)
        return xi_e, xi_o, res._replace(x=xi_e), x_native

    def _lane_home(self, eta_e, eta_o):
        """The home of host sources that cross as lane-dense views, else
        ``None``: both parities complex64 on one host CPU device, tiling
        whole, for a planar operator on one other device."""
        matrix = self.matrix
        home = matrix.foreign_home(eta_e)
        if (home is None or home.platform != "cpu"
                or matrix.foreign_home(eta_o) != home
                or len(matrix.devices()) != 1
                or matrix.domain != "planar"
                or eta_o.dtype != eta_e.dtype
                or not layout.lanes_fit(eta_e.shape, eta_e.dtype)):
            return None
        return home

    def _run_lanes(self, entry, eta_e, eta_o, home):
        """:meth:`_run_plain` for host sources that cross as lane-dense
        float32 views of their own bytes: encode and decode only
        reinterpret bytes on the host, and the cached executable
        transposes on the operator's device.  The planar solution
        (``x_native``, for recycling) stays there."""
        (device,) = self.matrix.devices()
        with tracing.span("repro.solve.encode"):
            l_e, l_o = layout.spinor_lanes(eta_e), layout.spinor_lanes(eta_o)
        with tracing.span("repro.solve.h2d",
                          bytes=_nbytes(l_e, l_o)) as sp:
            l_e, l_o = jax.device_put((l_e, l_o), device)
            sp.fence(l_e, l_o)
        args = (l_e, l_o)
        if entry.deflation is not None:
            args += (entry.deflation.basis,)
        with tracing.span("repro.solve.exec") as sp:
            y_e, y_o, res = entry.lanes_fn(*args)
            sp.fence(y_e, y_o, res)
        with tracing.span("repro.solve.d2h",
                          bytes=_nbytes(y_e, y_o)) as sp:
            y_e, y_o = jax.device_put((y_e, y_o), home)
            sp.fence(y_e, y_o)
        with tracing.span("repro.solve.decode") as sp:
            xi_e, xi_o = jax.device_put(
                (layout.spinor_from_lanes(y_e, eta_e.shape),
                 layout.spinor_from_lanes(y_o, eta_o.shape)), home)
            sp.fence(xi_e, xi_o)
        return xi_e, xi_o, res._replace(x=xi_e), res.x

    def solve_block(self, eta_e, eta_o, spec: Optional[SolveSpec] = None,
                    *, donate: bool = False, bounds=None):
        """Batched serving entry: solve one coalesced RHS block and
        split the result back per request.

        ``eta_e`` / ``eta_o`` is a multi-RHS block (a leading ``nrhs``
        axis; a single 6-d source pair is promoted to a block of one).
        ``bounds`` maps batch columns back to the independent requests
        that were coalesced into the block — a sequence of ``(lo, hi)``
        column ranges (default: one range per column); the returned
        ``parts`` list holds one per-request result each, produced by
        :func:`repro.core.solver.split_columns` (per-column iterations
        / residuals / convergence verdicts — meaningful independently
        because converged columns freeze bit-exactly).

        ``donate=True`` switches the cache entry to a buffer-donating
        executable (see :class:`~repro.api.SolveSpec` ``donate_rhs``):
        the sources are consumed by the solve — the contract a
        coalescing daemon wants for the batch temporaries it assembles.

        Returns ``(xi_e, xi_o, res, parts)``.
        """
        spec = self.default_spec if spec is None else spec
        if eta_e.ndim == 6:
            eta_e, eta_o = eta_e[None], eta_o[None]
        nrhs = int(eta_e.shape[0])
        if spec.nrhs is not None and spec.nrhs != nrhs:
            # A serving block's size is chosen by the batcher, not the
            # spec; a pinned nrhs would just fragment the cache.
            spec = dataclasses.replace(spec, nrhs=None)
        if donate and not spec.donate_rhs:
            spec = dataclasses.replace(spec, donate_rhs=True)
        xi_e, xi_o, res = self.solve(eta_e, eta_o, spec)
        if bounds is None:
            bounds = [(j, j + 1) for j in range(nrhs)]
        return xi_e, xi_o, res, _solver.split_columns(res, bounds)

    def _maybe_harvest(self, entry, x_native, res, batched):
        """Feed converged solutions of a recycle-deflated key back into
        the basis (x solves the normal system ``A x = Dhat^dag rhs``, so
        it is naturally rich in A's low modes); the next solve of the
        same key sees the grown basis as a changed jit *argument* — no
        retrace."""
        state = entry.deflation
        if (state is None or state.mode != "recycle"
                or x_native is None or state.count >= state.rank):
            return
        if batched:
            ok = jax.device_get(res.converged)
            for j, conv in enumerate(ok):
                if not conv:
                    continue
                col = jax.tree_util.tree_map(lambda l: l[j], x_native)
                state.harvest_column(col)
                if state.count >= state.rank:
                    break
        elif bool(res.converged):
            state.harvest_column(x_native)

    def _escalation_factory(self):
        """A ``bops_factory`` for the refined solve's precision ladder:
        rebinds the *session's* backend at the requested rung when its
        capabilities allow, else drops to the jnp reference operator at
        the matching complex dtype (always available) — so the f64 rung of
        a compiled Pallas backend, which has no f64 kernel, runs on jnp."""
        matrix = self.matrix

        def factory(rung: str):
            bspec = matrix.backend
            caps = backends.backend_info(bspec.name)
            U_e, U_o = matrix.gauge_complex(jnp.complex128)
            compiled_only = (rung in caps.interpret_only_dtypes
                             and not resolve_interpret(bspec.interpret))
            if rung in caps.dtypes and not compiled_only:
                spec2 = dataclasses.replace(bspec, dtype=rung)
                extra = (matrix._opaque.value
                         if (matrix._rebuild == "native"
                             and matrix._opaque) else {})
                return backends.make_wilson_ops(
                    bspec.name, U_e, U_o,
                    **{**spec2.factory_opts(), **extra})
            cdt = jnp.complex128 if rung == "f64" else jnp.complex64
            return backends.make_wilson_ops(
                "jnp", U_e.astype(cdt), U_o.astype(cdt))

        return factory

    def _build(self, spec: SolveSpec, batched: bool,
               shape: tuple) -> _CacheEntry:
        if spec.inner_dtype is not None:
            # Mixed-precision refinement: the bound matrix IS the inner
            # backend (bind it at the inner dtype); the f64 reference
            # operator is rebuilt from the bound gauge leaves and jitted
            # once here.
            U64_e, U64_o = self.matrix.gauge_complex()
            fn = _solver.make_refined_solve(
                self.matrix.ops, U64_e, U64_o, self.matrix.kappa,
                method=spec.method, tol=spec.tol,
                max_iters=spec.max_iters,
                recompute_every=spec.recompute_every,
                inner_tol=spec.inner_tol, max_outer=spec.max_outer,
                batched=batched, guard=spec.guard,
                stagnation_window=spec.stagnation_window,
                max_restarts=spec.max_restarts,
                inner_dtype=spec.inner_dtype,
                escalate=spec.escalate,
                bops_factory=(self._escalation_factory()
                              if spec.escalate else None))
            self._counters["traces"] += 1
            return _CacheEntry(fn, "refined")

        deflation = None
        if spec.deflate_rank > 0:
            deflation = self.matrix.ensure_deflation(
                spec.deflate_rank, spec.deflate_mode,
                checkpoint=spec.deflate_checkpoint,
                lanczos_iters=spec.deflate_iters)
        native = _solver.make_native_solve(
            self.matrix.ops, self.matrix.kappa, method=spec.method,
            tol=spec.tol, max_iters=spec.max_iters,
            recompute_every=spec.recompute_every, batched=batched,
            guard=spec.guard,
            stagnation_window=spec.stagnation_window,
            max_restarts=spec.max_restarts,
            deflated=deflation is not None)
        counters = self._counters

        to_dom = (self.matrix.ops.to_domain_batched if batched
                  else self.matrix.ops.to_domain)

        if deflation is not None:
            def counted(v_e, v_o, basis):
                counters["traces"] += 1
                return native(v_e, v_o, basis)
        else:
            def counted(v_e, v_o):
                # Python side effect at trace time only: counts real
                # (re)compiles, not calls.
                counters["traces"] += 1
                return native(v_e, v_o)

        def lanes(l_e, l_o, *basis):
            # The same solve on lane-dense views of complex64 sources of
            # ``shape`` (see _run_lanes), planar in between.
            dtype = jax.eval_shape(
                to_dom, jax.ShapeDtypeStruct(shape, jnp.complex64)).dtype
            x, v_xi_o, res = counted(
                layout.planar_from_lanes(l_e, shape, dtype),
                layout.planar_from_lanes(l_o, shape, dtype), *basis)
            return (layout.lanes_from_planar(x),
                    layout.lanes_from_planar(v_xi_o), res)

        # donate_rhs: the encoded source vectors (argnums 0/1; a
        # deflation basis argument is never donated) are handed to XLA
        # for reuse — the serving hot path's batch temporaries.
        # Platforms without donation support warn and run undonated.
        jit_kw = {"donate_argnums": (0, 1)} if spec.donate_rhs else {}
        return _CacheEntry(jax.jit(counted, **jit_kw), "plain",
                           deflation, lanes_fn=jax.jit(lanes, **jit_kw))

    # --- observability ------------------------------------------------

    def stats(self) -> dict:
        """Serving-loop report: totals plus per-key timing breakdown.

        ``traces`` counts compile events (for plain keys, incremented at
        actual trace time); ``steady_state_s`` is the median wall time
        of the cached (non-first) solves of a key — the number a serving
        loop sustains once warm.
        """
        keys = {}
        for (spec, shape, dtype), entry in self._cache.items():
            times = entry.times
            steady = sorted(times[1:])
            row = {
                "kind": entry.kind,
                "solves": len(times),
                "first_solve_s": times[0] if times else None,
                "steady_state_s": (steady[len(steady) // 2]
                                   if steady else None),
            }
            if entry.kind == "refined":
                row["outer_iterations"] = list(entry.outer)
                row["escalations"] = [list(e) for e in entry.escalations]
            else:
                # Per-solve Krylov iteration counts in call order — on a
                # recycle-deflated key this is where the drop across the
                # request stream shows up.
                row["iterations"] = list(entry.iterations)
                if entry.col_iterations:
                    row["col_iterations"] = [
                        list(c) for c in entry.col_iterations]
            if entry.deflation is not None:
                row["deflation"] = {
                    "mode": entry.deflation.mode,
                    "rank": entry.deflation.rank,
                    "filled": entry.deflation.count,
                    "active": entry.deflation.active,
                    "harvested": entry.deflation.harvested,
                }
            keys["|".join([spec.cache_token(), f"shape={shape}",
                           f"dtype={dtype}"])] = row
        return {
            **self._counters,
            "backend": self.matrix.backend.name,
            "degraded": bool(getattr(self.matrix, "degraded", False)),
            "fallback_events": list(
                getattr(self.matrix, "fallback_events", ()) or ()),
            "keys": keys,
        }

"""QCD solver driver: solve D_W xi = eta on the (distributed) lattice with
checkpoint/restart fault tolerance — the end-to-end "serving" loop of the
paper's kind (linear solves are the unit of work in lattice QCD).

  PYTHONPATH=src python -m repro.launch.solve --lattice wilson-16x16x16x16 \
      --tol 1e-6 --ckpt-dir /tmp/qcd_ck

Built on the public object API (:mod:`repro.api`): the CLI args are
parsed into one ``(LatticeSpec, BackendSpec, SolveSpec)`` triple, the
gauge field is bound ONCE into a :class:`repro.api.WilsonMatrix` (layout
conversion / sharding placement / policy selection at bind), and every
solve goes through one :class:`repro.api.SolveSession` — so the Krylov
loop is traced/compiled on the first solve only and each later
same-shape solve reuses the executable.  The session's cache/timing
report is printed at the end.

Restart logic: CG is restart-friendly — checkpoint (x, step) and rebuild
the residual from scratch on resume (r = b - A x); convergence continues
where it left off.

Multi-RHS: ``--nrhs N`` solves N sources as ONE batched Krylov solve.
``--inner-dtype f32|bf16`` switches to mixed-precision iterative
refinement (inner solves in the cheap dtype, outer f64 true-residual
loop; enables jax x64 automatically).  ``--backend help`` prints the
registry's per-backend capability metadata and exits.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import api, backends, configs
from repro.checkpoint.ckpt import Checkpointer
from repro.core import solver as _solver
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.problem import HostProblem


def _backend_help() -> str:
    lines = ["registered operator backends (see also --backend help):"]
    for name in backends.available_backends():
        caps = backends.backend_info(name)
        lines.append(f"  {name}: {caps.description} "
                     f"[domain={caps.domain}, batched_kernels="
                     f"{caps.batched_kernels}]")
    return " ".join(lines)


def _print_backend_info():
    print("registered operator backends:")
    for name in backends.available_backends():
        caps = backends.backend_info(name)
        print(f"  {name}")
        print(f"    domain={caps.domain} gauge_form={caps.gauge_form} "
              f"batched_kernels={caps.batched_kernels}")
        print(f"    dtypes={list(caps.dtypes) or '(follows gauge)'} "
              f"interpret={caps.supports_interpret} "
              f"policies={list(caps.policies)}")
        print(f"    gauge_compressions={list(caps.gauge_compressions)}")
        print(f"    {caps.description}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lattice", default="wilson-16x16x16x16")
    ap.add_argument("--kappa", type=float, default=0.13)
    ap.add_argument("--tol", type=float, default=1e-6)
    # choices DERIVED from the solver's method tuple via SolveSpec —
    # adding a Krylov method there adds it here (this is where plain
    # "cg", valid on the normal equations, comes from).
    ap.add_argument("--method", default="cgnr",
                    choices=list(api.SolveSpec.METHODS))
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "help"] + backends.available_backends(),
                    help="operator backend (registry name); 'auto' picks "
                         "jnp off-TPU and pallas_fused on TPU; 'help' "
                         "prints per-backend capability metadata and "
                         "exits. " + _backend_help())
    ap.add_argument("--gauge-compression", default="none",
                    choices=["none", "two_row", "minimal"],
                    help="stored SU(3) link representation: two_row "
                         "ships 12 of 18 real planes (-33%% gauge "
                         "bytes), minimal ships 8 (-55%%); the kernels "
                         "reconstruct the full matrix in-register")
    ap.add_argument("--overlap", default="",
                    choices=["", "fused", "split", "interior", "on",
                             "off"],
                    help="distributed-backend halo strategy: 'interior' "
                         "(alias 'on') overlaps the ppermute exchange "
                         "with the interior stencil, 'fused' (alias "
                         "'off') exchanges first, 'split' separates "
                         "local/halo passes; only valid with "
                         "--backend distributed")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="number of right-hand sides per solve; >1 runs "
                         "the batched kernels (gauge field streamed once "
                         "per application for the whole block)")
    ap.add_argument("--inner-dtype", default="",
                    choices=["", "f32", "bf16"],
                    help="mixed-precision iterative refinement: inner "
                         "Krylov solves in this dtype, outer f64 "
                         "true-residual loop to --tol (needs x64; "
                         "enabled automatically)")
    ap.add_argument("--recompute-every", type=int, default=0,
                    help="recompute the true residual every N Krylov "
                         "iterations (0 = never)")
    ap.add_argument("--deflate-rank", type=int, default=0,
                    help="low-mode deflation rank (0 = off): project "
                         "the normal operator's low modes out of every "
                         "solve of this gauge (methods: "
                         + ", ".join(_solver.DEFLATABLE_METHODS) + ")")
    ap.add_argument("--deflate-mode", default="lanczos",
                    choices=list(api.SolveSpec.DEFLATE_MODES),
                    help="how the deflation basis is built: 'lanczos' "
                         "pays a once-per-gauge eigensolve; 'recycle' "
                         "starts empty and harvests converged solutions "
                         "from the stream (per-solve iterations drop as "
                         "it fills — watch the session stats)")
    ap.add_argument("--deflate-iters", type=int, default=0,
                    help="Lanczos step count for --deflate-mode lanczos "
                         "(0 = auto; raise it when the low spectrum is "
                         "degenerate)")
    ap.add_argument("--validate", default="none",
                    choices=["none", "warn", "repair"],
                    help="SU(3) gauge-integrity audit at bind: 'warn' "
                         "reports unitarity/finiteness defects, "
                         "'repair' projects defective links back onto "
                         "the group before any codec packs them")
    ap.add_argument("--fallback", action="store_true",
                    help="graceful degradation: on a backend failure "
                         "(bind or solve time) walk the declared "
                         "fallback chain toward the jnp reference "
                         "instead of aborting the campaign")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--restart-every", type=int, default=0,
                    help="simulate failure/restart every N solves")
    ap.add_argument("--n-solves", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    if args.backend == "help":
        _print_backend_info()
        return
    enable_compile_cache()

    inner_dtype = args.inner_dtype or None
    if inner_dtype:
        # The refinement outer loop measures its residual in f64.
        jax.config.update("jax_enable_x64", True)

    lat = configs.get_qcd(args.lattice)
    lattice = api.LatticeSpec(lat.shape)
    # Under mixed precision the bound matrix IS the inner-solve backend,
    # so bind it at the inner dtype (jnp has no dtype knob: its inner
    # solve runs at the gauge's complex64).  Resolve "auto" FIRST so
    # e.g. auto->pallas_fused on TPU still honors --inner-dtype.
    bname = api.BackendSpec(name=args.backend).resolve_name()
    opts = []
    if args.overlap:
        if bname != "distributed":
            ap.error("--overlap only applies to --backend distributed")
        opts.append(("overlap",
                     {"on": "interior", "off": "fused"}.get(args.overlap,
                                                            args.overlap)))
    bspec = api.BackendSpec(
        name=bname,
        dtype=(inner_dtype if inner_dtype and bname != "jnp"
               else None),
        gauge_compression=args.gauge_compression,
        opts=tuple(opts)).validated()
    sspec = api.SolveSpec(
        method=args.method, tol=args.tol,
        recompute_every=args.recompute_every,
        nrhs=args.nrhs if args.nrhs > 1 else None,
        inner_dtype=inner_dtype,
        deflate_rank=args.deflate_rank,
        deflate_mode=args.deflate_mode,
        deflate_iters=args.deflate_iters or None)

    T, Z, Y, X = lattice.extents
    print(f"lattice {lattice.extents}, kappa={args.kappa}, "
          f"nrhs={args.nrhs}"
          + (f", inner_dtype={inner_dtype}" if inner_dtype else ""))

    # The gauge and sources are made on the host CPU and only the bound
    # (native layout) operator goes to the accelerator: see
    # repro.launch.problem.  Bind once: layout conversion, placement,
    # and policy selection happen HERE; the session below then reuses
    # one compiled solve for the whole batch of same-shape solves.
    problem = HostProblem(lattice.extents, args.kappa, args.seed)
    matrix = problem.bind(bspec, validate=args.validate,
                          fallback=args.fallback)
    session = api.SolveSession(matrix, sspec)
    print(f"backend {matrix.backend.name} "
          f"(native domain: {matrix.ops.domain})")
    if args.validate != "none":
        print(f"gauge audit: {matrix.gauge_audit}")
    if matrix.degraded:
        print(f"DEGRADED: requested {matrix.requested_backend}, running "
              f"{matrix.backend.name}; events={matrix.fallback_events}")

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    nrhs = args.nrhs

    # Mixed precision refines the iterate in f64; the sources (and hence
    # the returned solution, which is cast back to the source dtype)
    # must be complex128 for that accuracy to survive the decode.
    cdtype = jnp.complex128 if inner_dtype else jnp.complex64

    for i in range(args.n_solves):
        eta, ee, eo = problem.source(i, nrhs if nrhs > 1 else None,
                                     cdtype)
        # The session call returns once the solution is ready.
        t0 = time.perf_counter()
        xe, xo, res = session.solve(ee, eo)
        dt = time.perf_counter() - t0
        # The residual check is deliberately NOT the session's operator:
        # it re-verifies the solution against the independent full-system
        # reference D_W, so a broken backend can't vouch for itself.
        t1 = time.perf_counter()
        rel = problem.true_residual(eta, xe, xo)
        check_s = time.perf_counter() - t1
        vol = T * Z * Y * X
        iters = int(jnp.max(res.iterations))
        flops = 1368.0 * vol * 2 * iters * nrhs  # ~2 Dhat/iter
        line = (f"solve {i}: iters={iters} rel={rel:.2e} {dt:.2f}s "
                f"({dt / nrhs:.2f}s/rhs) "
                f"~{flops / max(dt, 1e-9) / 1e9:.2f} GFlop/s sustained; "
                f"reference check {check_s:.2f}s")
        if hasattr(res, "f64_applies"):
            line += (f"  [outer={res.outer_iterations} "
                     f"f64_applies={res.f64_applies} "
                     f"inner_iters={res.inner_iterations}]")
        print(line, flush=True)
        if ckpt:
            ckpt.save(i, (xe, xo), extras={"rel": rel}, block=True)

    st = session.stats()
    for keystr, row in st["keys"].items():
        steady = (f"{row['steady_state_s']:.3f}s"
                  if row["steady_state_s"] is not None else "n/a")
        line = (f"session[{keystr}]: solves={row['solves']} "
                f"first={row['first_solve_s']:.3f}s steady={steady}")
        if row.get("iterations"):
            line += f" iters={row['iterations']}"
        if row.get("deflation"):
            d = row["deflation"]
            line += (f" deflation={d['mode']}:{d['filled']}/{d['rank']}"
                     f" active={d['active']}")
        print(line)
    print(f"session: solves={st['solves']} traces={st['traces']} "
          f"cache_hits={st['cache_hits']} "
          f"cache_misses={st['cache_misses']} "
          f"fallbacks={st['fallbacks']} degraded={st['degraded']}")
    print("done")


if __name__ == "__main__":
    main()

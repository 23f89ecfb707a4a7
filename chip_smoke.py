"""Smoke test of the even-odd Wilson solve on a TPU, through the public API.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # distributed path, 2x2 host

The gauge field (made from ``--seed``) and the sources are made on the
host CPU by ``repro.launch.problem.HostProblem``, the helper the solve
CLI uses; only the bound, planar operator and native vectors go to the
chip.  The one-chip run binds the gauge into a ``WilsonMatrix`` on the
compiled ``pallas_fused`` backend, with the Pallas interpreter off and no
fallback chain armed, and then, at 16^4:

* applies Dhat and Dhat^dag and compares them with the ``jnp`` reference
  on the same chip (max relative difference <= 1e-5 in f32);
* runs one ``SolveSession`` solve with the CLI defaults (kappa 0.13,
  cgnr, tol 1e-6) and checks the true residual of the full Wilson system,
  computed on the host by the independent ``repro.core.wilson`` operator;
* runs one 12-column batched solve (a point-source propagator) and
  checks each column's true residual;
* answers three requests through an in-process ``PropagatorDaemon``;

and at 32^3x64 runs one cgnr solve and checks its true residual.

``--four-chips`` instead runs the ``distributed`` backend (T split four
ways) at 32^3x64 and compares its Dhat and solution with the one-chip
``pallas_fused`` result, to the same bounds.

Lines before the last are diagnostics: kernel variant, compile and solve
seconds of this single run, peak device memory.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed.  The script exits non-zero, without that line, when JAX
finds no TPU, when the repository's ``src/`` is not beside it, or when
any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

SRC = pathlib.Path(__file__).resolve().parent / "src"

KAPPA = 0.13           # the solve CLI's defaults
TOL = 1e-6
METHOD = "cgnr"
DHAT_RTOL = 1e-5       # Dhat / Dhat^dag against jnp, f32
SOLUTION_RTOL = 1e-5   # distributed solution against one chip's
N_PROPAGATOR = 12      # 4 spins x 3 colours of one point source
N_REQUESTS = 3

# Volumes, (T, Z, Y, X).  At 32^3x64 one cgnr solve needs 7.65 GB of a
# v5e's 16 GB and the 12-column solve 22.5 GB (memory_analysis of the
# solve compiled for v5e), so the 12-column solve and the phases built on
# it run at 16^4 only.
SMALL = (16, 16, 16, 16)    # wilson-16x16x16x16
LARGE = (32, 32, 32, 64)    # wilson-64x32x32x16


def log(msg: str) -> None:
    print(msg, flush=True)


def max_rel_diff(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling in this process,
    and the persistent-cache reads and writes, from ``jax.monitoring``.
    JAX records ``cache_misses`` only when it writes an entry, so a
    compile whose executable is too small, too quick or too large to
    store counts as neither."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
    CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                    "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self):
        import jax

        self.seconds = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.cache = dict.fromkeys(self.CACHE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds[self.EVENTS[event]] += duration

    def _event(self, event, **_):
        if event in self.CACHE_EVENTS:
            self.cache[self.CACHE_EVENTS[event]] += 1

    def report(self) -> str:
        s = self.seconds
        return (f"trace {s['trace']:.3f} s, lower {s['lower']:.3f} s, "
                f"XLA compile or cache load {s['compile']:.3f} s; "
                f"persistent cache {self.cache['hits']} hits, "
                f"{self.cache['writes']} entries written")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def bind_pallas(prob):
    """``pallas_fused`` named explicitly, compiled kernels, no fallback."""
    from repro import api
    from repro.kernels.wilson_stencil import resolve_interpret

    matrix = prob.bind(api.BackendSpec("pallas_fused", interpret=False),
                       fallback=False)
    check(matrix.backend.name == "pallas_fused" and not matrix.degraded,
          f"bound {matrix.backend.name}, degraded={matrix.degraded}")
    check(resolve_interpret(matrix.backend.interpret) is False,
          "pallas_fused would run in the Pallas interpreter")
    return matrix


def check_kernels_compiled(matrix, psi) -> str:
    """The bound operator lowers to Mosaic kernels, not interpreted
    Pallas; returns the fused-Dhat variant the policy picks."""
    import jax

    from repro.kernels.wilson_stencil import fused_dhat_policy

    v = matrix.encode(psi)
    hlo = jax.jit(matrix.apply_native).lower(v).as_text()
    n = hlo.count("tpu_custom_call")
    check(n >= 1, "the Dhat lowering holds no tpu_custom_call")
    return f"{fused_dhat_policy(v.shape, v.dtype)} ({n} tpu_custom_call)"


def operator_phase(prob, matrix) -> None:
    import jax

    from repro import api

    ref = prob.bind(api.BackendSpec("jnp"))
    _, psi, _ = prob.source(0)
    variant = check_kernels_compiled(matrix, psi)
    t0 = time.perf_counter()
    out = jax.block_until_ready(matrix(psi))
    first_s = time.perf_counter() - t0
    d = max_rel_diff(out, ref(psi))
    dd = max_rel_diff(matrix.dagger(psi), ref.dagger(psi))
    log(f"[{prob.extents}] kernel variant: {variant}; first Dhat "
        f"(compile + run) {first_s:.3f} s")
    log(f"[{prob.extents}] Dhat vs jnp max rel diff {d:.3e}; Dhat^dag "
        f"{dd:.3e} (bound {DHAT_RTOL:g})")
    check(d <= DHAT_RTOL and dd <= DHAT_RTOL,
          f"Dhat/Dhat^dag differ from jnp by {d:.3e}/{dd:.3e}")


def solve_phase(prob, session, nrhs=None, label="solve", runs=2):
    """``runs`` same-shape solves: the first compiles, later ones do not.
    Returns the last solution pair, on the host."""
    times = []
    for i in range(1, runs + 1):
        eta, ee, eo = prob.source(i, nrhs)
        t0 = time.perf_counter()
        xe, xo, res = session.solve(ee, eo)
        times.append(time.perf_counter() - t0)
        rel = prob.true_residual(eta, xe, xo)
        iters = int(res.iterations.max())
        log(f"[{prob.extents}] {label} {i}: iters={iters} true residual "
            f"{rel:.3e} (tol {TOL:g}) {times[-1]:.3f} s")
        check(bool(res.converged.all()), f"{label} {i} did not converge")
        check(rel <= TOL, f"{label} {i} true residual {rel:.3e} > {TOL}")
    if runs > 1:
        log(f"[{prob.extents}] {label}: compile ~{times[0] - times[1]:.3f} "
            f"s (first - second), solve {times[1]:.3f} s")
    check(session.stats()["fallbacks"] == 0, "a fallback was taken")
    return xe, xo


def daemon_phase(prob, matrix) -> None:
    from repro import api
    from repro.serving import BatchingPolicy, PropagatorDaemon

    daemon = PropagatorDaemon(batching=BatchingPolicy(
        max_block=4, buckets=(4,), linger_s=0.5))
    daemon.register("smoke", matrix)
    spec = api.SolveSpec(method=METHOD, tol=TOL)
    daemon.start()
    try:
        sources = [prob.source(10 + i) for i in range(N_REQUESTS)]
        futs = [daemon.submit("smoke", ee, eo, spec, timeout_s=None)
                for _, ee, eo in sources]
        for i, ((eta, _, _), fut) in enumerate(zip(sources, futs)):
            r = fut.result(timeout=900)
            rel = prob.true_residual(eta, r.xi_e[0], r.xi_o[0])
            log(f"[{prob.extents}] daemon request {i}: converged="
                f"{r.converged} true residual {rel:.3e}, rode batch "
                f"{r.stats['batch_id']} ({r.stats['batch_columns']} "
                f"columns in bucket {r.stats['bucket']})")
            check(r.converged and rel <= TOL and not r.stats["degraded"],
                  f"daemon request {i}: residual {rel:.3e}")
    finally:
        daemon.drain(timeout=60)
    m = daemon.metrics()
    log(f"[{prob.extents}] daemon: completed={m['completed']} "
        f"failed={m['failed']} batches={m['batches']}")
    check(m["completed"] == N_REQUESTS and m["failed"] == 0,
          f"daemon answered {m['completed']} of {N_REQUESTS}")


def one_chip(seed: int) -> None:
    from repro import api
    from repro.launch.problem import HostProblem

    spec = api.SolveSpec(method=METHOD, tol=TOL)
    prob = HostProblem(SMALL, KAPPA, seed)
    matrix = bind_pallas(prob)
    operator_phase(prob, matrix)
    session = api.SolveSession(matrix, spec)
    solve_phase(prob, session)
    solve_phase(prob, session, nrhs=N_PROPAGATOR,
                label=f"{N_PROPAGATOR}-column solve")
    daemon_phase(prob, matrix)
    del prob, matrix, session
    gc.collect()

    prob = HostProblem(LARGE, KAPPA, seed)
    matrix = bind_pallas(prob)
    solve_phase(prob, api.SolveSession(matrix, spec), runs=1)
    log(f"[{LARGE}] {N_PROPAGATOR}-column solve not run: it needs 22.5 GB "
        "(see LARGE)")


def four_chips(seed: int) -> None:
    """``distributed`` at 32^3x64 (T over four chips) against one-chip
    ``pallas_fused``, both from one host-made problem."""
    import jax
    import numpy as np

    from repro import api
    from repro.launch.problem import HostProblem

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    spec = api.SolveSpec(method=METHOD, tol=TOL)
    prob = HostProblem(LARGE, KAPPA, seed)
    _, psi, _ = prob.source(0)

    one = bind_pallas(prob)
    ref_dhat = np.asarray(one.apply_native(one.encode(psi)))
    xe1, xo1 = solve_phase(prob, api.SolveSession(one, spec), runs=1,
                           label="one-chip pallas_fused solve")
    del one
    gc.collect()

    dist = prob.bind(api.BackendSpec("distributed"), fallback=False)
    check(dist.backend.name == "distributed" and not dist.degraded,
          "distributed backend did not bind")
    vd = dist.encode(psi)
    mesh = dict(vd.sharding.mesh.shape)
    log(f"[{LARGE}] distributed mesh {mesh} (T over 'data')")
    check(mesh.get("data") == 4, f"T is not split four ways: {mesh}")
    d = max_rel_diff(dist.apply_native(vd), ref_dhat)
    log(f"[{LARGE}] distributed Dhat vs one-chip pallas_fused max rel "
        f"diff {d:.3e} (bound {DHAT_RTOL:g})")
    check(d <= DHAT_RTOL, f"distributed Dhat differs by {d:.3e}")
    xe, xo = solve_phase(prob, api.SolveSession(dist, spec), runs=1,
                         label="distributed solve")
    dx = max(max_rel_diff(xe, xe1), max_rel_diff(xo, xo1))
    log(f"[{LARGE}] distributed solution vs one-chip max rel diff "
        f"{dx:.3e} (bound {SOLUTION_RTOL:g})")
    check(dx <= SOLUTION_RTOL, f"distributed solution differs by {dx:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed path on four chips and "
                         "the one-chip result it is compared with")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except Exception:   # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        return 1
    log(f"compile seconds (this run): {clock.report()}")
    stats = devices[0].memory_stats() or {}
    log(f"peak device memory (device 0): "
        f"{stats.get('peak_bytes_in_use', 'not reported')} B; wall "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

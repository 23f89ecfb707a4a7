"""Chip benchmark of the even-odd Wilson solve: one cell, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  The run makes its gauge field and sources
from ``--seed``, binds them through ``repro.api``, warms up the cell's
own shapes (set-up), measures for ``--seconds``, checks the results
against the plain reference (``chipbench/reference.py``) once the window
has closed, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared, with its limit.  The same checks are the last lines of
standard error.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run of one cell: what a driver needs from the harness."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 clock, t0: float):
        self.cell = cell
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.clock, self.t0 = clock, t0
        self.setup_s = None
        self.compile_setup = None
        self.compile_window = None
        self.memory_peak_bytes = None
        self.reduced_trace = None
        self._tracedir = None
        self._window_span = None

    def span(self, name: str):
        """A host span in the profiler's trace (``bench.<name>``)."""
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def start_window(self) -> float:
        """End of set-up: the measured window starts now."""
        import jax
        self.compile_setup = self.clock.snapshot()
        if self.trace:
            self._tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # no per-call Python events
            jax.profiler.start_trace(self._tracedir, profiler_options=opts)
            self._window_span = self.span("window")
            self._window_span.__enter__()
        now = time.perf_counter()
        self.setup_s = now - self.t0
        return now

    def end_window(self) -> None:
        """The window has closed: stop the trace, read the peak memory."""
        import jax
        if self.trace:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.compile_window = self.clock.snapshot()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:self.cell["chips"]]]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def reduce_trace(self) -> None:
        if not self.trace:
            return
        from chipbench import trace as tr
        try:
            self.reduced_trace = tr.reduce(tr.find_xplane(self._tracedir))
        finally:
            shutil.rmtree(self._tracedir, ignore_errors=True)


def compiled_in_window(run: Run) -> list:
    """Names of the functions XLA compiled inside the window."""
    return run.compile_window["compiled"][
        len(run.compile_setup["compiled"]):]


def execute(bench, cell_name: str, seed: int, seconds: float,
            trace: bool = False, config_overrides=None) -> dict:
    """Set up, measure and check one cell; return the result object.

    Does not look for a chip: ``main`` does.  ``config_overrides``
    replaces keys of the configuration (the control runs of
    ``readings.py`` use it).
    """
    import jax

    from chipbench.compile_clock import CompileClock

    clock = CompileClock()
    cell = bench.cell(cell_name)
    cell["config"] = {**cell["config"], **(config_overrides or {})}
    run = Run(cell, seed, seconds, trace, clock, T0)
    out = bench.driver(cell["traffic"]["driver"]).run(run)

    compiled = compiled_in_window(run)
    checks = dict(out["checks"])
    checks["compiles_in_window"] = (len(compiled), 0, not compiled)
    for line in out.get("notes", []):
        log(line)
    if compiled:
        log(f"compiled inside the window: {compiled}")
    run.reduce_trace()

    metrics = {}
    if trace:
        rec = {"cell": cell, "compile": run.compile_setup,
               "layers": out["layers"], "trace": run.reduced_trace,
               "device_kind": jax.devices()[0].device_kind,
               "root": bench.dir}
        for m in bench.per_layer(cell_name):
            value = bench.reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**out["end_to_end"], "setup_s": run.setup_s}
        for m in bench.end_to_end(cell_name):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": all(ok for _, _, ok in checks.values()),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace:
        t = run.reduced_trace
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim, _) in checks.items()}
    for name, (v, lim, ok) in checks.items():
        log(f"check {name}: {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}")
    return result


def require_chips(n: int) -> None:
    """Exit 2 unless JAX finds at least ``n`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        log(f"chipbench: JAX finds {len(devices)} {devices[0].platform} "
            f"device(s); the cell needs {n} TPU chip(s)")
        sys.exit(2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"chipbench: no program source under {ROOT / 'src'}")
        return 2
    from chipbench.bench import Bench

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    require_chips(cell["chips"])
    # The program's own persistent compilation cache, as its entry points
    # turn it on: $JAX_COMPILATION_CACHE_DIR where that is set, else
    # <checkout>/.jax_cache; any size cap is the environment's.
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = execute(bench, args.workload, args.seed, args.seconds,
                     trace=bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())

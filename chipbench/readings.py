"""Readings behind the limits: several runs of one cell in
one process, one JSON line each.

    python3 chipbench/readings.py --workload <cell> --seeds 11,12,13 \\
        --seconds 4 [--config dtype='"bf16"']

* the program's readings: the compared numbers (``checks``) on a dozen
  seeds or more;
* the control: the same cell with ``--config dtype='"bf16"'``, the
  program's own lower-precision path, which has to come out not correct.

Each ``--config`` value is JSON.  Needs the chip, like
``run.py``; the set-up time it reports is not the cell's (later runs
share the process).
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run  # noqa: E402  (chipbench/ is on sys.path)


def _pairs(items):
    return {k: json.loads(v) for k, v in (i.split("=", 1) for i in items)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--config", action="append", default=[])
    args = ap.parse_args(argv)

    import jax

    from chipbench.bench import Bench

    bench = Bench(bench_run.ROOT)
    bench_run.require_chips(bench.cell(args.workload)["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    config = _pairs(args.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = bench_run.execute(bench, args.workload, seed, args.seconds,
                                    trace=bool(args.trace),
                                    config_overrides=config)
        except Exception as exc:  # noqa: BLE001 — a failed run is a reading
            out = {"error": repr(exc)}
        print(json.dumps({"seed": seed, "config": config, **out}),
              flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())

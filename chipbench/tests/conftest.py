"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

(from the root of the checkout; the repository's ``tests/`` suite does
not collect them)."""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


TINY = {"name": "tiny", "source": "test", "extents": [4, 4, 4, 8],
        "kappa": 0.13, "gauge": "haar", "dtype": "f32",
        "backend": "pallas_fused", "interpret": True, "fallback": False,
        "method": "cgnr", "tol": 1e-6, "true_residual_limit": 1e-5,
        "max_iters": 2000, "chips": 1,
        "assumed": {}, "reduced": []}

# Each tiny cell reports the metrics of the cell it stands in for.
TRAFFIC = {
    "tinyprop": ("prop12", {"driver": "closed_solves", "source": "point",
                            "columns": 12, "pool": 2, "check_solves": 2}),
    "tinysolve": ("solve1", {"driver": "closed_solves",
                             "source": "gaussian", "columns": 1, "pool": 2,
                             "check_solves": 3}),
}


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """A copy of the benchmark with one new configuration and two new
    cells, added as files and entries only."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "chipbench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    for name, (like, traffic) in TRAFFIC.items():
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        cell = f"{name}.tiny"
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": name, "chips": 1,
                                  "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if any(w.startswith(like + ".") for w in m.get("workloads", [])):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    from chipbench.bench import Bench
    return Bench(root)

"""Paper Fig. 10 analogue: weak scaling of the even-odd Wilson operator.

Fixed local volume per device, growing device count (forced host devices
in subprocesses: 1, 2, 4, 8).  Reports wall time per Dhat application and
the sustained-throughput-per-device ratio to the 1-device case — the
paper's "performance per node is almost constant" claim, reproduced
structurally on CPU.  The TPU-projected version of this figure comes from
the dry-run collective terms (see EXPERIMENTS.md §Roofline).

Each device count also sweeps the stored SU(3) link representation
(``none`` / ``two_row`` / ``minimal``): compressed links are shipped
compressed, so the multi-host rows carry the per-compression *exchange*
bytes from :func:`repro.distributed.halo.halo_traffic_model` alongside
the measured time — ``weak_scaling_n{N}_{comp}`` rows in
``BENCH_scaling.json`` show gauge halo traffic shrinking 33%/55% with
the storage while the stencil reconstructs links in-register.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

from .common import Row, smoke, write_json

COMPRESSIONS = ("none", "two_row", "minimal")

_CHILD = """
import os
import time
import jax, jax.numpy as jnp
from repro import compat
from repro.core import su3, evenodd
from repro.kernels import layout, ops
from repro.distributed import qcd

n = jax.device_count()
smoke = os.environ.get("REPRO_BENCH_SMOKE", "") not in ("", "0")
Tl = 4
T, Z, Y, X = (Tl * n, 4, 4, 8) if smoke else (Tl * n, 8, 8, 16)
U = su3.random_gauge(jax.random.PRNGKey(0), (T, Z, Y, X))
psi = (jax.random.normal(jax.random.PRNGKey(1), (T, Z, Y, X, 4, 3))
       + 1j*jax.random.normal(jax.random.PRNGKey(2), (T, Z, Y, X, 4, 3))
       ).astype(jnp.complex64)
Ue, Uo = evenodd.pack_gauge(U)
e, _ = evenodd.pack(psi)
ep = layout.spinor_to_planar(e)
mesh = compat.make_mesh((n, 1), ("data", "model"))
part = qcd.QCDPartition.for_mesh(mesh, backend="jnp", overlap="fused")
ep_d = jax.device_put(ep, part.spinor_sharding())
for comp in ("none", "two_row", "minimal"):
    # compressed links are stored AND shipped compressed: the planar
    # comps axis shrinks before placement, so halo faces shrink with it
    Uep, Uop = ops.make_planar_fields(Ue, Uo, compression=comp)
    dhat = jax.jit(qcd.make_dhat_fn(part, 0.13))
    args = (jax.device_put(Uep, part.gauge_sharding()),
            jax.device_put(Uop, part.gauge_sharding()),
            ep_d)
    for _ in range(2):
        jax.block_until_ready(dhat(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(dhat(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    print("RESULT", n, comp, ts[len(ts)//2])
"""


def run() -> list:
    from repro.distributed.halo import halo_traffic_model
    from repro.kernels.layout import GAUGE_COMPRESSIONS

    rows: list[Row] = []
    repo = pathlib.Path(__file__).resolve().parents[1]
    base = None
    for n in (1, 2) if smoke() else (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n} "
                            + env.get("XLA_FLAGS", ""))
        env["PYTHONPATH"] = str(repo / "src")
        # The children are forced-host-device CPU experiments: keep them
        # off an accelerator, which this process may already hold.
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c",
                              textwrap.dedent(_CHILD)],
                             capture_output=True, text=True, env=env,
                             timeout=900)
        if out.returncode != 0:
            rows.append((f"weak_scaling_n{n}", -1.0,
                         f"error={out.stderr.strip()[-120:]}"))
            continue
        results = {}
        for line in out.stdout.splitlines():
            if line.startswith("RESULT"):
                _, n_s, comp, t_s = line.split()
                results[comp] = float(t_s)

        # headline row (uncompressed): weak-scaling efficiency
        us = results["none"] * 1e6
        if base is None:
            base = us
        rows.append((f"weak_scaling_n{n}", us,
                     f"efficiency={base / us:.3f}"))

        # per-compression rows: measured time + modeled per-rank
        # exchange bytes for this local block (Tl fixed, Z unsharded)
        Tl = 4
        _, Z, Y, X = (4, 4, 4, 8) if smoke() else (4, 8, 8, 16)
        none_us = results["none"] * 1e6
        for comp in COMPRESSIONS:
            if comp not in results:
                continue
            traffic = halo_traffic_model(
                Tl, Z, Y, X // 2,
                gauge_comps=GAUGE_COMPRESSIONS[comp])
            cus = results[comp] * 1e6
            rows.append((
                f"weak_scaling_n{n}_{comp}", cus,
                f"gauge_comps={GAUGE_COMPRESSIONS[comp]};"
                f"bytes_gauge_exchange={traffic['bytes_gauge_exchange']};"
                f"bytes_spinor_exchange="
                f"{traffic['bytes_spinor_exchange']};"
                f"bytes_dhat_exchange={traffic['bytes_dhat_exchange']};"
                f"time_vs_none={cus / none_us:.2f}x"))
    write_json("scaling", rows)
    return rows

"""Host sources that cross to the operator's device as lane-dense
float32 views of their own bytes (``repro.kernels.layout``'s helper pair
and ``SolveSession``'s path for them)."""
import ast
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import layout


@pytest.mark.parametrize("shape", [
    (4, 4, 4, 4, 4, 3),             # one column
    (2, 4, 4, 4, 4, 4, 3),          # nrhs = 2
    (12, 4, 4, 4, 4, 4, 3),         # a 12-column propagator
    (2, 2, 2, 3, 4, 3),             # 576 reals: no whole (8, 128) tile
])
def test_lane_dense_codec_is_the_planar_codec(shape):
    """The lane-dense view, transposed by the traceable helpers, gives
    the planar vector of ``spinor_to_planar`` and the spinor of
    ``spinor_from_planar`` bit for bit; a shape that does not fill
    whole tiles has no lane-dense view."""
    k = jax.random.PRNGKey(7)
    psi = (jax.random.normal(k, shape)
           + 1j * jax.random.normal(jax.random.fold_in(k, 1), shape)
           ).astype(jnp.complex64)
    fits = layout.lanes_fit(shape, psi.dtype)
    assert fits == (shape[-3] != 3)
    if not fits:
        return
    assert not layout.lanes_fit(shape, jnp.complex128)
    lanes = layout.spinor_lanes(psi)
    assert lanes.shape == (2 * psi.size // 128, 128)
    assert np.shares_memory(lanes, np.asarray(psi))    # a view, no copy

    planar = jax.jit(lambda v: layout.planar_from_lanes(v, shape))(lanes)
    want = np.asarray(layout.spinor_to_planar(psi))
    assert planar.shape == want.shape
    np.testing.assert_array_equal(np.asarray(planar).view(np.uint32),
                                  want.view(np.uint32))

    back = layout.spinor_from_lanes(
        np.asarray(jax.jit(layout.lanes_from_planar)(want)), shape)
    ref = np.asarray(layout.spinor_from_planar(want))
    assert back.shape == ref.shape and back.dtype == ref.dtype
    np.testing.assert_array_equal(back.view(np.uint32),
                                  ref.view(np.uint32))


_LANE_PATH_BODY = """
import jax
import numpy as np
from repro import api
from repro.launch import problem

devs = jax.devices()
host, chip = devs[1], devs[0]
problem.host_device = lambda: host          # 'host' = device 1
prob = problem.HostProblem((4, 4, 4, 8), 0.13, seed=3)
m = prob.bind(api.BackendSpec("pallas_fused", interpret=True))
assert m.devices() == {chip} and m.domain == "planar"
out = {}

# Two columns from the host, twice: one executable.
eta, ee, eo = prob.source(0, nrhs=2)
assert ee.sharding.device_set == {host}
session = api.SolveSession(m, api.SolveSpec(method="cgnr", tol=1e-6))
session.solve(ee, eo)
xe, xo, res = session.solve(ee, eo)
st = session.stats()
out["traces"], out["lane"] = st["traces"], st["lane_dense_solves"]
out["home"] = [xe.sharding.device_set == {host},
               xo.sharding.device_set == {host}]
out["dtype"] = [str(xe.dtype), str(xo.dtype)]
out["shape"] = xe.shape == ee.shape and xo.shape == eo.shape
out["true_residual"] = prob.true_residual(eta, xe, xo)

# The same solve with the sources already on the operator's device
# takes the other path.
ce, co = jax.device_put((ee, eo), chip)
ye, yo, res_dev = session.solve(ce, co)
out["lane_after_dev"] = session.stats()["lane_dense_solves"]
out["iters"] = [list(map(int, res.iterations)),
                list(map(int, res_dev.iterations))]
out["rel"] = max(
    float(np.linalg.norm(np.asarray(a) - np.asarray(b))
          / np.linalg.norm(np.asarray(b)))
    for a, b in ((xe, ye), (xo, yo)))

# A donating, recycle-deflated key on the same path: the sources are
# left as they were and the planar solution is harvested.
eta1, e1, o1 = prob.source(1)
kept = np.array(e1), np.array(o1)
rec = api.SolveSession(m, api.SolveSpec(
    method="cgnr", tol=1e-6, donate_rhs=True, deflate_rank=2,
    deflate_mode="recycle"))
ze, zo, zres = rec.solve(e1, o1)
st = rec.stats()
(row,) = st["keys"].values()
out["recycle"] = {
    "lane": st["lane_dense_solves"], "traces": st["traces"],
    "converged": bool(zres.converged),
    "home": ze.sharding.device_set == {host},
    "harvested": row["deflation"]["harvested"],
    "true_residual": prob.true_residual(eta1, ze, zo),
    "sources_kept": all(np.array_equal(k, np.asarray(v))
                        for k, v in zip(kept, (e1, o1))),
}
print("OUT", repr(out))
"""


@pytest.fixture(scope="module")
def lane_path():
    """Host sources on one device and a ``pallas_fused`` (interpret)
    operator on the other, in a subprocess with two host devices."""
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", ""))
    out = subprocess.run([sys.executable, "-c", _LANE_PATH_BODY],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    (line,) = [ln for ln in out.stdout.splitlines()
               if ln.startswith("OUT ")]
    return ast.literal_eval(line[4:])


def test_host_sources_take_the_lane_dense_path(lane_path):
    """Both solves of host sources ran on the lane-dense path, under one
    trace, and came back complex64 on the sources' device."""
    assert lane_path["lane"] == 2
    assert lane_path["traces"] == 1
    assert lane_path["home"] == [True, True]
    assert lane_path["dtype"] == ["complex64", "complex64"]
    assert lane_path["shape"]
    assert lane_path["true_residual"] <= 1e-5


def test_lane_dense_path_matches_device_sources(lane_path):
    """Sources already on the operator's device take the other path,
    with the same iterations per column and the same solution."""
    assert lane_path["lane_after_dev"] == 2
    its_lane, its_dev = lane_path["iters"]
    assert its_lane == its_dev and len(its_lane) == 2
    assert lane_path["rel"] <= 1e-6


def test_lane_dense_path_donates_and_recycles(lane_path):
    """A ``donate_rhs`` recycle-deflated key runs on the same path:
    the caller's sources survive and the solution is harvested."""
    rec = lane_path["recycle"]
    assert rec["lane"] == 1 and rec["traces"] == 1
    assert rec["converged"] and rec["home"]
    assert rec["harvested"] == 1
    assert rec["true_residual"] <= 1e-5
    assert rec["sources_kept"]

"""End-to-end behaviour tests for the paper's system: the even-odd Wilson
solve pipeline from gauge field to verified solution, through the public
API, including the Pallas-backed path and checkpoint/restart."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import api, configs
from repro.core import evenodd, su3, wilson
from repro.kernels import ops


def test_end_to_end_solve_paper_pipeline():
    """The full pipeline of the paper: random gauge -> even-odd pack ->
    Schur-preconditioned Krylov solve -> reconstruct -> verify."""
    lat = configs.get_qcd("wilson-16x16x16x16")
    # shrink to CI size but keep the pipeline identical
    T, Z, Y, X = 8, 8, 8, 8
    kappa = lat.kappa
    U = su3.random_gauge(jax.random.PRNGKey(0), (T, Z, Y, X))
    eta = (jax.random.normal(jax.random.PRNGKey(1), (T, Z, Y, X, 4, 3))
           + 1j * jax.random.normal(jax.random.PRNGKey(2),
                                    (T, Z, Y, X, 4, 3))
           ).astype(jnp.complex64)
    Ue, Uo = evenodd.pack_gauge(U)
    ee, eo = evenodd.pack(eta)
    xe, xo, res = api.solve(Ue, Uo, ee, eo, kappa,
                            spec=api.SolveSpec(method="bicgstab",
                                               tol=1e-6))
    xi = evenodd.unpack(xe, xo)
    rel = float(jnp.linalg.norm(eta - wilson.apply_wilson(U, xi, kappa))
                / jnp.linalg.norm(eta))
    assert rel < 1e-5
    assert int(res.iterations) < 100


def test_solver_driver_cli(tmp_path, capsys):
    """The launch/solve.py driver runs end to end with checkpointing."""
    from repro.launch import solve

    solve.main(["--lattice", "wilson-16x16x16x16", "--tol", "1e-5",
                "--ckpt-dir", str(tmp_path), "--n-solves", "1"])
    out = capsys.readouterr().out
    assert "solve 0:" in out and "done" in out
    # The solve's time and rate leave out the host reference check,
    # which the line reports on its own.
    assert "GFlop/s sustained; reference check" in out
    from repro.checkpoint.ckpt import Checkpointer
    assert Checkpointer(str(tmp_path)).latest_step() == 0


def test_kernel_backend_equals_jnp_backend_end_to_end(small_lattice,
                                                      small_eo):
    """Dhat through the Pallas kernel == through pure jnp, applied twice
    (operator composition stability)."""
    U, _, kappa = small_lattice
    Ue, Uo, e, _, _ = small_eo
    from repro.kernels import layout, ref

    Uep, Uop = ops.make_planar_fields(Ue, Uo)
    ep = layout.spinor_to_planar(e)
    a = ops.apply_dhat_planar(Uep, Uop, ep, kappa, interpret=True)
    a = ops.apply_dhat_planar(Uep, Uop, a, kappa, interpret=True)
    b = ref.apply_dhat_planar_ref(Uep, Uop, ep, kappa)
    b = ref.apply_dhat_planar_ref(Uep, Uop, b, kappa)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_arch_registry_complete():
    assert len(configs.ARCH_NAMES) == 10
    for name in configs.ARCH_NAMES:
        cfg = configs.get(name)
        cells = configs.shapes_for(cfg)
        assert len(cells) == 4
        # long_500k must run for subquadratic archs, skip for the rest
        long_skip = dict((c.name, s) for c, s in cells)["long_500k"]
        if cfg.subquadratic:
            assert long_skip is None
        else:
            assert long_skip is not None


def test_shape_cells_constants():
    s = configs.SHAPE_BY_NAME
    assert s["train_4k"].seq_len == 4096 and \
        s["train_4k"].global_batch == 256
    assert s["prefill_32k"].seq_len == 32768 and \
        s["prefill_32k"].global_batch == 32
    assert s["decode_32k"].seq_len == 32768 and \
        s["decode_32k"].global_batch == 128
    assert s["long_500k"].seq_len == 524288 and \
        s["long_500k"].global_batch == 1

"""ADMM solver correctness: convergence, block equivalence, SPMD parity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import admm
from repro.data.synthetic import make_lasso


@pytest.fixture(scope="module")
def inst():
    return make_lasso(60, 240, sparsity=0.05, noise=0.01, seed=0)


def test_centralized_converges(inst):
    cfg = admm.ADMMConfig(lam=0.05, iters=300)
    x, hist = admm.centralized_admm(jnp.asarray(inst.A),
                                    jnp.asarray(inst.y), cfg)
    mse = float(np.mean((np.asarray(x) - inst.x_true) ** 2))
    assert mse < 5e-3
    # objective is (eventually) non-increasing over the tail
    objs = [float(admm.lasso_objective(jnp.asarray(inst.A),
                                       jnp.asarray(inst.y),
                                       hist[i], 0.05)) for i in (100, 299)]
    assert objs[1] <= objs[0] + 1e-6


def test_distributed_close_to_centralized(inst):
    cfg = admm.ADMMConfig(lam=0.05, iters=300)
    xc, _ = admm.centralized_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  cfg)
    xd, _ = admm.distributed_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  4, cfg)
    mse_c = float(np.mean((np.asarray(xc) - inst.x_true) ** 2))
    mse_d = float(np.mean((np.asarray(xd) - inst.x_true) ** 2))
    assert mse_d < mse_c + 0.1   # paper: ~0.07 gap at scale


def test_coupled_beats_uncoupled(inst):
    base = admm.ADMMConfig(lam=0.05, iters=300)
    xu, _ = admm.distributed_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  4, base)
    xq, _ = admm.distributed_admm(
        jnp.asarray(inst.A), jnp.asarray(inst.y), 4,
        admm.ADMMConfig(lam=0.05, iters=300, coupled=True))
    mse_u = float(np.mean((np.asarray(xu) - inst.x_true) ** 2))
    mse_q = float(np.mean((np.asarray(xq) - inst.x_true) ** 2))
    assert mse_q < mse_u


def test_consistent_scaling_beats_paper_printed(inst):
    a = admm.ADMMConfig(lam=0.05, iters=300, y_scale="consistent")
    b = admm.ADMMConfig(lam=0.05, iters=300, y_scale="paper")
    xa, _ = admm.distributed_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  4, a)
    xb, _ = admm.distributed_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  4, b)
    mse_a = float(np.mean((np.asarray(xa) - inst.x_true) ** 2))
    mse_b = float(np.mean((np.asarray(xb) - inst.x_true) ** 2))
    assert mse_a < mse_b


def test_dp_admm_noise_hurts(inst):
    cfg = admm.ADMMConfig(lam=0.05, iters=300)
    xd, _ = admm.distributed_admm(jnp.asarray(inst.A), jnp.asarray(inst.y),
                                  4, cfg)
    xdp, _ = admm.dp_admm(jnp.asarray(inst.A), jnp.asarray(inst.y), 4, cfg,
                          sigma=0.05, key=jax.random.PRNGKey(0))
    mse_d = float(np.mean((np.asarray(xd) - inst.x_true) ** 2))
    mse_dp = float(np.mean((np.asarray(xdp) - inst.x_true) ** 2))
    assert mse_dp > mse_d


def test_soft_threshold_properties():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
    out = np.asarray(admm.soft_threshold(x, 1.0))
    assert np.allclose(out, [-1.0, 0.0, 0.0, 0.0, 1.0])


def test_spmd_matches_blocked(subproc):
    subproc("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import admm
        from repro.data.synthetic import make_lasso
        from repro.launch.mesh import make_mesh
        inst = make_lasso(40, 160, 0.05, 0.01, seed=1)
        cfg = admm.ADMMConfig(lam=0.05, iters=100)
        x_ref, _ = admm.distributed_admm(jnp.asarray(inst.A),
                                         jnp.asarray(inst.y), 4, cfg)
        mesh = make_mesh((4,), ("data",))
        run = admm.make_spmd_admm(mesh, cfg, 4)
        with mesh:
            x, objs = run(jnp.asarray(inst.A), jnp.asarray(inst.y))
        d = float(np.max(np.abs(np.asarray(x) - np.asarray(x_ref))))
        assert d < 1e-8, d
        print("spmd parity:", d)
    """, devices=4)

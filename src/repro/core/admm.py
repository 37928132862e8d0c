"""ADMM LASSO solvers: centralized, distributed (paper eq. 10), coupled
consensus variant (beyond paper), and the DP-ADMM baseline.

All solvers are pure JAX (float64 — the paper's CPU doubles regime) and
jit-able; the distributed solver also ships a ``shard_map`` SPMD form where
each mesh device plays one edge node (launch/ scales this to the production
mesh).

Note on eq. (9)/(10a): the paper's x-update prints ``A_k^T y`` although the
decoupled subproblem (8) it solves contains ``y/K``, whose stationary point
is ``x_k = (A_k^T A_k + rho I)^{-1} (A_k^T y / K + rho (z_k - v_k))``. We
expose ``y_scale``: ``1/K`` (mathematically consistent, default) or ``1.0``
(paper as printed). benchmarks/bench_mse.py reports both.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

jax.config.update("jax_enable_x64", True)


@dataclasses.dataclass(frozen=True)
class ADMMConfig:
    rho: float = 1.0
    lam: float = 1.0
    iters: int = 100
    y_scale: str = "consistent"   # "consistent" (y/K) | "paper" (y)
    coupled: bool = False         # beyond-paper consensus coupling


def soft_threshold(x: jax.Array, t: float) -> jax.Array:
    """S_t(x) = sign(x) max(|x| - t, 0) (eq. 4b's shrinkage operator)."""
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def lasso_objective(A, y, x, lam):
    r = y - A @ x
    return 0.5 * jnp.vdot(r, r).real + lam * jnp.sum(jnp.abs(x))


# ---------------------------------------------------------------------------
# Centralized ADMM (eq. 4) — the paper's accuracy gold standard
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def centralized_admm(A: jax.Array, y: jax.Array, cfg: ADMMConfig):
    """Returns (x, history of per-iteration x) solving eq. (1)."""
    M, N = A.shape
    Bmat = jnp.linalg.inv(A.T @ A + cfg.rho * jnp.eye(N, dtype=A.dtype))
    Aty = A.T @ y

    def step(state, _):
        x, z, v = state
        x = Bmat @ (Aty + cfg.rho * (z - v))
        z = soft_threshold(v + x, cfg.lam / cfg.rho)
        v = v + x - z
        return (x, z, v), x

    z0 = jnp.zeros(N, A.dtype)
    (x, z, v), hist = jax.lax.scan(step, (z0, z0, z0), None, length=cfg.iters)
    return x, hist


# ---------------------------------------------------------------------------
# Distributed ADMM (paper eq. 10) — single-host blocked reference
# ---------------------------------------------------------------------------

def split_columns(A: np.ndarray, K: int) -> list[np.ndarray]:
    """Column blocks A_k; N need not divide K (last block is smaller)."""
    N = A.shape[1]
    sizes = [N // K + (1 if i < N % K else 0) for i in range(K)]
    out, ofs = [], 0
    for s in sizes:
        out.append(A[:, ofs:ofs + s])
        ofs += s
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "K"))
def distributed_admm(A: jax.Array, y: jax.Array, K: int, cfg: ADMMConfig):
    """Paper's synchronous (Jacobi) distributed ADMM, blocks stacked.

    Requires N % K == 0 (callers pad); returns (x, per-iter history).
    The x-update uses the (t-1) iterates exactly as eq. (10) — this is what
    lets all K blocks run in parallel and is what the privacy protocol wraps.
    """
    M, N = A.shape
    assert N % K == 0
    Nk = N // K
    Ak = jnp.transpose(A.reshape(M, K, Nk), (1, 0, 2))          # (K, M, Nk)
    eye = jnp.eye(Nk, dtype=A.dtype)
    Bk = jnp.linalg.inv(jnp.einsum("kmi,kmj->kij", Ak, Ak) + cfg.rho * eye)
    ys = y / K if cfg.y_scale == "consistent" else y
    AkTy = jnp.einsum("kmi,m->ki", Ak, ys)                      # (K, Nk)
    alpha = jnp.einsum("kij,kj->ki", Bk, AkTy)                  # B_k A_k^T y

    def step(state, _):
        x, z, v = state                                          # (K, Nk)
        if cfg.coupled:
            # beyond-paper: damped Jacobi residual coupling. Each block
            # re-fits its own contribution plus a 1/K share of the global
            # residual (undamped Jacobi — every block absorbing the full
            # residual simultaneously — diverges for K > 1).
            s = jnp.einsum("kmi,ki->m", Ak, x)
            r_k = (jnp.einsum("kmi,ki->km", Ak, x)
                   + (y - s)[None, :] / K)
            rhs = jnp.einsum("kmi,km->ki", Ak, r_k) + cfg.rho * (z - v)
            x_new = jnp.einsum("kij,kj->ki", Bk, rhs)
        else:
            x_new = alpha + cfg.rho * jnp.einsum("kij,kj->ki", Bk, z - v)
        z_new = soft_threshold(v + x, cfg.lam / cfg.rho)         # uses x^{t-1}
        v_new = v + x - z_new
        return (x_new, z_new, v_new), x_new

    z0 = jnp.zeros((K, Nk), A.dtype)
    (x, z, v), hist = jax.lax.scan(step, (z0, z0, z0), None, length=cfg.iters)
    return x.reshape(N), hist.reshape(cfg.iters, N)


# ---------------------------------------------------------------------------
# DP-ADMM baseline [22]: distributed ADMM + Gaussian perturbation of the
# shared primal iterate each round (privacy via noise instead of HE)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "K"))
def dp_admm(A: jax.Array, y: jax.Array, K: int, cfg: ADMMConfig,
            sigma: float, key: jax.Array):
    M, N = A.shape
    assert N % K == 0
    Nk = N // K
    Ak = jnp.transpose(A.reshape(M, K, Nk), (1, 0, 2))
    eye = jnp.eye(Nk, dtype=A.dtype)
    Bk = jnp.linalg.inv(jnp.einsum("kmi,kmj->kij", Ak, Ak) + cfg.rho * eye)
    ys = y / K if cfg.y_scale == "consistent" else y
    alpha = jnp.einsum("kij,kj->ki", Bk, jnp.einsum("kmi,m->ki", Ak, ys))

    def step(state, rkey):
        x, z, v = state
        x_new = alpha + cfg.rho * jnp.einsum("kij,kj->ki", Bk, z - v)
        # the shared (published) iterate is noised — the DP mechanism
        x_new = x_new + sigma * jax.random.normal(rkey, x_new.shape, x.dtype)
        z_new = soft_threshold(v + x, cfg.lam / cfg.rho)
        v_new = v + x - z_new
        return (x_new, z_new, v_new), x_new

    z0 = jnp.zeros((K, Nk), A.dtype)
    keys = jax.random.split(key, cfg.iters)
    (x, _, _), hist = jax.lax.scan(step, (z0, z0, z0), keys)
    return x.reshape(N), hist.reshape(cfg.iters, N)


# ---------------------------------------------------------------------------
# SPMD distributed ADMM: one mesh device per edge node (shard_map)
# ---------------------------------------------------------------------------

def make_spmd_admm(mesh, cfg: ADMMConfig, K: int, axis: str = "data"):
    """Build a pjit-able distributed ADMM over ``mesh`` with x/z/v sharded
    on ``axis`` (each shard = one edge node's block).

    Returns step(A_sh, y, state) -> (state, diagnostics) where
    A_sh: (M, N) sharded P(None, axis); state x/z/v: (N,) sharded P(axis).
    The uncoupled (paper) form runs with ZERO cross-edge collectives; the
    coupled form all-reduces the M-dim partial products (one psum).
    """
    def local_setup(Ak, y):
        Nk = Ak.shape[1]
        Bk = jnp.linalg.inv(Ak.T @ Ak + cfg.rho * jnp.eye(Nk, dtype=Ak.dtype))
        ys = y / K if cfg.y_scale == "consistent" else y
        return Bk, Ak.T @ ys

    def step_local(Ak, y, x, z, v):
        Bk, AkTy = local_setup(Ak, y)
        if cfg.coupled:
            s = jax.lax.psum(Ak @ x, axis)
            r = Ak @ x + (y - s) / K     # damped Jacobi share
            x_new = Bk @ (Ak.T @ r + cfg.rho * (z - v))
        else:
            x_new = Bk @ (AkTy + cfg.rho * (z - v))
        z_new = soft_threshold(v + x, cfg.lam / cfg.rho)
        v_new = v + x - z_new
        # global diagnostics: objective pieces
        res = jax.lax.psum(Ak @ x_new, axis)
        l1 = jax.lax.psum(jnp.sum(jnp.abs(x_new)), axis)
        obj = 0.5 * jnp.sum((y - res) ** 2) + cfg.lam * l1
        return x_new, z_new, v_new, obj

    smapped = jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(P(None, axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P()),
    )

    @jax.jit
    def run(A, y):
        N = A.shape[1]
        z0 = jnp.zeros(N, A.dtype)

        def body(state, _):
            x, z, v = state
            x, z, v, obj = smapped(A, y, x, z, v)
            return (x, z, v), obj

        (x, z, v), objs = jax.lax.scan(body, (z0, z0, z0), None,
                                       length=cfg.iters)
        return x, objs

    return run

"""Train-step builders.

* ``make_train_step``     — pjit path: loss -> grad -> AdamW, gradient
  all-reduce inserted by SPMD partitioning from the param/batch shardings.
  This is the step the multi-pod dry-run lowers for every train cell.
* ``make_dp_compressed_step`` — shard_map pure-DP path with the paper-derived
  Gamma-quantized compressed all-reduce + error feedback (secure_agg) — the
  gradient-compression feature demonstrated in tests/examples and measured
  (collective bytes) in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import optimizer as opt_mod
from ..core import secure_agg
from ..models import registry


def make_train_step(cfg, opt_cfg: opt_mod.OptConfig, *, use_scan=True,
                    remat=True, accum: int = 1) -> Callable:
    """(state, batch) -> (state, metrics); pure function of pjit shardings.

    ``accum`` > 1 enables microbatch gradient accumulation (a lax.scan over
    accum microbatches with a running gradient carry) — the standard lever
    that bounds activation memory for the widest configs at train_4k scale.
    """
    model = registry.get_model(cfg)

    def loss_of(params, batch):
        kw = {"remat": remat}
        if cfg.family in ("dense", "moe", "encdec"):
            kw["use_scan"] = use_scan
        return model.loss_fn(params, batch, cfg, **kw)

    def grads_of(params, batch):
        if accum == 1:
            return jax.value_and_grad(loss_of)(params, batch)

        def split(x):
            return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            g_acc, l_acc = carry
            loss, g = jax.value_and_grad(loss_of)(params, mb)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, l_acc + loss), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_sum, l_sum), _ = jax.lax.scan(
            body, (g0, jnp.zeros((), jnp.float32)), micro)
        return l_sum / accum, jax.tree.map(lambda g: g / accum, g_sum)

    def train_step(state, batch):
        loss, grads = grads_of(state["params"], batch)
        params, opt_state, om = opt_mod.adamw_update(
            grads, state["opt"], state["params"], opt_cfg)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, **om}

    return train_step


def init_train_state(cfg, key):
    model = registry.get_model(cfg)
    params = model.init(cfg, key)
    return {"params": params, "opt": opt_mod.init_opt_state(params),
            "step": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# Compressed-DP step (shard_map over `data`): the paper's quantizer as
# gradient compression with error feedback
# ---------------------------------------------------------------------------

def make_dp_compressed_step(cfg, opt_cfg: opt_mod.OptConfig, mesh,
                            comp: secure_agg.CompressionConfig,
                            axis: str = "data") -> Callable:
    """Pure data-parallel trainer whose gradient all-reduce is quantized.

    state adds a ``residuals`` pytree (error feedback). Batch is sharded on
    ``axis``; params replicated (DP). Loss/metrics are psum-averaged.
    """
    model = registry.get_model(cfg)

    def local_step(params, opt_state, residuals, batch):
        n_dev = jax.lax.psum(jnp.ones(()), axis)

        def loss_of(p):
            return model.loss_fn(p, batch, cfg, use_scan=False)

        loss, grads = jax.value_and_grad(loss_of)(params)
        grads, residuals = secure_agg.compress_tree_psum(
            grads, axis, comp, residuals)
        grads = jax.tree.map(lambda g: g / n_dev, grads)
        params, opt_state, om = opt_mod.adamw_update(
            grads, opt_state, params, opt_cfg)
        loss = jax.lax.psum(loss, axis) / n_dev
        return params, opt_state, residuals, loss, om["grad_norm"]

    p_rep = P()
    smapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_rep, p_rep, p_rep, P(axis)),
        out_specs=(p_rep, p_rep, p_rep, p_rep, p_rep),
        check_vma=False,
    )

    @jax.jit
    def step(state, batch):
        params, opt_state, residuals, loss, gn = smapped(
            state["params"], state["opt"], state["residuals"], batch)
        return ({"params": params, "opt": opt_state, "residuals": residuals,
                 "step": state["step"] + 1},
                {"loss": loss, "grad_norm": gn})

    return step


def init_dp_state(cfg, key):
    state = init_train_state(cfg, key)
    state["residuals"] = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
    return state

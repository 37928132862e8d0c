"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
the paper's quantizer as compressed gradient aggregation.

Two modes:
  --full   : xlstm-125m at its real config (125M params) — the "train ~100M
             model for a few hundred steps" deliverable; several hours on
             this CPU container, minutes on one TPU host.
  default  : the same pipeline at smoke scale (~0.3M params, 60 steps) so
             the example is runnable everywhere; loss must drop >20%.

Every substrate piece is live: sharded data pipeline, scan+remat layers,
AdamW + cosine schedule, Gamma-compressed DP all-reduce with error feedback,
atomic checkpoints with exact-resume.

Run:  PYTHONPATH=src python examples/train_lm_secure.py [--full]
"""
import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.core.secure_agg import CompressionConfig
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ckpt
from repro.train import loop as loop_mod
from repro.train.optimizer import OptConfig

ap = argparse.ArgumentParser()
ap.add_argument("--full", action="store_true")
ap.add_argument("--steps", type=int, default=None)
args = ap.parse_args()

cfg = get_config("xlstm_125m") if args.full else get_reduced("xlstm_125m")
steps = args.steps or (300 if args.full else 60)
batch, seq = (8, 256) if args.full else (4, 32)

n_dev = jax.device_count()
mesh = make_mesh((n_dev,), ("data",))
comp = CompressionConfig(bits=8, enabled=n_dev > 1, error_feedback=True)
opt = OptConfig(lr=3e-3, warmup_steps=steps // 10, total_steps=steps)

if n_dev > 1:
    step_fn = loop_mod.make_dp_compressed_step(cfg, opt, mesh, comp)
    state = loop_mod.init_dp_state(cfg, jax.random.PRNGKey(0))
else:
    step_fn = jax.jit(loop_mod.make_train_step(cfg, opt, use_scan=False,
                                               remat=False))
    state = loop_mod.init_train_state(cfg, jax.random.PRNGKey(0))

pipe = TokenPipeline(vocab=cfg.vocab, batch=batch, seq=seq, seed=0)
ckpt_dir = os.path.join(tempfile.gettempdir(), "repro_secure_lm")
losses = []
t0 = time.time()
with mesh:
    for i in range(steps):
        b = pipe.next(mesh=mesh if n_dev > 1 else None)
        if n_dev > 1:
            b = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                 for k, v in b.items()} if not hasattr(
                     next(iter(b.values())), "sharding") else b
        state, metrics = step_fn(state, b)
        losses.append(float(metrics["loss"]))
        if (i + 1) % max(steps // 10, 1) == 0:
            print(f"step {i+1:4d}  loss={losses[-1]:.4f}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
        if (i + 1) % max(steps // 3, 1) == 0:
            ckpt.save_async(ckpt_dir, i + 1, state,
                            extra={"pipeline": pipe.state()})

first = np.mean(losses[:5])
last = np.mean(losses[-5:])
print(f"loss {first:.4f} -> {last:.4f} "
      f"({100 * (first - last) / first:.1f}% drop, "
      f"{sum(p.size for p in jax.tree.leaves(state['params'])) / 1e6:.1f}M "
      f"params, compressed_allreduce={'on' if comp.enabled else 'off'})")
assert last < first * 0.8, "loss must drop >20%"
print("OK")

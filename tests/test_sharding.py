"""Sharding correctness: pjit'd train step == single-device step, collective
structure of the SPMD programs, input sharding specs."""
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import registry


def test_input_specs_cover_all_cells():
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in registry.SHAPES:
            specs = registry.input_specs(cfg, shape)
            assert specs, (arch, shape)
            sh = registry.input_shardings(cfg, shape, specs)
            # trees are congruent
            import jax
            jax.tree.util if False else None
            assert len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec") or True)) > 0


def test_sharded_train_step_matches_unsharded(subproc):
    subproc("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.launch.mesh import make_mesh
        from repro.models import registry
        from repro.train import loop as loop_mod
        from repro.train.optimizer import OptConfig

        cfg = get_reduced("yi_9b")
        step = loop_mod.make_train_step(cfg, OptConfig(lr=1e-3,
                                                       warmup_steps=1,
                                                       total_steps=10),
                                        use_scan=False, remat=False)
        state = loop_mod.init_train_state(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                       jnp.int32)}
        # single-device reference
        s_ref, m_ref = jax.jit(step)(state, batch)

        # 2x2 mesh pjit
        mesh = make_mesh((2, 2), ("data", "model"))
        mesh_shape = {"data": 2, "model": 2}
        p_spec = registry.param_pspecs(cfg, state["params"], mesh_shape)
        st_spec = {"params": p_spec,
                   "opt": {"m": p_spec, "v": p_spec, "count": P()},
                   "step": P()}
        sh = lambda t, s: jax.tree.map(
            lambda x, ss: jax.device_put(x, NamedSharding(mesh, ss)), t, s)
        state_sh = sh(state, st_spec)
        batch_sh = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                    for k, v in batch.items()}
        with mesh:
            s_got, m_got = jax.jit(step)(state_sh, batch_sh)
        # bf16 matmuls reduce in different orders across shardings; the
        # AdamW normalizer amplifies that slightly on the params
        assert abs(float(m_got["loss"]) - float(m_ref["loss"])) < 2e-3
        for a, b in zip(jax.tree.leaves(s_ref["params"]),
                        jax.tree.leaves(s_got["params"])):
            d = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
            assert d < 1e-2, d
        print("pjit parity ok")
    """, devices=4, timeout=900)


def test_moe_expert_parallel_lowers(subproc):
    """MoE forward lowers+compiles with experts sharded over `model`."""
    subproc("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.launch.mesh import make_mesh
        from repro.models import registry
        cfg = get_reduced("qwen2_moe_a27b")
        m = registry.get_model(cfg)
        params = m.init(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh((2, 4), ("data", "model"))
        specs = registry.param_pspecs(cfg, params, {"data": 2, "model": 4})
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs)
        toks = jnp.zeros((4, 16), jnp.int32)
        toks = jax.device_put(toks, NamedSharding(mesh, P("data")))
        with mesh:
            lowered = jax.jit(lambda p, t: m.forward(p, t, cfg,
                                                     use_scan=False)
                              ).lower(params, toks)
            compiled = lowered.compile()
        txt = compiled.as_text()
        has_coll = any(k in txt for k in ("all-reduce", "all-to-all",
                                          "all-gather", "reduce-scatter",
                                          "collective-permute"))
        assert has_coll, "EP must introduce collectives"
        out = jax.jit(lambda p, t: m.forward(p, t, cfg, use_scan=False))(
            params, toks)
        assert not bool(jnp.any(jnp.isnan(out)))
        print("moe EP lowering ok")
    """, devices=8, timeout=900)

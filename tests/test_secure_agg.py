"""Secure aggregation + compressed gradient all-reduce."""
import functools
import random

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import secure_agg, paillier as gold
from repro.core.quantization import QuantSpec

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")

KEY = gold.keygen(128, random.Random(0))
SPEC = QuantSpec(delta=1e6, zmin=-4.0, zmax=4.0)


@given(st.integers(0, 1000))
def test_paillier_aggregate_sums(seed):
    rng = np.random.default_rng(seed)
    K = int(rng.integers(2, 6))
    blocks = [rng.normal(0, 0.5, (2, 3)) for _ in range(K)]
    got = secure_agg.paillier_aggregate(blocks, KEY, SPEC,
                                        random.Random(seed))
    want = np.sum(blocks, axis=0)
    assert np.max(np.abs(got - want)) < K * SPEC.span / SPEC.delta * 2


@given(st.integers(0, 10_000), st.sampled_from([8, 16]))
def test_paillier_aggregate_bit_exact_vs_plain_mirror(seed, bits):
    """The homomorphic sum IS the plaintext sum: for random blocks at a
    bits-wide quantization grid, the encrypted aggregate equals
    ``plain_aggregate`` (same quantize -> integer-sum -> dequantize
    arithmetic, no crypto) bit-for-bit — the property that lets the
    row-split consensus workloads run the encrypted path on keyed arms
    and the mirror on the plain arm with identical trajectories."""
    rng = np.random.default_rng(seed)
    K = int(rng.integers(2, 7))
    spec = QuantSpec(delta=float(2 ** bits - 1), zmin=-4.0, zmax=4.0)
    # include out-of-range values: clipping is part of the shared path
    blocks = [rng.normal(0, 2.5, (3, 4)) for _ in range(K)]
    got = secure_agg.paillier_aggregate(blocks, KEY, spec,
                                        random.Random(seed))
    want = secure_agg.plain_aggregate(blocks, spec)
    assert np.array_equal(got, want), (seed, bits)


@given(st.integers(0, 10_000))
def test_paillier_aggregate_bit_exact_scalar_arm(seed):
    """Blocks below BATCH_MIN take the scalar enc/dec loops — same
    bit-exactness contract as the batched path."""
    rng = np.random.default_rng(seed)
    blocks = [rng.normal(0, 1.0, (3,)) for _ in range(3)]   # n_el=3 < 8
    got = secure_agg.paillier_aggregate(blocks, KEY, SPEC,
                                        random.Random(seed))
    assert np.array_equal(got, secure_agg.plain_aggregate(blocks, SPEC))


_EF_T, _EF_D = 12, 16


@functools.lru_cache(maxsize=4)
def _ef_step_fn(bits: int):
    """One jitted error-feedback step on a 1-device mesh, cached per
    ``bits`` so the property examples share a single compilation."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    cfg = secure_agg.CompressionConfig(bits=bits, error_feedback=True)
    f = jax.shard_map(
        lambda g, r: tuple(
            x[None] for x in secure_agg.compress_tree_psum(
                g[0], "data", cfg, residuals=r[0])),
        mesh=mesh, in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None)))
    jf = jax.jit(f)

    def step(g: np.ndarray, r: np.ndarray):
        with mesh:
            red, r_new = jf(jnp.asarray(g), jnp.asarray(r))
        return np.asarray(red)[0], np.asarray(r_new)

    return step


@given(st.integers(0, 1000), st.sampled_from([8, 16]))
def test_compressed_psum_error_feedback_telescopes(seed, bits):
    """Error-feedback residuals telescope: over T steps the cumulative
    applied gradient differs from the cumulative true gradient by
    exactly the FINAL residual, so the compression bias stays bounded
    by one step's quantization error instead of accumulating ~T of
    them.  Runs the real compress_tree_psum path on a 1-device mesh
    (psum == identity there; the quantize/error-feedback arithmetic is
    what is under test)."""
    step = _ef_step_fn(bits)
    T, D = _EF_T, _EF_D
    gs = np.random.default_rng(seed).normal(0, 1, (T, D))

    r = np.zeros((1, D))
    applied = np.zeros(D)
    qm = float(2 ** (bits - 1) - 1)
    max_step_err = 0.0
    for t in range(T):
        g = gs[t][None]
        red, r_new = step(g, r)
        applied += red
        scale = float(np.max(np.abs(g + r)))
        max_step_err = max(max_step_err, scale / (2.0 * qm) * (1 + 1e-9))
        # the residual is exactly this step's quantization error
        assert float(np.max(np.abs(r_new))) <= max_step_err
        r = r_new
        # telescoping: sum(applied) - sum(true) == -current residual
        bias = applied - gs[: t + 1].sum(0)
        assert np.allclose(bias, -r[0], atol=1e-12), (seed, bits, t)
    # final bias bounded by ONE step's quantization error — not T of them
    assert float(np.max(np.abs(applied - gs.sum(0)))) <= max_step_err


def test_compressed_psum_exact_sum_property(subproc):
    subproc("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import secure_agg
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        g = np.random.default_rng(0).normal(0, 1, (4, 128)).astype(np.float32)
        for bits, tol in ((8, 2e-2), (16, 1e-4)):
            f = jax.shard_map(lambda x: secure_agg.compressed_psum(
                              x[0], "data", bits=bits)[None],
                          mesh=mesh, in_specs=P("data", None),
                          out_specs=P("data", None))
            with mesh:
                out = np.asarray(f(jnp.asarray(g)))
            rel = np.max(np.abs(out - g.sum(0)[None])) / np.max(np.abs(g.sum(0)))
            assert rel < tol, (bits, rel)
        print("compressed psum ok")
    """, devices=4)


def test_error_feedback_converges(subproc):
    """DP training with compressed gradients still overfits a batch."""
    subproc("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.core.secure_agg import CompressionConfig
        from repro.launch.mesh import make_mesh
        from repro.train import loop as loop_mod
        from repro.train.optimizer import OptConfig
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_reduced("yi_9b")
        mesh = make_mesh((4,), ("data",))
        comp = CompressionConfig(bits=8, enabled=True, error_feedback=True)
        step = loop_mod.make_dp_compressed_step(
            cfg, OptConfig(lr=5e-3, warmup_steps=1, total_steps=20),
            mesh, comp)
        state = loop_mod.init_dp_state(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                       jnp.int32)}
        batch = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                 for k, v in batch.items()}
        losses = []
        with mesh:
            for _ in range(8):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("compressed-DP losses:", [round(x, 3) for x in losses])
    """, devices=4, timeout=900)

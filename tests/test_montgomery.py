"""Montgomery REDC ladders vs the Barrett oracle and Python-int gold.

Covers the PR-8 kernel work end to end:

* property tests (optional-hypothesis shim) racing ``ops.modexp`` /
  ``ops.modexp_fixed`` under both ``reduce_impl`` arms against Python-int
  ``pow`` — key sizes {256, 512, 1024} bits, top-limb edge moduli
  (all-ones and minimal-top-limb), exponent 0, and batch shapes
  B in {0, 1, non-block-multiple};
* the ops-layer jit-cache regression: one cache entry per (op, modulus,
  canonical block) across arbitrary incoming batch sizes;
* wrapper-boundary method validation (unknown method, win4 width);
* roofline pricing pinned against the OpCounter of a REAL protocol run
  (enc/dec priced by the fixed-window schedule, not the legacy
  1.5/bit binary estimate);
* device-mesh plumbing (``kernel_mesh`` / ``device_kind`` suffix);
* protocol conformance: bit-identical histories and ciphertext streams
  with ``REPRO_REDUCE_IMPL`` flipped between barrett and montgomery.
"""
import random

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, strategies as st

from repro.core import bigint as bi
from repro.kernels import montgomery as mg
from repro.kernels import ops

settings.register_profile("ci", max_examples=4, deadline=None)
settings.load_profile("ci")

BLOCK = 128


def _edge_moduli(bits: int) -> list[int]:
    """Top-limb edge cases: all-ones (0xFF top limb) and minimal top limb
    (0x80... | 1), plus a seeded random odd modulus of exactly ``bits``."""
    rng = random.Random(bits)
    rand_odd = (rng.getrandbits(bits) | (1 << (bits - 1))) | 1
    return [(1 << bits) - 1, (1 << (bits - 1)) | 1, rand_odd]


# pack once per modulus: the jit caches are keyed on m_int, so every
# hypothesis example reuses the same traces (values change, shapes don't)
PACKS = {bits: [ops.pack_modulus(m) for m in _edge_moduli(bits)]
         for bits in (256, 512, 1024)}


def _limbs(vals, L16):
    return jnp.asarray(bi.from_ints(list(vals), L16))


@given(st.integers(0, 2**31 - 1))
def test_modexp_mont_vs_barrett_vs_gold_across_key_sizes(seed):
    """Both reduce impls, per-element exponents incl. 0, vs pow()."""
    for bits, packs in PACKS.items():
        for pack in packs:
            rng = random.Random(seed ^ bits ^ pack.m_int)
            bases = [rng.randrange(pack.m_int) for _ in range(4)]
            exps = [0, 1] + [rng.randrange(1 << 32) for _ in range(2)]
            want = [pow(b, e, pack.m_int) for b, e in zip(bases, exps)]
            b16 = _limbs(bases, pack.L16)
            e16 = _limbs(exps, 2)
            for impl in ("barrett", "montgomery"):
                got = bi.to_ints(ops.modexp(b16, e16, pack, backend="ref",
                                            reduce_impl=impl))
                assert got == want, (bits, impl, pack.m_int)


@given(st.integers(0, 2**31 - 1))
def test_modexp_fixed_vs_both_impls_and_gold(seed):
    """Host-known-exponent ladder: E in {0, 1, random}, both impls."""
    pack = PACKS[256][2]
    rng = random.Random(seed)
    bases = [rng.randrange(pack.m_int) for _ in range(4)]
    b16 = _limbs(bases, pack.L16)
    for e in (0, 1, rng.randrange(1 << 60)):
        want = [pow(b, e, pack.m_int) for b in bases]
        for impl in ("barrett", "montgomery"):
            got = bi.to_ints(ops.modexp_fixed(b16, e, pack, backend="ref",
                                              reduce_impl=impl))
            assert got == want, (e, impl)


@pytest.mark.parametrize("B", [0, 1, 5])
def test_batch_shapes_through_pallas(B):
    """B in {0, 1, non-block-multiple} through the padded pallas path."""
    pack = PACKS[256][2]
    rng = random.Random(B)
    bases = [rng.randrange(pack.m_int) for _ in range(B)]
    exps = [rng.randrange(1 << 32) for _ in range(B)]
    b16 = _limbs(bases, pack.L16)
    e16 = _limbs(exps, 2).reshape(B, 2)
    for impl in ("barrett", "montgomery"):
        got = bi.to_ints(ops.modexp(b16, e16, pack, backend="pallas",
                                    reduce_impl=impl))
        assert got == [pow(b, e, pack.m_int)
                       for b, e in zip(bases, exps)], (B, impl)
    got = bi.to_ints(ops.modexp_fixed(b16, 37, pack, backend="pallas",
                                      reduce_impl="montgomery"))
    assert got == [pow(b, 37, pack.m_int) for b in bases], B


def test_even_modulus_falls_back_to_barrett():
    m = (1 << 256) - 2          # even: REDC needs gcd(m, 256) = 1
    pack = ops.pack_modulus(m)
    assert pack.mp8 is None
    bases = [12345, m - 1]
    got = bi.to_ints(ops.modexp(_limbs(bases, pack.L16), _limbs([7, 9], 1),
                                pack, backend="ref",
                                reduce_impl="montgomery"))
    assert got == [pow(12345, 7, m), pow(m - 1, 9, m)]


def test_redc_round_trip_identities():
    """to_mont/from_mont round-trips and montmul agrees with (a*b) mod m."""
    for pack in PACKS[512]:
        m, L8 = pack.m_int, pack.L8
        rng = random.Random(m & 0xFFFF)
        vals = [rng.randrange(m) for _ in range(4)]
        x8 = jnp.asarray(np.stack([np.asarray(
            [(v >> (8 * i)) & 0xFF for i in range(L8)], np.int32)
            for v in vals]))
        mm = jnp.asarray(pack.m8)
        r1, r2 = jnp.asarray(pack.r1_8), jnp.asarray(pack.r2_8)
        xm = mg.to_mont2d(x8, mm, pack.mp8, r2)
        back = mg.from_mont2d(xm, mm, pack.mp8)
        got = [sum(int(v) << (8 * i) for i, v in enumerate(row))
               for row in np.asarray(back)]
        assert got == vals, m
        prod = mg.from_mont2d(
            mg.montmul2d(xm, xm, mm, pack.mp8), mm, pack.mp8)
        got2 = [sum(int(v) << (8 * i) for i, v in enumerate(row))
                for row in np.asarray(prod)]
        assert got2 == [v * v % m for v in vals], m


# ---------------------------------------------------------------------------
# ops-layer cache + validation regressions
# ---------------------------------------------------------------------------

def test_mulmod_cache_one_entry_across_batch_sizes():
    """Varying incoming batch sizes must NOT grow the jit-closure cache:
    batches pad up to the canonical block and the key carries block_b,
    never the raw batch (the pre-PR leak grew one entry per size)."""
    m = (1 << 192) - 237        # fresh modulus: no prior cache entries
    pack = ops.pack_modulus(m)
    before = set(ops._JIT_CACHE)
    for B in (3, 5, 17, 64, 130):
        a = _limbs([i + 1 for i in range(B)], pack.L16)
        got = bi.to_ints(ops.mulmod(a, a, pack, backend="pallas"))
        assert got == [(i + 1) * (i + 1) % m for i in range(B)]
    new = [k for k in ops._JIT_CACHE if k not in before]
    assert new == [(m, "pallas", "mulmod", BLOCK)]


def test_modexp_rejects_unknown_method_and_width():
    pack = PACKS[256][2]
    b16 = _limbs([5], pack.L16)
    e16 = _limbs([3], 1)
    with pytest.raises(ValueError, match="unknown modexp method"):
        ops.modexp(b16, e16, pack, backend="ref", method="win8")
    with pytest.raises(ValueError, match="unknown reduce_impl"):
        ops.modexp(b16, e16, pack, backend="ref", reduce_impl="redc2")
    # the wrapper-boundary win4 width check (16-bit limbs always pass;
    # the guard protects future limb-width changes with a clear error)
    with pytest.raises(ValueError, match="multiple of 4"):
        ops._validate_method("win4", 18)
    with pytest.raises(ValueError, match="non-negative"):
        ops.modexp_fixed(b16, -3, pack, backend="ref")
    with pytest.raises(ValueError, match="negative"):
        mg.exp_windows(-1)


def test_exp_windows_schedule():
    assert mg.exp_windows(0) == ()
    assert mg.exp_windows(1) == (1,)
    assert mg.exp_windows(0xAB3) == (0xA, 0xB, 0x3)
    assert mg.exp_windows(0x1F) == (0x1, 0xF)   # trimmed to true length
    # stacked schedules: the shorter one is front-padded with zero windows
    assert mg.exp_window_rows(0xAB3, 0x1F).tolist() == [[0xA, 0xB, 0x3],
                                                         [0x0, 0x1, 0xF]]
    assert mg.exp_window_rows(0, 0x5).tolist() == [[0x0], [0x5]]
    assert mg.exp_window_rows(0, 0).shape == (2, 0)
    assert mg.exp_window_rows(0x1F).tolist() == [[0x1, 0xF]]


# ---------------------------------------------------------------------------
# stacked CRT halves: one fixed ladder over both moduli
# ---------------------------------------------------------------------------

def _odd(bits: int, rng: random.Random) -> int:
    return rng.getrandbits(bits) | (1 << (bits - 1)) | 1


def _exp(bits: int, rng: random.Random) -> int:
    return rng.getrandbits(bits) | (1 << (bits - 1)) if bits else 0


@pytest.mark.parametrize("p_bits,q_bits,ep_bits,eq_bits,B", [
    (256, 256, 250, 256, 1),      # window counts 63 and 64
    (256, 256, 250, 256, 3),
    (256, 256, 250, 256, 128),
    (256, 256, 60, 256, 3),       # 15 windows against 64
    (256, 256, 256, 0, 3),        # q's exponent 0: empty schedule
    (256, 256, 0, 0, 3),          # both empty: every row is 1
    (264, 256, 200, 120, 3),      # p wider: L8 33 and 32
    (200, 256, 120, 200, 3),      # q wider: L8 25 and 32
])
def test_modexp_fixed_pair_stacked_vs_gold(p_bits, q_bits, ep_bits, eq_bits,
                                           B):
    """Both groups of the stacked ladder bit-exact against pow(), next to
    the two-call Barrett arm, which must agree with them."""
    rng = random.Random(p_bits * 7 + q_bits + ep_bits + eq_bits + B)
    pp = ops.pack_modulus(_odd(p_bits, rng))
    pq = ops.pack_modulus(_odd(q_bits, rng))
    ep, eq = _exp(ep_bits, rng), _exp(eq_bits, rng)
    xs = [rng.randrange(pp.m_int) for _ in range(B)]
    ys = [rng.randrange(pq.m_int) for _ in range(B)]
    want_p = [pow(x, ep, pp.m_int) for x in xs]
    want_q = [pow(y, eq, pq.m_int) for y in ys]
    assert ops.fixed_pair_stacks(pp, pq, "ref", "montgomery")
    assert not ops.fixed_pair_stacks(pp, pq, "ref", "barrett")
    for impl in ("montgomery", "barrett"):
        xp, xq = ops.modexp_fixed_pair(_limbs(xs, pp.L16), ep, pp,
                                       _limbs(ys, pq.L16), eq, pq,
                                       backend="ref", reduce_impl=impl)
        assert xp.shape == (B, pp.L16) and xq.shape == (B, pq.L16)
        assert bi.to_ints(xp) == want_p, impl
        assert bi.to_ints(xq) == want_q, impl


def test_stacked_kernel_groups_match_single_group_ladders():
    """The G = 2 kernel's rows equal the G = 1 kernel (and the Barrett
    ladder) run on each group alone: stacking changes the layout, not
    the arithmetic."""
    rng = random.Random(5)
    packs = [ops.pack_modulus(_odd(256, rng)) for _ in range(2)]
    es = [_exp(256, rng), _exp(180, rng)]
    B = 4
    b8 = [jnp.asarray(np.stack([ops._to8(rng.randrange(p.m_int), p.L8)
                                for _ in range(B)])) for p in packs]
    singles = []
    for p, e, b in zip(packs, es, b8):
        win = jnp.asarray(mg.exp_window_rows(e))
        mont = mg.modexp2d_mont_fixed(b, win, jnp.asarray(p.m8), p.mp8,
                                      jnp.asarray(p.r1_8),
                                      jnp.asarray(p.r2_8))
        barr = mg.modexp2d_fixed_barrett(b, win, jnp.asarray(p.m8),
                                         jnp.asarray(p.mu8))
        assert np.array_equal(mont, barr)
        singles.append(np.asarray(mont))

    def rows(attr):
        return jnp.asarray(np.concatenate(
            [np.repeat(np.asarray(getattr(p, attr)).reshape(1, -1), B, 0)
             for p in packs]))

    mp = jnp.asarray(np.repeat([p.mp8 for p in packs], B).astype(np.int32))
    stacked = mg.modexp2d_mont_fixed(
        jnp.concatenate(b8), jnp.asarray(mg.exp_window_rows(*es)),
        rows("m8"), mp, rows("r1_8"), rows("r2_8"), groups=(B, B))
    assert np.array_equal(np.asarray(stacked), np.concatenate(singles))


# ---------------------------------------------------------------------------
# roofline pricing pinned to the active ladder schedule
# ---------------------------------------------------------------------------

def test_ladder_mulmods_pricing():
    from repro.analysis import roofline as rl
    assert rl.ladder_mulmods("binary", 20) == 40.0
    assert rl.ladder_mulmods("win4", 20) == 40.0          # 1.25*20 + 15
    assert rl.ladder_mulmods("win4", 20, "montgomery") == 42.0
    assert rl.ladder_mulmods("fixed", 0) == 0.0           # e == 0: no work
    assert rl.ladder_mulmods("fixed", 0, "montgomery") == 0.0
    with pytest.raises(ValueError, match="unknown modexp method"):
        rl.ladder_mulmods("win8", 20)


def test_roofline_prices_real_run_by_active_method():
    """limb_ops on a REAL gold-batched run's OpCounter: enc/dec priced at
    the fixed-window key-width schedule and modexp at the active method —
    not the legacy all-binary 1.5/bit estimate."""
    from repro.analysis import roofline as rl
    from repro.core import protocol
    from repro.core.quantization import QuantSpec
    from repro.data.synthetic import make_lasso
    from repro.runtime import LinkModel, topology as topo_mod
    from repro.runtime.runner import run_on_runtime

    inst = make_lasso(16, 32, sparsity=0.1, noise=0.01, seed=1)
    cfg = protocol.ProtocolConfig(
        K=4, lam=0.05, iters=2, spec=QuantSpec(1e6, -8.0, 8.0), seed=0,
        key_bits=128, cipher="gold", gold_batch=True)
    r = run_on_runtime(inst.A, inst.y, cfg,
                       topology=topo_mod.make("star", 4),
                       link=LinkModel(bytes_per_s=125e6, latency_s=1e-3))
    counts = {}
    for per_phase in r.stats["ops"].values():
        for op, n in per_phase.items():
            counts[op] = counts.get(op, 0) + int(n)
    assert counts.get("enc") and counts.get("dec") and counts.get("modexp")
    kb = r.stats["runtime"]["roofline"]["key_bits"]
    lo = rl.limb_ops(r.stats["ops"], kb, method="win4",
                     reduce_impl="montgomery")
    L = lo["limbs"]
    key_ladder = 1.25 * kb + 15 + 2      # fixed schedule + domain ops
    assert lo["by_op"]["enc"] == counts["enc"] * key_ladder * L * L
    assert lo["by_op"]["dec"] == counts["dec"] * key_ladder * L * L
    assert lo["by_op"]["modexp"] == \
        counts["modexp"] * (1.25 * rl.GAMMA2_EXP_BITS + 15 + 2) * L * L
    assert lo["by_op"]["mulmod"] == counts["mulmod"] * L * L
    # the run's own recorded roofline used the same active-schedule prices
    rec = r.stats["runtime"]["roofline"]
    assert rec["method"] == "win4" and rec["reduce_impl"] == "montgomery"
    assert rec["limb_muls"] == lo["limb_muls"]
    # binary pricing differs — the old flat estimate can't sneak back
    lo_bin = rl.limb_ops(r.stats["ops"], kb, method="binary",
                         reduce_impl="barrett")
    assert lo_bin["limb_muls"] != lo["limb_muls"]


# ---------------------------------------------------------------------------
# device mesh plumbing
# ---------------------------------------------------------------------------

def test_kernel_mesh_and_device_kind_suffix(monkeypatch):
    from repro.launch import mesh as lm
    from repro.runtime import dispatch
    if jax.local_device_count() == 1:
        assert lm.kernel_mesh() is None
        assert "x" + "1" not in dispatch.device_kind()
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    assert dispatch.device_kind() == f"{jax.default_backend()}x4"


def test_shard_batch_single_device_passthrough():
    from repro.core import paillier_batch as pb
    if jax.local_device_count() != 1:
        pytest.skip("single-device passthrough check")
    x = jnp.ones((4, 3), jnp.int32)
    y = pb._shard_batch(x)
    assert y is x
    a, b = pb._shard_batch(x, jnp.zeros((2, 3), jnp.int32))
    assert a is x and b.shape == (2, 3)


# ---------------------------------------------------------------------------
# protocol conformance across REPRO_REDUCE_IMPL
# ---------------------------------------------------------------------------

def test_protocol_history_bit_identical_across_reduce_impls(monkeypatch):
    """The whole encrypted protocol replays bit-identically with the
    reduction flipped: montgomery is a pure drop-in for the barrett
    oracle (histories AND rng consumption match the scalar gold arm)."""
    from repro.core import protocol
    from repro.core.quantization import QuantSpec
    from repro.data.synthetic import make_lasso

    inst = make_lasso(16, 32, sparsity=0.1, noise=0.01, seed=1)

    def one(impl, batched=True):
        monkeypatch.setenv("REPRO_REDUCE_IMPL", impl)
        cfg = protocol.ProtocolConfig(
            K=4, lam=0.05, iters=2, spec=QuantSpec(1e6, -8.0, 8.0),
            seed=0, key_bits=128, cipher="gold", gold_batch=batched)
        return protocol.run_protocol(inst.A, inst.y, cfg)

    mont = one("montgomery")
    barr = one("barrett")
    scalar = one("montgomery", batched=False)
    assert np.array_equal(mont.history, barr.history)
    assert np.array_equal(mont.history, scalar.history)

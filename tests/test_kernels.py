"""Limb kernel validation: the ``kernels/ops.py`` wrappers vs Python ints,
swept over modulus sizes (incl. odd byte lengths), batch shapes and
reductions.
"""
import random

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bigint as bi
from repro.kernels import common as cm
from repro.kernels import ops
from repro.kernels import ref as ref_impl

RNG = random.Random(2024)


def _mk_modulus(bits):
    return RNG.getrandbits(bits) | (1 << (bits - 1)) | 1


SWEEP_BITS = [24, 48, 56, 64, 96, 120, 160]


@pytest.mark.parametrize("bits", SWEEP_BITS)
def test_mulmod_sweep(bits):
    m = _mk_modulus(bits)
    pack = ops.pack_modulus(m)
    B = 7
    a = [RNG.randrange(m) for _ in range(B)]
    b = [RNG.randrange(m) for _ in range(B)]
    A = jnp.asarray(bi.from_ints(a, pack.L16))
    Bv = jnp.asarray(bi.from_ints(b, pack.L16))
    got = bi.to_ints(ops.mulmod(A, Bv, pack))
    assert got == [(x * y) % m for x, y in zip(a, b)]


@pytest.mark.parametrize("bits", [32, 64, 96])
def test_modexp_sweep(bits):
    m = _mk_modulus(bits)
    pack = ops.pack_modulus(m)
    B = 5
    a = [RNG.randrange(m) for _ in range(B)]
    e = [RNG.randrange(1 << 24) for _ in range(B)]
    A = jnp.asarray(bi.from_ints(a, pack.L16))
    E = jnp.asarray(bi.from_ints(e, 2))
    got = bi.to_ints(ops.modexp(A, E, pack))
    assert got == [pow(x, ee, m) for x, ee in zip(a, e)]


@pytest.mark.parametrize("bits", SWEEP_BITS)
def test_modexp_fixed_sweep(bits):
    """The fixed-exponent ladder, both reductions, against ``pow()``."""
    m = _mk_modulus(bits)
    pack = ops.pack_modulus(m)
    a = [RNG.randrange(m) for _ in range(5)]
    e = RNG.getrandbits(bits) | 1
    A = jnp.asarray(bi.from_ints(a, pack.L16))
    for impl in ("barrett", "montgomery"):
        got = bi.to_ints(ops.modexp_fixed(A, e, pack, reduce_impl=impl))
        assert got == [pow(x, e, m) for x in a], impl


@pytest.mark.parametrize("bits", SWEEP_BITS)
def test_modexp_fixed_pair_sweep(bits):
    """Both halves as one stacked ladder, q a byte narrower than p."""
    p, q = _mk_modulus(bits), _mk_modulus(bits - 8)
    pp, pq = ops.pack_modulus(p), ops.pack_modulus(q)
    assert ops.fixed_pair_stacks(pp, pq, "montgomery")
    a = [RNG.randrange(p) for _ in range(5)]
    b = [RNG.randrange(q) for _ in range(5)]
    ep, eq = RNG.getrandbits(bits) | 1, RNG.getrandbits(bits - 8) | 1
    xp, xq = ops.modexp_fixed_pair(
        jnp.asarray(bi.from_ints(a, pp.L16)), ep, pp,
        jnp.asarray(bi.from_ints(b, pq.L16)), eq, pq,
        reduce_impl="montgomery")
    assert bi.to_ints(xp) == [pow(x, ep, p) for x in a]
    assert bi.to_ints(xq) == [pow(x, eq, q) for x in b]


def test_radix_conversions_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).integers(
        0, 1 << 16, (4, 6), dtype=np.int64), dtype=jnp.int32)
    x8 = cm.limbs16_to8(x)
    back = cm.limbs8_to16(x8)
    assert (np.asarray(back) == np.asarray(x)).all()


def test_fft_reference_matches_exact():
    """The paper's FFT multiply (Algorithm 2) == exact convolution."""
    rng = np.random.default_rng(3)
    a8 = jnp.asarray(rng.integers(0, 256, (5, 32), np.int64), jnp.int32)
    b8 = jnp.asarray(rng.integers(0, 256, (5, 32), np.int64), jnp.int32)
    exact = cm.mul2d(a8, b8, 64)
    fft = ref_impl.fft_mul_ref(a8, b8)
    assert (np.asarray(exact) == np.asarray(fft)).all()


@pytest.mark.parametrize("L", [64, 256])
def test_carry_normalization_extremes(L):
    """Max-coefficient inputs: the int32 accumulation headroom claim."""
    a8 = jnp.full((2, L), 255, jnp.int32)
    out = cm.mul2d(a8, a8, 2 * L)
    a_int = (256 ** L - 1) // 255 * 255   # value with all limbs 255
    want = a_int * a_int
    got = 0
    arr = np.asarray(out)
    for i in range(2 * L - 1, -1, -1):
        got = (got << 8) | int(arr[0, i])
    assert got == want


# ---------------------------------------------------------------------------
# constant-depth carries, borrows and comparisons vs Python ints
# ---------------------------------------------------------------------------

CARRY_LIMBS = [1, 2, 128, 256, 257, 514]


def _int_of(row) -> int:
    return sum(int(v) << (8 * i) for i, v in enumerate(row))


def _ints(arr) -> list[int]:
    return [_int_of(row) for row in np.asarray(arr)]


def _rows(vals, L):
    return jnp.asarray([[(v >> (8 * i)) & 0xFF for i in range(L)]
                        for v in vals], jnp.int32)


@pytest.mark.parametrize("L", CARRY_LIMBS)
def test_carry2d_exact(L):
    """carry2d == the coefficients' value mod 256^L, on the inputs that
    stress a lookahead: a carry run across the whole vector, one broken
    at the top limb, all limbs 255, and the largest int32 coefficients."""
    rng = np.random.default_rng(L)
    top = [255] * (L - 1)
    coeffs = [
        rng.integers(0, 2 ** 31, L),            # anything int32 holds
        rng.integers(0, 2 ** 25, L),            # a montmul's high half
        [255] * L,                              # all limbs 255, no carry
        [256] + top,                            # a run across the whole
        ([256] + top[:-1] + [254]) if L > 1 else [254],   # broken at top
        [2 ** 31 - 1] * L,
        [0] * L,
    ]
    acc = jnp.asarray(np.asarray(coeffs, np.int64), jnp.int32)
    want = [sum(int(c) << (8 * i) for i, c in enumerate(row)) % 256 ** L
            for row in coeffs]
    assert _ints(cm.carry2d(acc)) == want


@pytest.mark.parametrize("L", CARRY_LIMBS)
def test_sub2d_exact(L):
    """sub2d == (a - b) mod 256^L, with borrow runs across the whole
    vector (0 - 1) and broken at the top limb (256^(L-1) - 1)."""
    R = 256 ** L
    rng = random.Random(L)
    pairs = [(rng.randrange(R), rng.randrange(R)) for _ in range(3)]
    pairs += [(0, 1), (R - 1, R - 1), (R - 1, 0), (0, R - 1),
              (R // 256, 1), (R // 256 * 255, R // 256 * 255 - 1)]
    a, b = zip(*pairs)
    got = _ints(cm.sub2d(_rows(a, L), _rows(b, L)))
    assert got == [(x - y) % R for x, y in pairs]


@pytest.mark.parametrize("L", CARRY_LIMBS)
def test_cond_sub2d_exact(L):
    """cond_sub2d at r in {0, m-1, m, 2m-1} (r one limb wider than m, as
    in REDC), for a random m and the all-255 one."""
    rng = random.Random(L + 7)
    for m in (rng.getrandbits(8 * L) | 1 | (1 << (8 * L - 1)),
              256 ** L - 1):
        rs = [0, m - 1, m, 2 * m - 1, m + 1]
        got = _ints(cm.cond_sub2d(_rows(rs, L + 1), _rows([m], L)))
        assert got == [r - m if r >= m else r for r in rs], m


def test_kernel_vs_gold_paillier_roundtrip():
    """End-to-end: encrypt with limb kernels, decrypt with Python ints."""
    from repro.core import paillier as gold
    from repro.core import paillier_vec as pv
    key = gold.keygen(96, random.Random(5))
    vk = pv.make_vec_key(key)
    ms = [123456, 42, 10**9]
    pool = gold.make_r_pool(key, len(ms), random.Random(6))
    rn = jnp.asarray(bi.from_ints(pool, vk.pack_n2.L16))
    c = pv.encrypt_batch(vk, jnp.asarray(ms, jnp.int64), rn)
    for m, ci in zip(ms, bi.to_ints(c)):
        assert gold.decrypt(key, ci) == m

"""Paillier: gold path, CRT decomposition, batched limb path equivalence."""
import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import bigint as bi
from repro.core import paillier as gold
from repro.core import paillier_batch as pb
from repro.core import paillier_vec as pv
from repro.core import protocol
from repro.core.quantization import QuantSpec
from repro.data.synthetic import make_lasso
from repro.kernels import ops

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")

KEY = gold.keygen(128, random.Random(1234))
VK = pv.make_vec_key(KEY)


def test_keygen_structure():
    assert KEY.n == KEY.p * KEY.q
    assert KEY.n2 == KEY.n ** 2
    assert (KEY.p2_inv_q2 * KEY.p2) % KEY.q2 == 1


@given(st.integers(0, 2**62 - 1), st.integers(0, 2**62 - 1))
def test_homomorphic_add(m1, m2):
    rng = random.Random(m1 ^ m2)
    c1 = gold.encrypt(KEY, m1, gold.rand_r(KEY, rng))
    c2 = gold.encrypt_crt(KEY, m2, gold.rand_r(KEY, rng))
    assert gold.decrypt(KEY, gold.c_add(KEY, c1, c2)) == (m1 + m2) % KEY.n
    assert gold.decrypt_crt(KEY, c1) == m1


@given(st.integers(0, 2**40 - 1), st.integers(0, 2**20 - 1))
def test_homomorphic_mul_const(m, k):
    rng = random.Random(m ^ k)
    c = gold.encrypt(KEY, m, gold.rand_r(KEY, rng))
    assert gold.decrypt(KEY, gold.c_mul_const(KEY, c, k)) == (m * k) % KEY.n
    assert gold.decrypt(KEY, gold.c_mul_const_crt(KEY, c, k)) \
        == (m * k) % KEY.n


def test_crt_modexp_equals_direct():
    rng = random.Random(0)
    for _ in range(10):
        base = rng.randrange(1, KEY.n2)
        e = rng.randrange(1, KEY.lam)
        assert gold.modexp_crt(KEY, base, e) == pow(base, e, KEY.n2)


def test_vec_encrypt_decrypt_matches_gold():
    rng = random.Random(7)
    ms = [rng.randrange(2**50) for _ in range(8)]
    pool = gold.make_r_pool(KEY, len(ms), rng)
    rn = jnp.asarray(bi.from_ints(pool, VK.pack_n2.L16))
    c = pv.encrypt_batch(VK, jnp.asarray(ms, jnp.int64), rn)
    c_ints = bi.to_ints(c)
    for m, ci, rni in zip(ms, c_ints, pool):
        assert ci == ((1 + m * KEY.n) * rni) % KEY.n2
        assert gold.decrypt(KEY, ci) == m
    dec = list(np.asarray(pv.decrypt_batch(VK, c)))
    assert dec == ms


def test_vec_homomorphic_ops():
    rng = random.Random(8)
    ms = [rng.randrange(10**6) for _ in range(4)]
    pool = gold.make_r_pool(KEY, len(ms), rng)
    rn = jnp.asarray(bi.from_ints(pool, VK.pack_n2.L16))
    c = pv.encrypt_batch(VK, jnp.asarray(ms, jnp.int64), rn)
    two = pv.c_add_batch(VK, c, c)
    for m, ci in zip(ms, bi.to_ints(two)):
        assert gold.decrypt(KEY, ci) == 2 * m
    k = jnp.asarray([5, 7, 11, 13], jnp.int64)
    mulc = pv.c_mul_const_batch(VK, c, k)
    for m, ki, ci in zip(ms, [5, 7, 11, 13], bi.to_ints(mulc)):
        assert gold.decrypt(KEY, ci) == (m * ki) % KEY.n


def test_vec_matvec():
    rng = random.Random(9)
    N, M = 5, 3
    ms = [rng.randrange(1000) for _ in range(N)]
    pool = gold.make_r_pool(KEY, N, rng)
    rn = jnp.asarray(bi.from_ints(pool, VK.pack_n2.L16))
    cvec = pv.encrypt_batch(VK, jnp.asarray(ms, jnp.int64), rn)
    Km = np.random.default_rng(0).integers(0, 99, (M, N))
    out = bi.to_ints(pv.c_matvec(VK, jnp.asarray(Km, jnp.int64), cvec))
    for i in range(M):
        assert gold.decrypt(KEY, out[i]) \
            == int(sum(Km[i, j] * ms[j] for j in range(N))) % KEY.n


def test_semantic_randomization():
    """Same plaintext, fresh r -> different ciphertexts (IND-CPA shape)."""
    rng = random.Random(10)
    c1 = gold.encrypt(KEY, 42, gold.rand_r(KEY, rng))
    c2 = gold.encrypt(KEY, 42, gold.rand_r(KEY, rng))
    assert c1 != c2
    assert gold.decrypt(KEY, c1) == gold.decrypt(KEY, c2) == 42


def test_plaintext_range_check():
    with pytest.raises(ValueError):
        gold.encrypt(KEY, KEY.n, 3)


# ---------------------------------------------------------------------------
# fixed CRT ladders (enc r^n, dec c^lam): both halves stacked in one ladder
# ---------------------------------------------------------------------------

def _fixed_crt_launches(fn):
    """``fn()`` and the fixed CRT launches it made, by form."""
    before = dict(ops.FIXED_CRT)
    out = fn()
    return out, {k: n - before[k] for k, n in ops.FIXED_CRT.items()}


def test_stacked_enc_dec_match_gold_crt_for_the_same_r(monkeypatch):
    bk = pb.make_batch_key(KEY)
    ms = [random.Random(11 + i).randrange(KEY.n) for i in range(8)]
    r1, r2 = random.Random(12), random.Random(12)
    ct, made = _fixed_crt_launches(lambda: pb.enc_ct(bk, ms, r1))
    assert made == {"stacked": 1, "split": 0}
    want = [gold.encrypt_crt(KEY, m, gold.rand_r(KEY, r2)) for m in ms]
    assert ct.to_ints() == want
    for cs in (ct, want):           # limb-in and int-in decryption
        pts, made = _fixed_crt_launches(lambda: pb.dec_vec(bk, cs))
        assert made == {"stacked": 1, "split": 0}
        assert pts == [gold.decrypt_crt(KEY, c) for c in want] == ms
    # the vec cipher's decryption takes the same stacked pair
    pts, made = _fixed_crt_launches(
        lambda: bi.to_ints(pv.decrypt_batch_limbs(VK, ct.limbs)))
    assert made == {"stacked": 1, "split": 0}
    assert pts == ms
    # the Barrett oracle keeps two ladders a launch
    monkeypatch.setenv("REPRO_REDUCE_IMPL", "barrett")
    pts, made = _fixed_crt_launches(lambda: pb.dec_vec(bk, ct))
    assert made == {"stacked": 0, "split": 1}
    assert pts == ms


def test_fixed_crt_programs_keep_the_name_fixed_body():
    """The device trace finds the fixed ladders by their program's name
    (``fixed_body``), so both jitted closures must keep it."""
    bk = pb.make_batch_key(KEY)
    rs = pb.rand_r_vec(KEY, 8, random.Random(13))
    ct = pb.enc_ct(bk, [1] * 8, random.Random(14))
    pb.rn_pool_limbs(bk, rs)
    pb.dec_vec(bk, ct)
    fns = {name[0]: fn for (vid, name), fn in pv._JIT_CACHE.items()
           if vid == id(bk.vk) and isinstance(name, tuple)
           and name[0] in ("crt_modexp_fixed", "crt_modexp_limbs_fixed")}
    assert set(fns) == {"crt_modexp_fixed", "crt_modexp_limbs_fixed"}

    def rows(L):
        return jax.ShapeDtypeStruct((8, L), jnp.int32)

    vk = bk.vk
    enc = fns["crt_modexp_fixed"].lower(rows(vk.pack_p2.L16),
                                        rows(vk.pack_q2.L16))
    dec = fns["crt_modexp_limbs_fixed"].lower(rows(vk.pack_n2.L16))
    for lowered in (enc, dec):
        assert "@jit_fixed_body" in lowered.as_text()


def _cfg(seed):
    return protocol.ProtocolConfig(
        K=3, rho=1.0, lam=1.0, iters=2,
        spec=QuantSpec(delta=1e15, zmin=-16.0, zmax=16.0), workload="lasso",
        cipher="gold", key_bits=128, gold_batch=True, crt=True, seed=seed)


def test_protocol_run_records_every_fixed_crt_launch_stacked():
    from repro.runtime.runner import run_on_runtime
    inst = make_lasso(3, 9, seed=1)
    fixed = run_on_runtime(inst.A, inst.y, _cfg(11)).stats["runtime"][
        "fixed_crt"]
    assert fixed["split"] == 0 and fixed["stacked"] >= 2 * 2  # enc+dec/round


def test_engine_run_records_no_fixed_crt_launch():
    """The serving engine runs the multi-modulus rows ladders instead."""
    from repro.serve.protocol_engine import ProtocolEngine
    eng = ProtocolEngine(seed=11, admission="concurrent")
    for i in range(2):
        inst = make_lasso(3, 9, seed=10 + i)
        eng.admit(inst.A, inst.y, _cfg(11 + i), tid=f"t{i}")
    results = eng.run()
    assert len(results) == 2
    for res in results.values():
        assert res.stats["runtime"]["fixed_crt"] == {"stacked": 0,
                                                     "split": 0}

"""Batched Paillier on the limb kernels — the "GPU-accelerated EP" in JAX.

Maps the paper's §IV onto the batched big-integer kernels: every vector
encryption/decryption/homomorphic-op becomes one (or a few) kernel launches
over the element batch, with the CRT decomposition (Z_{n^2} -> Z_{p^2} x
Z_{q^2}) halving operand width for the ModExp-heavy decryption path.

All functions return limb arrays (radix-2^16, ``core.bigint`` layout) and are
bit-exact vs. the Python-int gold path (``core.paillier``) — enforced in
tests/test_paillier.py.
"""
from __future__ import annotations

import dataclasses
import random

import numpy as np
import jax
import jax.numpy as jnp

from . import bigint as bi
from . import paillier as gold
from ..kernels import ops

jax.config.update("jax_enable_x64", True)

# per-key jitted closures: VecKey holds numpy constants, so we cache one
# jax.jit per (key-object, op); jax dedups shapes internally.
_JIT_CACHE: dict = {}


def _cached_jit(vk, name, builder):
    k = (id(vk), name)
    fn = _JIT_CACHE.get(k)
    if fn is None:
        fn = _JIT_CACHE[k] = jax.jit(builder)
    return fn


def int64_to_limbs(x: jax.Array, n_limbs: int) -> jax.Array:
    """Nonnegative int64 array (B,) -> (B, n_limbs) 16-bit limbs, in-graph."""
    x = jnp.asarray(x, jnp.int64)
    shifts = jnp.arange(n_limbs, dtype=jnp.int64) * 16
    return ((x[..., None] >> shifts) & 0xFFFF).astype(jnp.int32)


def limbs_to_int64(limbs: jax.Array) -> jax.Array:
    """(B, L) limbs -> int64 (values must fit 63 bits; callers guard)."""
    L = min(limbs.shape[-1], 4)
    shifts = jnp.arange(L, dtype=jnp.int64) * 16
    return jnp.sum(limbs[..., :L].astype(jnp.int64) << shifts, axis=-1)


@dataclasses.dataclass(frozen=True)
class VecKey:
    """Limb-packed key material for the batched path."""
    key: gold.PaillierKey
    pack_n: ops.ModulusPack
    pack_n2: ops.ModulusPack
    pack_p2: ops.ModulusPack
    pack_q2: ops.ModulusPack
    n_limbs: np.ndarray          # n as L16(n2) limbs (for 1 + m*n)
    mu_limbs: np.ndarray         # Paillier mu as L16(n) limbs
    lam_p: np.ndarray            # lam mod phi(p^2), exponent limbs
    lam_q: np.ndarray            # lam mod phi(q^2)
    p2_inv_q2: np.ndarray        # (p^2)^{-1} mod q^2, L16(q2) limbs
    p2_limbs: np.ndarray         # p^2 as L16(n2) limbs
    n_inv_2k: int                # n^{-1} mod 2^{16 (L16(n)+1)} for exact L(x)
    exp_limbs_half: int          # limb count of half-space exponents


def make_vec_key(key: gold.PaillierKey) -> VecKey:
    pack_n = ops.pack_modulus(key.n)
    pack_n2 = ops.pack_modulus(key.n2)
    pack_p2 = ops.pack_modulus(key.p2)
    pack_q2 = ops.pack_modulus(key.q2)
    le = max(bi.n_limbs_for(key.phi_p2), bi.n_limbs_for(key.phi_q2))
    k_bits = 16 * (pack_n.L16 + 1)
    return VecKey(
        key=key, pack_n=pack_n, pack_n2=pack_n2, pack_p2=pack_p2,
        pack_q2=pack_q2,
        n_limbs=bi.from_int(key.n, pack_n2.L16),
        mu_limbs=bi.from_int(key.mu, pack_n.L16),
        lam_p=bi.from_int(key.lam % key.phi_p2, le),
        lam_q=bi.from_int(key.lam % key.phi_q2, le),
        p2_inv_q2=bi.from_int(key.p2_inv_q2, pack_q2.L16),
        p2_limbs=bi.from_int(key.p2, pack_n2.L16),
        n_inv_2k=pow(key.n, -1, 1 << k_bits),
        exp_limbs_half=le,
    )


# ---------------------------------------------------------------------------
# Encryption: c = (1 + m n) * r^n mod n^2   (g = n+1 fast path)
# ---------------------------------------------------------------------------

def encrypt_batch(vk: VecKey, m: jax.Array, rn_limbs: jax.Array) -> jax.Array:
    """Encrypt int64 plaintexts (B,) with precomputed blindings r^n (B, L).

    The r^n pool comes from :func:`gold.make_r_pool` (amortized into T_pre,
    as the paper's initialization phase does for its own precomputations).
    """
    if vk.key.g != vk.key.n + 1:
        raise NotImplementedError("batched path uses the g = n+1 fast path")

    def body(m, rn_limbs):
        L2 = vk.pack_n2.L16
        m_limbs = int64_to_limbs(m, 4)
        n_row = jnp.broadcast_to(jnp.asarray(vk.n_limbs),
                                 (m_limbs.shape[0], L2))
        gm = bi.mul(m_limbs, n_row, out_limbs=L2)      # m*n < n^2, exact
        one = jnp.zeros_like(gm).at[..., 0].set(1)
        gm = bi.add(gm, one)                           # 1 + m n  (< n^2)
        return ops.mulmod(gm, rn_limbs, vk.pack_n2)

    return _cached_jit(vk, "enc", body)(m, rn_limbs)


# ---------------------------------------------------------------------------
# Decryption: m = L(c^lam mod n^2) * mu mod n, ModExp via CRT half-spaces
# ---------------------------------------------------------------------------

def crt_combine_batch(vk: VecKey, xp: jax.Array, xq: jax.Array) -> jax.Array:
    """x' (B, Lp2), x'' (B, Lq2) -> x (B, Ln2) per eq. (38).

    Shared by the in-graph decryption below and the int-in/int-out gold
    fast path (``core.paillier_batch``): one recombination per batch, done
    entirely in limb space (no per-element Python arithmetic).
    """
    B = xp.shape[0]
    Lq = vk.pack_q2.L16
    L2 = vk.pack_n2.L16
    # x' reduced into the q^2 space (x' < p^2 may exceed q^2 when p > q)
    xp_q = _reduce_into(xp, vk.pack_q2)
    xq_f = _fit(xq, Lq)
    # d = (x'' - x') mod q^2 with wrap-around correction
    neg = (bi.compare(xq_f, xp_q) < 0)[..., None]
    d0 = bi.sub(xq_f, xp_q)                     # mod 2^{16 Lq}
    q2_row = jnp.broadcast_to(jnp.asarray(vk.pack_q2.m16), d0.shape)
    d = jnp.where(neg, bi.add(d0, q2_row), d0)
    t = ops.mulmod(d, jnp.broadcast_to(jnp.asarray(vk.p2_inv_q2), d.shape),
                   vk.pack_q2)
    # x = x' + t * p^2  (exact, < n^2)
    tp2 = bi.mul(t, jnp.broadcast_to(jnp.asarray(vk.p2_limbs), (B, L2)),
                 out_limbs=L2)
    return bi.add(_fit(xp, L2), tp2)


def _fit(x: jax.Array, L: int) -> jax.Array:
    if x.shape[-1] == L:
        return x
    if x.shape[-1] > L:
        return x[..., :L]
    return jnp.pad(x, ((0, 0), (0, L - x.shape[-1])))


def _one(L: int) -> jax.Array:
    return jnp.zeros((L,), jnp.int32).at[0].set(1)


def decrypt_batch(vk: VecKey, c_limbs: jax.Array) -> jax.Array:
    """Ciphertext limbs (B, Ln2) -> int64 plaintexts (B,).

    Narrow legacy form: plaintexts MUST fit 63 bits or they silently
    wrap (``limbs_to_int64``).  Callers whose plaintexts can exceed that
    — any key over ~62 bits running the full Theorem-1 chain at large
    Delta — use :func:`decrypt_batch_limbs` and decode the limbs
    losslessly (``bigint.to_ints``), as ``protocol.VecBox`` does.
    """
    return limbs_to_int64(decrypt_batch_limbs(vk, c_limbs))


def decrypt_batch_limbs(vk: VecKey, c_limbs: jax.Array) -> jax.Array:
    """Ciphertext limbs (B, Ln2) -> plaintext limbs (B, Ln), full width.

    c^lam is computed in the two half-width spaces (the paper's CRT
    acceleration) and recombined; L(x) = (x-1)/n is an exact division done
    multiplicatively via n^{-1} mod 2^k (no big-int division circuit).
    The result is the complete residue mod n — no 63-bit truncation.
    """
    # the reduce impl resolves at trace time inside ops.modexp_fixed_pair,
    # so it must be part of the cache identity (env flips retrace, not
    # replay)
    fn = _cached_jit(vk, ("dec", ops.active_reduce_impl()),
                     lambda c: _decrypt_impl(vk, c))
    ops.count_fixed_crt(vk.pack_p2, vk.pack_q2)
    return fn(c_limbs)


def _decrypt_impl(vk: VecKey, c_limbs: jax.Array) -> jax.Array:
    B = c_limbs.shape[0]
    # reduce c into each half space (eq. 35a-b)
    cp = _reduce_into(c_limbs, vk.pack_p2)
    cq = _reduce_into(c_limbs, vk.pack_q2)
    # lam is key-constant and host-known, so the fixed-window ladder
    # applies (static schedule, no oblivious table selects)
    lam_p = bi.to_ints(np.asarray(vk.lam_p).reshape(1, -1))[0]
    lam_q = bi.to_ints(np.asarray(vk.lam_q).reshape(1, -1))[0]
    xp, xq = ops.modexp_fixed_pair(cp, lam_p, vk.pack_p2,
                                   cq, lam_q, vk.pack_q2)
    x = crt_combine_batch(vk, xp, xq)    # c^lam mod n^2
    # alpha = (x - 1) / n  — exact division, multiplicative
    Ln = vk.pack_n.L16
    k_limbs = Ln + 1
    xm1 = bi.sub(x, jnp.broadcast_to(_one(x.shape[-1]), x.shape))
    ninv = bi.from_int(vk.n_inv_2k, k_limbs)
    alpha = bi.mul(_fit(xm1, k_limbs),
                   jnp.broadcast_to(jnp.asarray(ninv), (B, k_limbs)),
                   out_limbs=k_limbs)
    # m = alpha * mu mod n (full limb width; wrappers narrow if asked)
    return ops.mulmod(_fit(alpha, Ln),
                      jnp.broadcast_to(jnp.asarray(vk.mu_limbs), (B, Ln)),
                      vk.pack_n)


def _reduce_into(c: jax.Array, pack: ops.ModulusPack) -> jax.Array:
    """Big (B, L) value -> (B, Lpack) reduced mod pack.m via chunked fold.

    Splits c into Lpack-limb chunks and folds MSB->LSB with
    acc = acc * 2^{16 Lpack} + chunk (two mulmods per chunk) — standard
    wide-to-narrow reduction without division.
    """
    Lp = pack.L16
    B = c.shape[0]
    n_chunks = -(-c.shape[-1] // Lp)
    c = _fit(c, n_chunks * Lp)
    base = (1 << (16 * Lp)) % pack.m_int
    base_l = jnp.broadcast_to(jnp.asarray(bi.from_int(base, Lp)), (B, Lp))
    one = jnp.broadcast_to(_one(Lp), (B, Lp))
    m_pad = _fit(jnp.broadcast_to(jnp.asarray(pack.m16), (B, Lp)), Lp + 1)
    acc = jnp.zeros((B, Lp), jnp.int32)
    for i in range(n_chunks - 1, -1, -1):
        # chunk < 2^{16 Lp} may exceed m by a large factor: Barrett it first
        chunk = ops.mulmod(c[..., i * Lp:(i + 1) * Lp], one, pack)
        acc = ops.mulmod(acc, base_l, pack)
        s = bi.add(_fit(acc, Lp + 1), _fit(chunk, Lp + 1))   # < 2m
        s = bi._cond_sub(s, m_pad)
        acc = s[..., :Lp]
    return acc


# ---------------------------------------------------------------------------
# Homomorphic operators (vectorized Definitions 1 & 2)
# ---------------------------------------------------------------------------

def c_add_batch(vk: VecKey, c1: jax.Array, c2: jax.Array) -> jax.Array:
    """Enc(a) ⊕ Enc(b): elementwise ciphertext product mod n^2."""
    return ops.mulmod(c1, c2, vk.pack_n2)


def c_mul_const_batch(vk: VecKey, c: jax.Array, k: jax.Array,
                      exp_limbs: int = 4) -> jax.Array:
    """k ⊗ Enc(a): per-element ciphertext^k mod n^2 (k int64 >= 0)."""
    def body(c, k):
        return ops.modexp(c, int64_to_limbs(k, exp_limbs), vk.pack_n2)
    return _cached_jit(vk, f"cmul_{exp_limbs}", body)(c, k)


def c_matvec(vk: VecKey, K: jax.Array, c_vec: jax.Array,
             exp_limbs: int = 4) -> jax.Array:
    """Homomorphic matrix-vector product: out[i] = Π_j c_j^{K[i,j]} mod n^2.

    This is the edge node's x-hat update (eq. 13): the (M, N) ModExp batch is
    flattened into one kernel launch (the paper's SM-level parallelism), then
    row-reduced with a log-depth tree of batched ciphertext multiplies.
    """
    return _cached_jit(vk, f"cmv_{exp_limbs}_{K.shape}",
                       lambda K, c: _c_matvec_impl(vk, K, c, exp_limbs))(
                           K, c_vec)


def _c_matvec_impl(vk: VecKey, K: jax.Array, c_vec: jax.Array,
                   exp_limbs: int) -> jax.Array:
    M, N = K.shape
    L2 = vk.pack_n2.L16
    powed = ops.modexp(
        jnp.broadcast_to(c_vec[None, :, :], (M, N, L2)).reshape(M * N, L2),
        int64_to_limbs(K.reshape(-1), exp_limbs),
        vk.pack_n2).reshape(M, N, L2)
    return mul_tree(vk, powed)


def mul_tree(vk: VecKey, cur: jax.Array) -> jax.Array:
    """Log-depth batched ciphertext product over axis 1: (R, N, L) -> (R, L).

    Each round halves N with one batched mulmod launch mod n^2; exact
    modular arithmetic makes the tree association bit-transparent vs. a
    sequential fold.  Shared by :func:`c_matvec`, the runtime's coalesced
    ``c_matvec_many`` and the gold fast path's homomorphic matvec.
    """
    R, n_cur, L2 = cur.shape
    while n_cur > 1:
        half = n_cur // 2
        a = cur[:, :half]
        b = cur[:, half:2 * half]
        prod = ops.mulmod(a.reshape(R * half, L2), b.reshape(R * half, L2),
                          vk.pack_n2).reshape(R, half, L2)
        if n_cur % 2:
            prod = jnp.concatenate([prod, cur[:, -1:]], axis=1)
            n_cur = half + 1
        else:
            n_cur = half
        cur = prod
    return cur[:, 0]

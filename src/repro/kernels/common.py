"""Radix-256 limb helpers: the multiplies, carries and ladders of the kernels.

Kernel-internal representation: base-2^8 limbs held in int32. Rationale
(DESIGN.md §2): TPU vector units have no 64-bit integer path; with 8-bit
limbs every partial product is < 2^16 and a full 4096-bit convolution row
accumulates to < 2^27, exactly in int32 — the same "high bitwidth -> wide
low-bitwidth lanes" decomposition the paper performs for CUDA cores, re-sized
for the TPU's int32 VPU (and int8-MXU-friendly if the convolution is ever
re-cast as a Toeplitz matmul).

Public arrays elsewhere in repro use 16-bit limbs (core/bigint.py); the
converters below are exact and cheap.
"""
from __future__ import annotations

import os as _os

import jax
import jax.numpy as jnp

RADIX_BITS = 8
RADIX = 1 << RADIX_BITS
RADIX_MASK = RADIX - 1


def limbs16_to8(x: jax.Array) -> jax.Array:
    """(..., L) base-2^16 int32 -> (..., 2L) base-2^8 int32 (little-endian)."""
    lo = x & 0xFF
    hi = (x >> 8) & 0xFF
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*x.shape[:-1], 2 * x.shape[-1]).astype(jnp.int32)


def limbs8_to16(x: jax.Array) -> jax.Array:
    """(..., 2L) base-2^8 -> (..., L) base-2^16 (length must be even)."""
    assert x.shape[-1] % 2 == 0
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    return (pairs[..., 0] + (pairs[..., 1] << 8)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Radix-256 primitives on 2-D blocks (B, L), as plain jnp.
# ---------------------------------------------------------------------------

# Carries, borrows and comparisons resolve in constant depth: no per-limb
# loop.  A few vectorised radix folds bound every coefficient to [0, 256];
# what is left is a one-bit cascade, which a carry-lookahead over the limb
# axis settles in one running maximum (``_lookahead``).  A borrow is the
# same cascade on the digits of ``a - b``, and the borrow out of the top
# limb is the comparison ``a < b``, so a conditional subtract needs no
# separate compare.

# Four folds take any coefficient in [0, 2^31) to [0, 256]: the excess over
# a limb shrinks to < 2^23, < 2^15, < 2^7 and then <= 1.
_N_FOLDS = 4


def _shift_up(x: jax.Array, d: int = 1) -> jax.Array:
    """Move every limb d places up (limb k -> k + d); the top d drop."""
    return jnp.pad(x[:, :-d], ((0, 0), (d, 0)))


def _lookahead(g: jax.Array, p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Resolve a one-bit cascade: limb k generates a carry (``g``) or passes
    on the one it receives (``p``), never both.

    The carry out of limb k is the generate bit of the last limb at or
    below k that does not pass one on.  Keyed ``2(k+1) + g`` there (0 where
    ``p``), that is the low bit of a running maximum over the limb axis:
    one ``lax.cummax``, which XLA lowers on TPU to a ``reduce_window``.
    Timed on a TPU v5e, a Kogge-Stone doubling of the same maximum was a
    few percent faster inside ``montmul2d``, but it moved the Barrett
    mulmod's convolution accumulators to a limb-minor layout at batches of
    128 rows and more, which slowed the rows ladders far more than that;
    the cummax leaves every loop's layout as the sequential carries had it.
    Returns the carry INTO each limb (B, L) and the carry out of the top
    limb (B,).
    """
    pos = 2 * jnp.arange(1, g.shape[1] + 1, dtype=jnp.int32)
    key = jnp.where(p, 0, pos + g.astype(jnp.int32))
    c_out = jax.lax.cummax(key, axis=1) & 1
    return _shift_up(c_out), c_out[:, -1]


def carry2d(acc: jax.Array) -> jax.Array:
    """Exact carry propagation of int32 coefficients in [0, 2^31) to base 256.

    Overflow past the last limb is dropped (callers size outputs to avoid
    information loss).
    """
    v = acc
    for _ in range(_N_FOLDS):
        v = (v & RADIX_MASK) + _shift_up(v >> RADIX_BITS)
    c_in, _ = _lookahead(v == RADIX, v == RADIX_MASK)
    return (v + c_in) & RADIX_MASK


def _sub_borrow(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """a - b mod 256^L for limbs in [0, 255], and the borrow out (B,) of the
    top limb, which is 1 exactly when a < b."""
    d = a - b
    b_in, b_top = _lookahead(d < 0, d == 0)
    return (d - b_in) & RADIX_MASK, b_top


def sub2d(a: jax.Array, b: jax.Array) -> jax.Array:
    """a - b mod 256^L (wrap-around); limbs in [0, 255]."""
    return _sub_borrow(a, b)[0]


# Convolution strategy (§Perf iteration log):
#   "loop"   — La sequential shift-and-add steps (the direct port of the
#              paper's per-bit GPU decomposition);
#   "matmul" — one constant-index gather building the per-row Toeplitz of b,
#              then a single batched int matmul t = a @ Toeplitz(b) — the
#              MXU-shaped form from DESIGN.md §2. MEASURED on the XLA CPU
#              backend: 5.4x SLOWER than "loop" (gather materialization has
#              no MXU to feed) — hypothesis refuted for CPU, kept selectable
#              for real-TPU evaluation (EXPERIMENTS.md §Perf).
MUL_IMPL = _os.environ.get("REPRO_MUL_IMPL", "loop")


def _mul2d_loop(a: jax.Array, b: jax.Array) -> jax.Array:
    bsz, la = a.shape
    lb = b.shape[1]
    acc = jnp.zeros((bsz, la + lb), jnp.int32)

    def body(i, acc):
        ai = jax.lax.dynamic_slice(a, (0, i), (bsz, 1))
        seg = jax.lax.dynamic_slice(acc, (0, i), (bsz, lb))
        return jax.lax.dynamic_update_slice(acc, seg + ai * b, (0, i))

    return jax.lax.fori_loop(0, la, body, acc)


def _mul2d_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    bsz, la = a.shape
    lb = b.shape[1]
    full = la + lb
    k = jnp.arange(full)
    i = jnp.arange(la)
    idx = k[None, :] - i[:, None]                      # (la, full), static
    valid = (idx >= 0) & (idx < lb)
    idx_c = jnp.clip(idx, 0, lb - 1)
    toep = jnp.where(valid[None], b[:, idx_c], 0)      # (bsz, la, full)
    return jnp.einsum("bi,bif->bf", a, toep)


def conv2d(a: jax.Array, b: jax.Array) -> jax.Array:
    """Limb convolution (B, La) x (B, Lb) -> (B, La + Lb), coefficients left
    unnormalised: each is at most min(La, Lb) * 255^2."""
    return (_mul2d_matmul(a, b) if MUL_IMPL == "matmul"
            else _mul2d_loop(a, b))


def mul2d(a: jax.Array, b: jax.Array, out_limbs: int) -> jax.Array:
    """Exact limb convolution (B, La) x (B, Lb) -> (B, out_limbs), base 256.

    Every coefficient stays < La * 255^2 < 2^31 for La <= 8192, so int32
    accumulation is exact in both implementations.  Carries only move up,
    so the low ``out_limbs`` limbs need only the low coefficients.
    """
    acc = conv2d(a, b)
    full = acc.shape[1]
    if out_limbs <= full:
        return carry2d(acc[:, :out_limbs])
    return jnp.pad(carry2d(acc), ((0, 0), (0, out_limbs - full)))


def cond_sub2d(r: jax.Array, m: jax.Array) -> jax.Array:
    """r - m if r >= m else r; m broadcast/padded to r's width.

    The comparison is the borrow out of ``r - m``: none means r >= m.
    """
    if m.shape[1] < r.shape[1]:
        m = jnp.pad(m, ((0, 0), (0, r.shape[1] - m.shape[1])))
    if m.shape[0] == 1 and r.shape[0] != 1:
        m = jnp.broadcast_to(m, r.shape)
    diff, borrow = _sub_borrow(r, m)
    return jnp.where(borrow[:, None] == 1, r, diff)


def barrett2d(x: jax.Array, m: jax.Array, mu: jax.Array) -> jax.Array:
    """x (B, 2L) mod m (1|B, L) with mu = floor(256^{2L}/m) (1|B, L+1)."""
    bsz = x.shape[0]
    L = m.shape[1]
    if m.shape[0] == 1 and bsz != 1:
        m = jnp.broadcast_to(m, (bsz, L))
    if mu.shape[0] == 1 and bsz != 1:
        mu = jnp.broadcast_to(mu, (bsz, mu.shape[1]))
    if x.shape[1] < 2 * L:
        x = jnp.pad(x, ((0, 0), (0, 2 * L - x.shape[1])))
    q1 = x[:, L - 1:]                                   # L+1 limbs
    q2 = mul2d(q1, mu, 2 * L + 2)
    q3 = q2[:, L + 1:]                                  # L+1 limbs
    r1 = x[:, :L + 1]
    r2 = mul2d(q3, m, L + 1)
    r = sub2d(r1, r2)
    r = cond_sub2d(r, m)
    r = cond_sub2d(r, m)
    return r[:, :L]


def mulmod2d(a, b, m, mu):
    L = m.shape[1]
    return barrett2d(mul2d(a, b, 2 * L), m, mu)


def modexp2d(base, exp, m, mu):
    """base^exp mod m; per-row exponents (B, Le); constant-time ladder.

    Binary square-and-multiply: 2 mulmods per exponent bit (1 squaring + 1
    selected multiply). See modexp2d_win4 for the windowed variant.
    """
    L = m.shape[1]
    bsz = base.shape[0]
    n_bits = exp.shape[1] * RADIX_BITS
    one = jnp.zeros((bsz, L), jnp.int32).at[:, 0].set(1)
    base = barrett2d(base, m, mu)

    def body(j, st):
        res, b = st
        limb = jax.lax.dynamic_slice(exp, (0, j // RADIX_BITS), (bsz, 1))[:, 0]
        bit = (limb >> (j % RADIX_BITS)) & 1
        res = jnp.where((bit == 1)[:, None], mulmod2d(res, b, m, mu), res)
        b = mulmod2d(b, b, m, mu)
        return res, b

    res, _ = jax.lax.fori_loop(0, n_bits, body, (one, base))
    return res


def modexp2d_win4(base, exp, m, mu):
    """4-bit fixed-window ModExp (beyond-paper §Perf optimization).

    Left-to-right over 4-bit windows: 4 squarings + 1 constant-time
    table-select multiply per window = 1.25 mulmods/bit vs the binary
    ladder's 2/bit (predicted ~1.6x; measured in EXPERIMENTS.md §Perf).
    The 16-entry power table is built with 15 mulmods up front (amortized
    over >= 64-bit exponents) and selected obliviously via masked sums —
    no data-dependent addressing, preserving the constant-time property.
    """
    L = m.shape[1]
    bsz = base.shape[0]
    n_bits = exp.shape[1] * RADIX_BITS
    n_win = n_bits // 4
    assert n_bits % 4 == 0
    one = jnp.zeros((bsz, L), jnp.int32).at[:, 0].set(1)
    base = barrett2d(base, m, mu)

    # table[t] = base^t, t = 0..15  (15 sequential mulmods)
    def build(t, tab):
        prev = jax.lax.dynamic_slice(tab, (t - 1, 0, 0), (1, bsz, L))[0]
        nxt = mulmod2d(prev, base, m, mu)
        return jax.lax.dynamic_update_slice(tab, nxt[None], (t, 0, 0))

    tab0 = jnp.zeros((16, bsz, L), jnp.int32).at[0].set(one).at[1].set(base)
    table = jax.lax.fori_loop(2, 16, build, tab0)

    def body(w, res):
        # windows processed MSB-first: window index j = n_win-1-w
        j = n_win - 1 - w
        limb = jax.lax.dynamic_slice(exp, (0, (4 * j) // RADIX_BITS),
                                     (bsz, 1))[:, 0]
        win = (limb >> ((4 * j) % RADIX_BITS)) & 0xF          # (bsz,)
        # 4 squarings
        for _ in range(4):
            res = mulmod2d(res, res, m, mu)
        # oblivious table select: sum_t [win == t] * table[t]
        sel = jnp.zeros((bsz, L), jnp.int32)
        onehot = (win[None, :] == jnp.arange(16, dtype=win.dtype)[:, None])
        sel = jnp.sum(jnp.where(onehot[..., None], table, 0), axis=0)
        sel = sel.astype(jnp.int32)
        return mulmod2d(res, sel, m, mu)

    return jax.lax.fori_loop(0, n_win, body, one)

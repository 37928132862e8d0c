"""Montgomery-form radix-256 limb kernels (REDC) — the fast reduce path.

Barrett reduction (``common.barrett2d``) costs two extra full convolutions
(``q2 = q1*mu``, ``r2 = q3*m``) plus two conditional subtractions per
mulmod.  Montgomery multiplication replaces all of that with a single
L-step REDC sweep interleaving the inverse-limb multiply and carry, so a
mulmod is one convolution + one REDC + one conditional subtract.  Those
two L-step loops are the only serial chains of a multiply: carries,
borrows and the subtract's comparison resolve in constant depth
(``common.carry2d``, ``common.cond_sub2d``).

Representation (same as ``common.py``): little-endian radix-2^8 limbs in
int32, 2-D blocks ``(B, L)``.  With ``R = 256^L``:

* ``mont(x) = x * R mod m``                (domain enter: ``to_mont2d``)
* ``montmul(a, b) = a*b*R^{-1} mod m``     (so mont(a)·mont(b) → mont(ab))
* ``redc2d(t) = t * R^{-1} mod m``         (domain leave when t = mont(x))

REDC correctness bound: for ``t < R*m`` the unreduced output is ``< 2m``,
so exactly one conditional subtract normalizes it.  Every call site below
satisfies ``t < R*m`` because at least one convolution operand is ``< m``.

Overflow bound: ``montmul2d`` hands the convolution's coefficients to the
sweep unnormalised.  Each is at most ``L*255^2``; the sweep adds at most
``L-1`` partial products ``u*m[j] <= 255^2`` into any coefficient, and the
running carry stays below ``2L*255``, so every coefficient the sweep reads
is below ``(2L-1)*255^2 + 2L*255 < 2L*255*256`` -- under ``2^25`` at
``L = 256``, and exact in int32 for ``L <= 16384``.  The sweep needs of
limb i only its value mod 256 and an exact ``>> 8``, both of which an
unnormalised coefficient gives; ``common.carry2d`` then normalises the
high half once.

The exponent ladders mirror ``common.modexp2d``/``modexp2d_win4`` with the
Barrett mulmod swapped for ``montmul2d`` (the ``REPRO_REDUCE_IMPL`` knob in
``kernels/ops.py`` selects between them; Barrett stays the oracle).  The
``*_fixed`` ladders take a host-known exponent shared by each row group
(enc's ``r^n``, dec's ``c^lam``) as a static MSB-first 4-bit window schedule:
the table select becomes a constant-index gather (the access pattern is
baked into the trace, so runtime behaviour stays input-independent) and the
ladder length tracks the exponent's true bit-length instead of the padded
limb width.  The Montgomery one also takes per-row moduli and a schedule per
row group, so the two CRT halves of a fixed exponentiation run stacked as
one ladder (``ops.modexp_fixed_pair``) instead of two in a row.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import common as cm


def mont_constants(m: int, L8: int) -> tuple[int, int, int] | None:
    """Host-side Montgomery material for modulus ``m`` at ``L8`` limbs.

    Returns ``(mp, r1, r2)`` with ``mp = -m^{-1} mod 256``,
    ``r1 = R mod m`` (the Montgomery form of 1) and ``r2 = R^2 mod m``
    (the domain-enter multiplier), or ``None`` for even moduli (REDC
    requires ``gcd(m, 256) = 1``; callers fall back to Barrett).
    """
    if m % 2 == 0 or m <= 1:
        return None
    R = 1 << (8 * L8)
    mp = (-pow(m, -1, 256)) % 256
    return mp, R % m, (R * R) % m


def _bcast_m(m: jax.Array, bsz: int) -> jax.Array:
    if m.shape[0] == 1 and bsz != 1:
        m = jnp.broadcast_to(m, (bsz, m.shape[1]))
    return m


def redc2d(t: jax.Array, m: jax.Array, mp) -> jax.Array:
    """t (B, <=2L) * R^{-1} mod m -> (B, L); requires t < R*m, m odd.

    ``t``'s coefficients may be unnormalised (the overflow bound in the
    module docstring).  ``m`` is (1, L) or per-row (B, L); ``mp``
    (``-m^{-1} mod 256``) is an int or a per-row (B,) array to match.

    One sequential sweep of L steps: step i zeroes limb i by adding
    ``u = (t[i] * mp) & 0xFF`` copies of m at position i, carrying through
    the chain; the surviving high half divided by R is the result.
    """
    bsz = t.shape[0]
    L = m.shape[1]
    if t.shape[1] < 2 * L:
        t = jnp.pad(t, ((0, 0), (0, 2 * L - t.shape[1])))
    m = _bcast_m(m, bsz)
    m0 = m[:, 0]
    m_hi = m[:, 1:]                                    # (bsz, L-1)

    def step(i, st):
        c, acc = st
        v = jax.lax.dynamic_slice(acc, (0, i), (bsz, 1))[:, 0] + c
        u = ((v & cm.RADIX_MASK) * mp) & cm.RADIX_MASK
        c2 = (v + u * m0) >> cm.RADIX_BITS             # low limb is now 0
        if L > 1:
            seg = jax.lax.dynamic_slice(acc, (0, i + 1), (bsz, L - 1))
            acc = jax.lax.dynamic_update_slice(
                acc, seg + u[:, None] * m_hi, (0, i + 1))
        return c2, acc

    c, acc = jax.lax.fori_loop(
        0, L, step, (jnp.zeros((bsz,), jnp.int32), t))
    # high half (coefficients still unnormalized) + the final carry at
    # position L; value < 2m so L+1 limbs suffice and one cond_sub ends it.
    hi = jnp.pad(acc[:, L:2 * L], ((0, 0), (0, 1)))
    hi = hi.at[:, 0].add(c)
    r = cm.carry2d(hi)
    return cm.cond_sub2d(r, m)[:, :L]


def montmul2d(a: jax.Array, b: jax.Array, m: jax.Array, mp: int) -> jax.Array:
    """mont-domain product a*b*R^{-1} mod m; (B, L) x (B, L) -> (B, L).

    The convolution goes to REDC unnormalised, with no carry between."""
    return redc2d(cm.conv2d(a, b), m, mp)


def to_mont2d(x: jax.Array, m: jax.Array, mp: int, r2: jax.Array) -> jax.Array:
    """Enter the Montgomery domain: x -> x*R mod m (x may be >= m)."""
    bsz = x.shape[0]
    return montmul2d(x, jnp.broadcast_to(r2, (bsz, r2.shape[1])), m, mp)


def from_mont2d(x: jax.Array, m: jax.Array, mp: int) -> jax.Array:
    """Leave the Montgomery domain: mont(v) -> v (= REDC of the bare x)."""
    return redc2d(x, m, mp)


def _mont_one(r1: jax.Array, bsz: int) -> jax.Array:
    return jnp.broadcast_to(r1, (bsz, r1.shape[1]))


def modexp2d_mont(base, exp, m, mp, r1, r2):
    """Binary constant-time ladder in the Montgomery domain.

    Same schedule as ``common.modexp2d`` (1 squaring + 1 selected multiply
    per exponent bit) with REDC in place of Barrett; domain enter/leave
    adds 2 montmul-equivalents total, amortized over the whole ladder.
    """
    bsz = base.shape[0]
    n_bits = exp.shape[1] * cm.RADIX_BITS
    m = _bcast_m(m, bsz)
    one = _mont_one(r1, bsz)
    base_m = to_mont2d(base, m, mp, r2)

    def body(j, st):
        res, b = st
        limb = jax.lax.dynamic_slice(
            exp, (0, j // cm.RADIX_BITS), (bsz, 1))[:, 0]
        bit = (limb >> (j % cm.RADIX_BITS)) & 1
        res = jnp.where((bit == 1)[:, None], montmul2d(res, b, m, mp), res)
        b = montmul2d(b, b, m, mp)
        return res, b

    res, _ = jax.lax.fori_loop(0, n_bits, body, (one, base_m))
    return from_mont2d(res, m, mp)


def _mont_table16(base_m, one, m, mp):
    """table[t] = mont(base^t), t = 0..15 (15 sequential montmuls)."""
    bsz, L = base_m.shape

    def build(t, tab):
        prev = jax.lax.dynamic_slice(tab, (t - 1, 0, 0), (1, bsz, L))[0]
        nxt = montmul2d(prev, base_m, m, mp)
        return jax.lax.dynamic_update_slice(tab, nxt[None], (t, 0, 0))

    tab0 = (jnp.zeros((16, bsz, L), jnp.int32)
            .at[0].set(one).at[1].set(base_m))
    return jax.lax.fori_loop(2, 16, build, tab0)


def modexp2d_mont_win4(base, exp, m, mp, r1, r2):
    """4-bit fixed-window ladder in the Montgomery domain.

    Mirrors ``common.modexp2d_win4`` (4 squarings + 1 oblivious table
    select per window = 1.25 mulmods/bit + a 15-montmul table) with REDC
    as the reduction.  Exponent bit-width must be a multiple of 4
    (``ops.modexp`` validates at the wrapper boundary).
    """
    bsz, L = base.shape[0], m.shape[1]
    n_bits = exp.shape[1] * cm.RADIX_BITS
    n_win = n_bits // 4
    assert n_bits % 4 == 0
    m = _bcast_m(m, bsz)
    one = _mont_one(r1, bsz)
    base_m = to_mont2d(base, m, mp, r2)
    table = _mont_table16(base_m, one, m, mp)

    def body(w, res):
        j = n_win - 1 - w
        limb = jax.lax.dynamic_slice(
            exp, (0, (4 * j) // cm.RADIX_BITS), (bsz, 1))[:, 0]
        win = (limb >> ((4 * j) % cm.RADIX_BITS)) & 0xF
        for _ in range(4):
            res = montmul2d(res, res, m, mp)
        onehot = (win[None, :] == jnp.arange(16, dtype=win.dtype)[:, None])
        sel = jnp.sum(jnp.where(onehot[..., None], table, 0),
                      axis=0).astype(jnp.int32)
        return montmul2d(res, sel, m, mp)

    return from_mont2d(jax.lax.fori_loop(0, n_win, body, one), m, mp)


def exp_windows(e: int) -> tuple[int, ...]:
    """Host-known exponent -> static MSB-first 4-bit window tuple.

    Length tracks ``e.bit_length()`` rounded up to a nibble, so small
    key-constant exponents get proportionally shorter ladders.  ``e = 0``
    yields the empty tuple (the ladders then return 1).  A stacked ladder
    runs several schedules at once: :func:`exp_window_rows` front-pads them
    to one length.
    """
    if e < 0:
        raise ValueError("exp_windows requires a non-negative exponent")
    n_win = -(-max(e.bit_length(), 0) // 4)
    return tuple((e >> (4 * j)) & 0xF for j in reversed(range(n_win)))


def exp_window_rows(*es: int) -> np.ndarray:
    """Host-known exponents -> (G, n_win) int32 schedule, one row each.

    Shorter schedules are padded at the FRONT with zero windows: a leading
    zero window squares mont(1) and multiplies it by ``table[0] = mont(1)``,
    so the result is unchanged and the ladder runs as long as the longest
    exponent.
    """
    wins = [exp_windows(e) for e in es]
    n_win = max(map(len, wins), default=0)
    return np.asarray([(0,) * (n_win - len(w)) + w for w in wins],
                      np.int32).reshape(len(wins), n_win)


def _win_at(win_arr: jax.Array, w: jax.Array, g: int = 0) -> jax.Array:
    """Window value at position w of schedule row g (a static index)."""
    return jax.lax.dynamic_slice(win_arr, (w * 0 + g, w), (1, 1))[0, 0]


def modexp2d_mont_fixed(base, win_arr, m, mp, r1, r2, groups=()):
    """Fixed (host-known, per row group) exponent ladder, Montgomery domain.

    ``win_arr`` is the (G, n_win) int32 schedule of MSB-first 4-bit windows
    from :func:`exp_window_rows`; ``groups`` is the static tuple of the G
    groups' row counts, in row order (``()``: one group, the whole batch).
    ``m``, ``r1`` and ``r2`` are (1, L) or per-row (bsz, L), and ``mp`` an
    int or a per-row (bsz,) array, so each group may have its own modulus.
    The 16-entry power table is selected with a plain gather per group,
    joined by a static row mask, instead of the oblivious masked sum (the
    schedule is input-independent — it only depends on the key-constant
    exponents), and leading zero windows of the longest exponent are
    already trimmed — the two wins of knowing the exponent host-side.
    """
    bsz, L = base.shape[0], m.shape[1]
    n_grp, n_win = win_arr.shape
    m = _bcast_m(m, bsz)
    one = _mont_one(r1, bsz)
    if n_win == 0:
        return from_mont2d(one, m, mp)
    base_m = to_mont2d(base, m, mp, r2)
    table = _mont_table16(base_m, one, m, mp)
    row_grp = np.repeat(np.arange(n_grp), groups or (bsz,))[:, None]

    def body(w, res):
        for _ in range(4):
            res = montmul2d(res, res, m, mp)
        sel = None
        for g in range(n_grp):
            win = _win_at(win_arr, w, g)
            sel_g = jax.lax.dynamic_slice(table, (win, win * 0, win * 0),
                                          (1, bsz, L))[0]
            sel = sel_g if sel is None else jnp.where(row_grp == g, sel_g,
                                                      sel)
        return montmul2d(res, sel, m, mp)

    res = jax.lax.fori_loop(0, n_win, body, one)
    return from_mont2d(res, m, mp)


def modexp2d_fixed_barrett(base, win_arr, m, mu):
    """Fixed-exponent ladder on the Barrett oracle (REPRO_REDUCE_IMPL
    fallback and the even-modulus path); one group's (1, n_win) schedule."""
    bsz, L = base.shape[0], m.shape[1]
    n_win = win_arr.shape[1]
    one = jnp.zeros((bsz, L), jnp.int32).at[:, 0].set(1)
    if n_win == 0:
        return one
    base_r = cm.barrett2d(base, m, mu)
    table = _barrett_table16(base_r, one, m, mu)

    def body(w, res):
        for _ in range(4):
            res = cm.mulmod2d(res, res, m, mu)
        win = _win_at(win_arr, w)
        sel = jax.lax.dynamic_slice(table, (win, win * 0, win * 0),
                                    (1, bsz, L))[0]
        return cm.mulmod2d(res, sel, m, mu)

    return jax.lax.fori_loop(0, n_win, body, one)


def _barrett_table16(base_r, one, m, mu):
    bsz, L = base_r.shape

    def build(t, tab):
        prev = jax.lax.dynamic_slice(tab, (t - 1, 0, 0), (1, bsz, L))[0]
        nxt = cm.mulmod2d(prev, base_r, m, mu)
        return jax.lax.dynamic_update_slice(tab, nxt[None], (t, 0, 0))

    tab0 = (jnp.zeros((16, bsz, L), jnp.int32)
            .at[0].set(one).at[1].set(base_r))
    return jax.lax.fori_loop(2, 16, build, tab0)

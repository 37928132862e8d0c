"""Exact big-integer limb arithmetic in JAX.

TPU adaptation of the paper's §IV "adaptive GPU acceleration": a big integer
is a little-endian row of 16-bit limbs stored in int32 (``(..., L)``), with
products accumulated exactly in int64 (16+16+log2(L) <= 43 bits for L=2048).
High-bitwidth ModExp becomes wide low-bitwidth vector work batched over the
ciphertext axis — the batch dimension, not FFT butterflies, provides the
parallelism on the VPU/MXU (see DESIGN.md §2 for why the paper's float FFT
does not transfer to TPU).

Barrett reduction (HAC 14.42) replaces division by two multiplications and
limb shifts, exactly as the paper's Algorithm 2, with precomputed
``mu = floor(B^{2L} / m)``.

All functions are shape-polymorphic over leading batch dims and jit-safe.
Host-side helpers (``from_int``/``to_int``/``barrett_mu``) use Python ints.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.trace import span

jax.config.update("jax_enable_x64", True)

LIMB_BITS = 16
LIMB_BASE = 1 << LIMB_BITS
LIMB_MASK = LIMB_BASE - 1


# ---------------------------------------------------------------------------
# Host-side conversions (Python ints <-> limb arrays)
# ---------------------------------------------------------------------------

def from_int(x: int, n_limbs: int) -> np.ndarray:
    """Encode a nonnegative Python int as ``n_limbs`` little-endian limbs."""
    if x < 0:
        raise ValueError("bigint limbs encode nonnegative integers only")
    if x >> (LIMB_BITS * n_limbs):
        raise ValueError(f"{x.bit_length()}-bit value does not fit {n_limbs} limbs")
    out = np.zeros(n_limbs, dtype=np.int32)
    for i in range(n_limbs):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    return out


def from_ints(xs, n_limbs: int) -> np.ndarray:
    """Vectorize :func:`from_int` over a flat list -> (len(xs), n_limbs).

    Bulk codec: one ``int.to_bytes`` per element into a contiguous buffer,
    decoded by numpy in a single pass — ~10x faster than limb-at-a-time
    Python shifting at protocol batch sizes, with identical semantics
    (including the B=0 case and :func:`from_int`'s range errors).
    """
    with span("host:to_limbs"):
        xs = [int(x) for x in xs]
        if not xs:
            return np.zeros((0, n_limbs), dtype=np.int32)
        nbytes = 2 * n_limbs
        try:
            buf = b"".join(x.to_bytes(nbytes, "little") for x in xs)
        except OverflowError:
            for x in xs:
                if x < 0:
                    raise ValueError("bigint limbs encode nonnegative "
                                     "integers only") from None
                if x >> (LIMB_BITS * n_limbs):
                    raise ValueError(f"{x.bit_length()}-bit value does not "
                                     f"fit {n_limbs} limbs") from None
            raise
        out = np.frombuffer(buf, dtype="<u2").astype(np.int32)
        return out.reshape(len(xs), n_limbs)


def to_int(limbs) -> int:
    """Decode little-endian limbs (1-D) back to a Python int."""
    arr = np.asarray(limbs).astype(object)
    out = 0
    for i in range(arr.shape[-1] - 1, -1, -1):
        out = (out << LIMB_BITS) | int(arr[i])
    return out


def to_ints(limbs) -> list:
    """Decode a (..., L) limb array to a flat list of Python ints.

    Bulk codec mirror of :func:`from_ints`: the whole array is serialized
    to little-endian uint16 bytes in one numpy pass, then each row decodes
    with a single ``int.from_bytes`` (limbs are always normalized to
    [0, 2^16) by ``carry_normalize``, which this relies on).
    """
    arr = fetch(limbs)
    with span("host:to_ints"):
        flat = arr.reshape(-1, arr.shape[-1])
        if flat.shape[0] == 0:
            return []
        if flat.dtype == object:
            return [to_int(row) for row in flat]
        buf = np.ascontiguousarray(flat.astype("<u2")).tobytes()
        nbytes = 2 * flat.shape[1]
        return [int.from_bytes(buf[i * nbytes:(i + 1) * nbytes], "little")
                for i in range(flat.shape[0])]


def fetch(x) -> np.ndarray:
    """``np.asarray(x)``; a device array's copy to the host, which waits
    for the programs that make it, gets a ``host:fetch`` span of its own
    so that no host-path span covers a wait for the device."""
    if isinstance(x, jax.Array):
        with span("host:fetch"):
            return np.asarray(x)
    return np.asarray(x)


def barrett_mu(m: int, n_limbs: int) -> np.ndarray:
    """Precompute ``mu = floor(B^{2L} / m)`` as ``n_limbs + 1`` limbs."""
    mu = (1 << (LIMB_BITS * 2 * n_limbs)) // m
    return from_int(mu, n_limbs + 1)


def n_limbs_for(m: int) -> int:
    """Minimum limb count holding ``m`` (at least 1)."""
    return max(1, -(-m.bit_length() // LIMB_BITS))


# ---------------------------------------------------------------------------
# Carry / borrow propagation
# ---------------------------------------------------------------------------

def carry_normalize(acc: jax.Array) -> jax.Array:
    """Normalize int64 coefficients to base-2^16 limbs (int32).

    Overflow past the last limb is dropped (callers size outputs so this
    never loses information, mirroring fixed-register hardware).
    """
    acc = acc.astype(jnp.int64)
    xs = jnp.moveaxis(acc, -1, 0)  # (L, ...batch)

    def step(c, x):
        t = x + c
        return t >> LIMB_BITS, (t & LIMB_MASK).astype(jnp.int32)

    _, limbs = jax.lax.scan(step, jnp.zeros(xs.shape[1:], jnp.int64), xs)
    return jnp.moveaxis(limbs, 0, -1)


def add(a: jax.Array, b: jax.Array) -> jax.Array:
    """Limb-wise a + b with carry propagation. Shapes must match."""
    return carry_normalize(a.astype(jnp.int64) + b.astype(jnp.int64))


def sub(a: jax.Array, b: jax.Array) -> jax.Array:
    """a - b mod B^L (wrap-around two's-complement-style subtraction)."""
    diff = a.astype(jnp.int64) - b.astype(jnp.int64)
    xs = jnp.moveaxis(diff, -1, 0)

    def step(c, x):
        t = x + c
        borrow = (t < 0).astype(jnp.int64)
        return -borrow, (t + (borrow << LIMB_BITS)).astype(jnp.int32)

    _, limbs = jax.lax.scan(step, jnp.zeros(xs.shape[1:], jnp.int64), xs)
    return jnp.moveaxis(limbs, 0, -1)


def compare(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise big-int compare over the last axis: -1 / 0 / +1."""
    d = jnp.sign(a.astype(jnp.int64) - b.astype(jnp.int64))
    xs = jnp.moveaxis(d, -1, 0)

    def step(c, x):  # LSB -> MSB; higher limbs overwrite
        return jnp.where(x != 0, x, c), None

    out, _ = jax.lax.scan(step, jnp.zeros(xs.shape[1:], jnp.int64), xs)
    return out


# ---------------------------------------------------------------------------
# Multiplication: exact limb convolution (shift-and-add; the kernels'
# radix-256 form is kernels/common.py)
# ---------------------------------------------------------------------------

def mul(a: jax.Array, b: jax.Array, out_limbs: int | None = None) -> jax.Array:
    """Exact product of limb arrays: (..., La) x (..., Lb) -> (..., out).

    ``out_limbs`` defaults to La + Lb (full product, never truncates).
    """
    la = a.shape[-1]
    lb = b.shape[-1]
    out_limbs = out_limbs if out_limbs is not None else la + lb
    a64 = a.astype(jnp.int64)
    b64 = b.astype(jnp.int64)
    acc = jnp.zeros((*a.shape[:-1], la + lb), jnp.int64)

    def body(i, acc):
        # acc[..., i : i+lb] += a[..., i] * b
        seg = jax.lax.dynamic_slice_in_dim(acc, i, lb, axis=-1)
        seg = seg + a64[..., i][..., None] * b64
        return jax.lax.dynamic_update_slice_in_dim(acc, seg, i, axis=-1)

    acc = jax.lax.fori_loop(0, la, body, acc)
    full = carry_normalize(acc)
    if out_limbs == la + lb:
        return full
    if out_limbs < la + lb:
        return full[..., :out_limbs]
    pad = [(0, 0)] * (full.ndim - 1) + [(0, out_limbs - la - lb)]
    return jnp.pad(full, pad)


def shift_right_limbs(a: jax.Array, k: int) -> jax.Array:
    """Drop the k least-significant limbs (floor-divide by B^k)."""
    return a[..., k:]


def low_limbs(a: jax.Array, k: int) -> jax.Array:
    """Keep the k least-significant limbs (mod B^k)."""
    return a[..., :k]


# ---------------------------------------------------------------------------
# Barrett reduction and modular ops
# ---------------------------------------------------------------------------

def _cond_sub(r: jax.Array, m: jax.Array) -> jax.Array:
    """r - m if r >= m else r (shapes padded to match)."""
    lm = m.shape[-1]
    lr = r.shape[-1]
    if lm < lr:
        pad = [(0, 0)] * (m.ndim - 1) + [(0, lr - lm)]
        m = jnp.pad(m, pad)
    geq = (compare(r, m) >= 0)[..., None]
    return jnp.where(geq, sub(r, m), r)


def barrett_reduce(x: jax.Array, m: jax.Array, mu: jax.Array) -> jax.Array:
    """x mod m for x < B^{2L}, modulus m of L limbs, mu = floor(B^{2L}/m).

    Returns L limbs. Exact per HAC 14.42; the final remainder is < 3m so two
    fixed conditional subtractions suffice (static shapes, no data-dependent
    control flow — the same structure the paper maps onto GPU warps maps here
    onto SPMD vector lanes).
    """
    L = m.shape[-1]
    if x.shape[-1] < 2 * L:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, 2 * L - x.shape[-1])]
        x = jnp.pad(x, pad)
    q1 = shift_right_limbs(x, L - 1)                      # L+1 limbs
    q2 = mul(q1, _bcast(mu, q1))                          # 2L+2 limbs
    q3 = shift_right_limbs(q2, L + 1)                     # L+1 limbs
    r1 = low_limbs(x, L + 1)
    r2 = low_limbs(mul(q3, _bcast(m, q3), out_limbs=L + 1), L + 1)
    r = sub(r1, r2)                                       # mod B^{L+1}
    r = _cond_sub(r, _bcast(m, r))
    r = _cond_sub(r, _bcast(m, r))
    return low_limbs(r, L)


def _bcast(m: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast a 1-D modulus/constant to ``like``'s batch shape."""
    if m.ndim == 1 and like.ndim > 1:
        return jnp.broadcast_to(m, (*like.shape[:-1], m.shape[-1]))
    return m


def mulmod(a: jax.Array, b: jax.Array, m: jax.Array, mu: jax.Array) -> jax.Array:
    """(a * b) mod m, all operands of L limbs (a, b already reduced)."""
    return barrett_reduce(mul(a, b), m, mu)


def modexp(base: jax.Array, exp: jax.Array, m: jax.Array, mu: jax.Array) -> jax.Array:
    """base^exp mod m via constant-time binary square-and-multiply.

    ``base``: (..., L) limbs; ``exp``: (..., Le) limbs (per-element exponents);
    ``m``/``mu``: 1-D modulus limbs (broadcast) or batched. Returns (..., L).
    """
    L = m.shape[-1]
    n_bits = exp.shape[-1] * LIMB_BITS
    one = jnp.zeros_like(base).at[..., 0].set(1)
    exp64 = exp.astype(jnp.int64)

    def body(j, state):
        res, b = state
        limb = jax.lax.dynamic_index_in_dim(exp64, j // LIMB_BITS, axis=-1,
                                            keepdims=False)
        bit = (limb >> (j % LIMB_BITS).astype(limb.dtype)) & 1
        res_new = mulmod(res, b, m, mu)
        res = jnp.where((bit == 1)[..., None], res_new, res)
        b = mulmod(b, b, m, mu)
        return res, b

    # reduce base mod m first (callers may pass unreduced bases)
    base = barrett_reduce(base, _bcast(m, base), _bcast(mu, base))
    res, _ = jax.lax.fori_loop(0, n_bits, body, (one, base))
    return res


def mod_small(a: jax.Array, m: jax.Array, mu: jax.Array) -> jax.Array:
    """a mod m for a of up to 2L limbs (general entry point)."""
    return barrett_reduce(a, m, mu)

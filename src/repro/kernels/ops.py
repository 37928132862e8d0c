"""Public jit'd wrappers over the crypto kernels.

Callers hold big integers as radix-2^16 limb arrays (core/bigint.py format);
these wrappers pack the modulus, convert to the kernels' radix-256 layout,
run the jitted XLA ladders (``kernels/common.py``, ``kernels/montgomery.py``)
and convert back.

Reduction (``REPRO_REDUCE_IMPL``, read per call):
  * ``montgomery`` (default) — REDC ladders from kernels/montgomery.py for
    ``modexp``/``modexp_fixed`` (odd moduli; even moduli fall back);
  * ``barrett``    — the original trial-division-free oracle path.
  Standalone ``mulmod`` always uses Barrett: a lone product can't amortize
  the Montgomery domain enter/leave, so REDC only pays inside ladders.

Barrett correctness requires the modulus to fill its top radix-256 limb, so
``pack_modulus`` sizes L8 to the exact byte length (DESIGN.md §2 note on
radix re-sizing vs. the paper's b-tilde choice).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from ..core import bigint as bi
from ..obs.trace import span
from . import common as cm
from . import montgomery as mg
from . import ref as ref_impl

METHODS = ("binary", "win4")
REDUCE_IMPLS = ("barrett", "montgomery")

# the ladder of ``modexp`` and ``modexp_rows``: read per call, so a test
# may switch it to ``binary``
MODEXP_METHOD = "win4"

# jitted-closure cache: keyed by (modulus, op, method / reduce impl) —
# jax.jit dedups shapes internally, so each (op, modulus, shape) traces
# exactly once.
_JIT_CACHE: dict = {}


def _cached_jit(key, builder):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = jax.jit(builder)
    return fn


@dataclasses.dataclass(frozen=True)
class ModulusPack:
    """Precomputed modulus material for both radices.

    ``mp8``/``r1_8``/``r2_8`` are the Montgomery constants at the radix-256
    width (``-m^{-1} mod 256``, ``R mod m``, ``R^2 mod m`` with
    ``R = 256^L8``); ``None`` for even moduli, where only Barrett applies.
    """
    m_int: int
    L16: int
    L8: int
    m16: np.ndarray    # (L16,)
    mu16: np.ndarray   # (L16+1,)  floor(2^{32 L16} / m)
    m8: np.ndarray     # (1, L8)
    mu8: np.ndarray    # (1, L8+1) floor(256^{2 L8} / m)
    mp8: int | None = None
    r1_8: np.ndarray | None = None   # (1, L8)
    r2_8: np.ndarray | None = None   # (1, L8)


def pack_modulus(m: int) -> ModulusPack:
    L8 = max(1, -(-m.bit_length() // 8))
    L16 = max(1, -(-m.bit_length() // 16))
    mu8 = (1 << (16 * L8)) // m  # 256^{2 L8} = 2^{16 L8}
    mu8_limbs = np.zeros(L8 + 1, np.int32)
    x = mu8
    for i in range(L8 + 1):
        mu8_limbs[i] = x & 0xFF
        x >>= 8
    assert x == 0
    mont = mg.mont_constants(m, L8)
    mp8 = r1_8 = r2_8 = None
    if mont is not None:
        mp8, r1, r2 = mont
        r1_8 = _to8(r1, L8)[None, :]
        r2_8 = _to8(r2, L8)[None, :]
    return ModulusPack(
        m_int=m, L16=L16, L8=L8,
        m16=bi.from_int(m, L16), mu16=bi.barrett_mu(m, L16),
        m8=_to8(m, L8)[None, :], mu8=mu8_limbs[None, :],
        mp8=mp8, r1_8=r1_8, r2_8=r2_8,
    )


def _to8(x: int, n: int) -> np.ndarray:
    out = np.zeros(n, np.int32)
    for i in range(n):
        out[i] = x & 0xFF
        x >>= 8
    if x:
        raise ValueError("value does not fit limb count")
    return out


def _to_radix8(x16: jax.Array, L8: int) -> jax.Array:
    x8 = cm.limbs16_to8(x16)
    if x8.shape[-1] >= L8:
        return x8[..., :L8]
    return jnp.pad(x8, ((0, 0), (0, L8 - x8.shape[-1])))


def _to_radix16(x8: jax.Array, L16: int) -> jax.Array:
    if x8.shape[-1] < 2 * L16:
        x8 = jnp.pad(x8, ((0, 0), (0, 2 * L16 - x8.shape[-1])))
    return cm.limbs8_to16(x8)


def active_reduce_impl() -> str:
    """The session-wide reduction knob, validated (read per call so tests
    and the conformance matrix can flip it without re-importing)."""
    impl = os.environ.get("REPRO_REDUCE_IMPL", "montgomery")
    if impl not in REDUCE_IMPLS:
        raise ValueError(f"REPRO_REDUCE_IMPL={impl!r}; expected one of "
                         f"{REDUCE_IMPLS}")
    return impl


def _resolve_reduce(pack: ModulusPack, reduce_impl: str | None) -> str:
    impl = reduce_impl or active_reduce_impl()
    if impl not in REDUCE_IMPLS:
        raise ValueError(f"unknown reduce_impl {impl!r}; expected one of "
                         f"{REDUCE_IMPLS}")
    if impl == "montgomery" and pack.mp8 is None:
        return "barrett"            # even modulus: REDC needs gcd(m,256)=1
    return impl


def mulmod(a16: jax.Array, b16: jax.Array, pack: ModulusPack) -> jax.Array:
    """(B, L16) x (B, L16) -> (B, L16): (a*b) mod m."""
    m8 = pack.m8
    mu8 = pack.mu8
    L8, L16 = pack.L8, pack.L16
    if a16.shape[0] == 0:
        return jnp.zeros((0, L16), jnp.int32)

    def body(a16, b16):
        return _to_radix16(
            ref_impl.mulmod_ref(_to_radix8(a16, L8), _to_radix8(b16, L8),
                                jnp.asarray(m8), jnp.asarray(mu8)), L16)
    return _cached_jit((pack.m_int, "mulmod"), body)(a16, b16)


def _validate_method(method: str, exp_bits: int) -> None:
    if method not in METHODS:
        raise ValueError(f"unknown modexp method {method!r}; expected one "
                         f"of {METHODS}")
    if method == "win4" and exp_bits % 4 != 0:
        raise ValueError(
            f"win4 modexp requires an exponent bit-width that is a "
            f"multiple of 4, got {exp_bits} bits; pad the exponent limbs "
            f"or use method='binary'")


def modexp(base16: jax.Array, exp16: jax.Array, pack: ModulusPack,
           method: str | None = None,
           reduce_impl: str | None = None) -> jax.Array:
    """base^exp mod m over a batch; per-element exponents.

    ``method``: "binary" (the paper's Algorithm-2 ladder) or "win4"
    (4-bit fixed window, beyond-paper §Perf optimization; default).
    Exponent bit-width must be a multiple of 4 for win4 (16-bit limbs
    always satisfy this; validated here — the kernel-side assert is a
    trace-time no-op). ``reduce_impl`` overrides ``REPRO_REDUCE_IMPL``.
    """
    method = method or MODEXP_METHOD
    _validate_method(method, exp16.shape[1] * 16)
    impl = _resolve_reduce(pack, reduce_impl)
    m8 = pack.m8
    mu8 = pack.mu8
    L8, L16 = pack.L8, pack.L16
    if base16.shape[0] == 0:
        return jnp.zeros((0, L16), jnp.int32)
    # numpy constants, NOT jnp: converting here while an outer jit is
    # tracing would capture that trace's tracers in the cached closure
    mont_args = {}
    if impl == "montgomery":
        mont_args = dict(r1_8=pack.r1_8, r2_8=pack.r2_8, mp=pack.mp8)

    def body(base16, exp16):
        return _to_radix16(
            ref_impl.modexp_ref(_to_radix8(base16, L8),
                                cm.limbs16_to8(exp16),
                                jnp.asarray(m8), jnp.asarray(mu8),
                                method=method, reduce_impl=impl,
                                **mont_args), L16)
    return _cached_jit((pack.m_int, "modexp", method, impl),
                       body)(base16, exp16)


def modexp_fixed(base16: jax.Array, e: int, pack: ModulusPack,
                 reduce_impl: str | None = None) -> jax.Array:
    """base^e mod m with ONE host-known exponent shared across the batch.

    The fixed-base/fixed-exponent fast path (ROADMAP item 3): enc's
    ``r^n``, dec's CRT ``c^lam`` halves and scalar ``pow_c`` all raise a
    whole batch to the same key-constant exponent, so the 4-bit window
    schedule is precomputed host-side (:func:`montgomery.exp_windows`),
    baked into the trace as a constant, and the ladder length tracks the
    exponent's true bit-length.  Only call with key-constant exponents —
    the jit cache is keyed on ``e``.  The two CRT halves of one
    exponentiation go through :func:`modexp_fixed_pair`, which stacks
    them into one ladder where it can.
    """
    if e < 0:
        raise ValueError("modexp_fixed requires a non-negative exponent; "
                         "invert the base host-side first")
    impl = _resolve_reduce(pack, reduce_impl)
    m8 = pack.m8
    mu8 = pack.mu8
    L8, L16 = pack.L8, pack.L16
    if base16.shape[0] == 0:
        return jnp.zeros((0, L16), jnp.int32)
    windows = mg.exp_windows(e)

    def body(base16):
        b8 = _to_radix8(base16, L8)
        win_arr = jnp.asarray(windows, jnp.int32).reshape(1, -1)
        if impl == "montgomery":
            out8 = mg.modexp2d_mont_fixed(
                b8, win_arr, jnp.asarray(m8), pack.mp8,
                jnp.asarray(pack.r1_8), jnp.asarray(pack.r2_8))
        else:
            out8 = mg.modexp2d_fixed_barrett(
                b8, win_arr, jnp.asarray(m8), jnp.asarray(mu8))
        return _to_radix16(out8, L16)
    return _cached_jit((pack.m_int, "modexp_fixed", impl, e), body)(base16)


# fixed CRT ladder launches by form, bumped by the callers that launch them
# (``core.paillier_batch``, ``core.paillier_vec``): "stacked" runs both
# halves as one ladder, "split" as two ladders in a row (the Barrett oracle)
FIXED_CRT = {"stacked": 0, "split": 0}


def fixed_pair_stacks(pack_p: ModulusPack, pack_q: ModulusPack,
                      reduce_impl: str | None = None) -> bool:
    """Whether :func:`modexp_fixed_pair` runs its halves as one stacked
    ladder: when both moduli are on the Montgomery path.  Barrett, the
    oracle, keeps two calls."""
    return (_resolve_reduce(pack_p, reduce_impl) == "montgomery"
            and _resolve_reduce(pack_q, reduce_impl) == "montgomery")


def count_fixed_crt(pack_p: ModulusPack, pack_q: ModulusPack) -> None:
    """Count one launch of a fixed CRT pair in :data:`FIXED_CRT`."""
    FIXED_CRT["stacked" if fixed_pair_stacks(pack_p, pack_q)
              else "split"] += 1


@functools.lru_cache(maxsize=None)
def _pair_constants(m_p: int, m_q: int, L8: int) -> tuple:
    """(2, L8) moduli, (2,) ``mp``, (2, L8) ``r1`` and ``r2`` of two odd
    moduli at a common radix-256 width ``L8`` (R = 256^L8 for both)."""
    rows = [(m, *mg.mont_constants(m, L8)) for m in (m_p, m_q)]
    return (np.stack([_to8(m, L8) for m, *_ in rows]),
            np.asarray([mp for _, mp, _, _ in rows], np.int32),
            np.stack([_to8(r1, L8) for _, _, r1, _ in rows]),
            np.stack([_to8(r2, L8) for *_, r2 in rows]))


def modexp_fixed_pair(bp16: jax.Array, ep: int, pack_p: ModulusPack,
                      bq16: jax.Array, eq: int, pack_q: ModulusPack,
                      reduce_impl: str | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """(bp^ep mod p, bq^eq mod q) over two batches of the same size: the
    two CRT halves of one fixed exponentiation.

    Each half alone is a serial ladder whose time grows far slower than
    its batch (on a TPU v5e at 2048-bit keys it is flat up to ~180 rows),
    so where :func:`fixed_pair_stacks` holds the halves run as ONE ladder
    over the stacked ``(2B, L8)`` batch, which does the work of the two
    ladders plus at most the difference of their window counts.  Rows
    ``[0, B)`` carry p's modulus and schedule, rows ``[B, 2B)`` q's; the
    shorter schedule is front-padded with zero windows; both moduli are
    laid out at the wider one's limb width, with their Montgomery
    constants taken at it.  Radix conversion runs once each way for the
    stacked batch.  Elsewhere it is two :func:`modexp_fixed` calls.
    Exponents are key-constant, as for :func:`modexp_fixed` (the jit
    cache is keyed on them).
    """
    if not fixed_pair_stacks(pack_p, pack_q, reduce_impl):
        return (modexp_fixed(bp16, ep, pack_p, reduce_impl=reduce_impl),
                modexp_fixed(bq16, eq, pack_q, reduce_impl=reduce_impl))
    if bp16.shape[0] == 0:
        return (jnp.zeros((0, pack_p.L16), jnp.int32),
                jnp.zeros((0, pack_q.L16), jnp.int32))
    L8, L16 = max(pack_p.L8, pack_q.L8), max(pack_p.L16, pack_q.L16)
    m8, mp8, r1_8, r2_8 = _pair_constants(pack_p.m_int, pack_q.m_int, L8)
    windows = mg.exp_window_rows(ep, eq)

    def body(bp16, bq16):
        n = bp16.shape[0]       # the trace's batch, not the first call's

        def fit16(x):
            return jnp.pad(x, ((0, 0), (0, L16 - x.shape[1])))

        def rows(c):    # (2, ...) per-half constant -> (2n, ...) per row
            return jnp.repeat(jnp.asarray(c), n, axis=0)

        b8 = _to_radix8(jnp.concatenate([fit16(bp16), fit16(bq16)]), L8)
        out8 = mg.modexp2d_mont_fixed(
            b8, jnp.asarray(windows), rows(m8), rows(mp8), rows(r1_8),
            rows(r2_8), groups=(n, n))
        out16 = _to_radix16(out8, L16)
        return out16[:n, :pack_p.L16], out16[n:, :pack_q.L16]

    return _cached_jit((pack_p.m_int, pack_q.m_int, "modexp_fixed_pair",
                        ep, eq), body)(bp16, bq16)


# ---------------------------------------------------------------------------
# Multi-modulus "rows" ops — per-ROW moduli ride as operands.
#
# The serving layer fuses same-shaped Paillier launches ACROSS tenants:
# every tenant holds a different key, so the per-key jit closures above
# cannot be shared, but ``cm.barrett2d`` already broadcasts modulus
# material per row when ``m.shape[0] == B``.  These wrappers expose that
# directly: operands, exponents, moduli and Barrett mu all arrive as
# (B, ·) radix-256 limb arrays, and the jits below are keyed ONLY on
# shapes (via jax.jit's own cache) — one trace per (batch, limb-width)
# class, shared by every tenant key of that width.
#
# Two trace-count bounds (serving batch sizes vary per round):
#   * batches pad UP to a power of two (>= _ROWS_PAD_MIN), padding rows
#     repeat row 0 (a valid modulus row) so the ladder stays well-defined;
#   * exponent widths pad UP to a power of two bytes, zero-extended
#     (leading zero windows multiply by table[0] == 1 — exact).
# ---------------------------------------------------------------------------

_ROWS_PAD_MIN = 8


def _pow2_at_least(n: int, floor: int = 1) -> int:
    p = max(floor, 1)
    while p < n:
        p *= 2
    return p


def pack_rows(xs, L8: int) -> np.ndarray:
    """List of ints -> (B, L8) little-endian radix-256 int32 limbs."""
    with span("host:pack_rows"):
        out = np.zeros((len(xs), L8), np.int32)
        for i, x in enumerate(xs):
            b = int(x).to_bytes(L8, "little")    # OverflowError if too wide
            out[i] = np.frombuffer(b, dtype=np.uint8)
        return out


def unpack_rows(arr) -> list[int]:
    """(B, L) radix-256 limb array -> list of Python ints."""
    arr = bi.fetch(arr)
    with span("host:unpack_rows"):
        a = arr.astype(np.uint8)
        return [int.from_bytes(row.tobytes(), "little") for row in a]


@functools.lru_cache(maxsize=4096)
def _row_modulus_bytes(m: int, L8: int) -> tuple[bytes, bytes]:
    if (m >> (8 * (L8 - 1))) == 0:
        raise ValueError(
            f"modulus does not fill {L8} radix-256 limbs (Barrett needs "
            "the top limb populated); cluster by exact byte length")
    mu = (1 << (16 * L8)) // m
    return m.to_bytes(L8, "little"), mu.to_bytes(L8 + 1, "little")


def rows_modulus(ms, L8: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-row Barrett material: (B, L8) moduli + (B, L8+1) mu limbs.

    Every modulus must have EXACT byte length ``L8`` — same-width
    clustering is the caller's (the coalescer's) fusion invariant.
    """
    m8 = np.zeros((len(ms), L8), np.int32)
    mu8 = np.zeros((len(ms), L8 + 1), np.int32)
    for i, m in enumerate(ms):
        mb, mub = _row_modulus_bytes(int(m), L8)
        m8[i] = np.frombuffer(mb, dtype=np.uint8)
        mu8[i] = np.frombuffer(mub, dtype=np.uint8)
    return m8, mu8


def _pad_rows(rows_arrays: list, bsz: int) -> tuple[list, int]:
    """Pad each (B, ·) array to the next power-of-two batch by repeating
    its row 0 (a valid modulus/operand row — padded results are exact
    garbage, sliced off by the caller)."""
    padded_b = _pow2_at_least(bsz, _ROWS_PAD_MIN)
    if padded_b == bsz:
        return rows_arrays, bsz
    out = []
    for a in rows_arrays:
        pad = np.broadcast_to(a[0:1], (padded_b - bsz,) + a.shape[1:])
        out.append(np.concatenate([a, pad], axis=0))
    return out, bsz


@jax.jit
def _mulmod_rows8(a8, b8, m8, mu8):
    return cm.mulmod2d(a8, b8, m8, mu8)


_MODEXP_ROWS8 = {
    "binary": jax.jit(cm.modexp2d),
    "win4": jax.jit(cm.modexp2d_win4),
}


def mulmod_rows(a, b, m8, mu8) -> np.ndarray:
    """(a*b) mod m, row-wise, per-row moduli; all args (B, ·) int32."""
    (a, b, m8, mu8), bsz = _pad_rows([np.asarray(a), np.asarray(b),
                                      np.asarray(m8), np.asarray(mu8)],
                                     a.shape[0])
    return bi.fetch(_mulmod_rows8(a, b, m8, mu8))[:bsz]


def modexp_rows(base, exp, m8, mu8, method: str | None = None) -> np.ndarray:
    """base^exp mod m, row-wise, per-row moduli AND exponents.

    ``exp`` is (B, Le8) radix-256; Le8 pads to a power of two bytes so
    the ladder trace is shared across nearby exponent widths (radix-8
    widths always satisfy win4's bits%4==0 requirement).
    """
    method = method or MODEXP_METHOD
    if method not in _MODEXP_ROWS8:
        raise ValueError(f"unknown modexp method {method!r}; expected one "
                         f"of {tuple(_MODEXP_ROWS8)}")
    exp = np.asarray(exp)
    le8 = _pow2_at_least(exp.shape[1])
    if le8 != exp.shape[1]:
        exp = np.pad(exp, ((0, 0), (0, le8 - exp.shape[1])))
    (base, exp, m8, mu8), bsz = _pad_rows(
        [np.asarray(base), exp, np.asarray(m8), np.asarray(mu8)],
        base.shape[0])
    return bi.fetch(_MODEXP_ROWS8[method](base, exp, m8, mu8))[:bsz]


@jax.jit
def _prod_rows8(x, m8, mu8):
    # x (R, N, L): reduce prod over axis 1 mod the per-row modulus, by
    # log-depth pairwise halving (exact ring product — order-free).
    n = x.shape[1]
    while n > 1:
        h = n // 2
        rr, _, ll = x.shape
        a = x[:, :h].reshape(rr * h, ll)
        b = x[:, h:2 * h].reshape(rr * h, ll)
        mm = jnp.repeat(m8, h, axis=0)
        mmu = jnp.repeat(mu8, h, axis=0)
        prod = cm.mulmod2d(a, b, mm, mmu).reshape(rr, h, ll)
        if n % 2:
            x = jnp.concatenate([prod, x[:, n - 1:n]], axis=1)
            n = h + 1
        else:
            x = prod
            n = h
    return x[:, 0]


def prod_rows(x, m8, mu8) -> np.ndarray:
    """Row-wise modular product over axis 1: (R, N, L8) -> (R, L8)."""
    (x, m8, mu8), rsz = _pad_rows(
        [np.asarray(x), np.asarray(m8), np.asarray(mu8)], x.shape[0])
    return bi.fetch(_prod_rows8(x, m8, mu8))[:rsz]

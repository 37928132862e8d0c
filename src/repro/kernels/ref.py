"""The jnp ladders behind ``kernels/ops.py``, and the paper's FFT multiply.

1. ``mulmod_ref`` / ``modexp_ref`` — the radix-256 primitives from
   ``kernels/common.py`` and ``kernels/montgomery.py``, chosen by ladder
   and reduction, as ordinary traced jnp; exercised against
   ``core.bigint`` (radix-2^16 / int64) and Python ints in tests.

2. ``fft_mul_ref`` — the paper's own FFT polynomial multiplication
   (Algorithm 2 lines 8-12) over complex doubles. Kept as documentation of
   the GPU algorithm; exact only while products fit the float53 mantissa
   (small L / small radix), which is precisely why the TPU port replaces it
   with the exact integer convolution (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as cm
from . import montgomery as mg


def mulmod_ref(a8: jax.Array, b8: jax.Array, m8: jax.Array, mu8: jax.Array) -> jax.Array:
    """(B, L) x (B, L) mod m -> (B, L), radix-256 int32 limbs."""
    return cm.mulmod2d(a8, b8, m8, mu8)


def modexp_ref(base8: jax.Array, exp8: jax.Array, m8: jax.Array,
               mu8: jax.Array, method: str = "binary",
               reduce_impl: str = "barrett",
               r1_8: jax.Array | None = None,
               r2_8: jax.Array | None = None,
               mp: int | None = None) -> jax.Array:
    """ModExp oracle, radix-256 int32 limbs (binary or win4 ladder).

    ``reduce_impl="montgomery"`` runs the same ladder schedule over REDC
    (``kernels/montgomery.py``) — the fast path; ``"barrett"`` is the
    oracle. Unknown names raise instead of silently falling back.
    """
    if method not in ("binary", "win4"):
        raise ValueError(f"unknown modexp method {method!r}; "
                         "expected 'binary' or 'win4'")
    if reduce_impl == "montgomery":
        if r1_8 is None or r2_8 is None or mp is None:
            raise ValueError("montgomery reduce_impl needs r1_8/r2_8/mp")
        if method == "win4":
            return mg.modexp2d_mont_win4(base8, exp8, m8, mp, r1_8, r2_8)
        return mg.modexp2d_mont(base8, exp8, m8, mp, r1_8, r2_8)
    if reduce_impl != "barrett":
        raise ValueError(f"unknown reduce_impl {reduce_impl!r}; "
                         "expected 'barrett' or 'montgomery'")
    if method == "win4":
        return cm.modexp2d_win4(base8, exp8, m8, mu8)
    return cm.modexp2d(base8, exp8, m8, mu8)


def fft_mul_ref(a8: jax.Array, b8: jax.Array) -> jax.Array:
    """The paper's FFT big-int multiply (complex double), radix-256 input.

    Exact only when ``L * 255^2 < 2^53 / (2L)`` headroom holds and FFT
    round-off stays below 0.5 ulp of a coefficient — guaranteed for the
    L <= 512 sizes used in tests; documents eq. (44)-(46).
    """
    bsz, la = a8.shape
    lb = b8.shape[1]
    n = 1
    while n < la + lb:
        n *= 2
    fa = jnp.fft.rfft(a8.astype(jnp.float64), n=n, axis=-1)
    fb = jnp.fft.rfft(b8.astype(jnp.float64), n=n, axis=-1)
    coeff = jnp.fft.irfft(fa * fb, n=n, axis=-1)
    coeff = jnp.round(coeff).astype(jnp.int64)[:, :la + lb]
    # exact carry in int64 then back to radix-256 int32
    def step(c, x):
        t = x + c
        return t >> 8, (t & 0xFF).astype(jnp.int32)
    _, limbs = jax.lax.scan(step, jnp.zeros((bsz,), jnp.int64),
                            jnp.moveaxis(coeff, -1, 0))
    return jnp.moveaxis(limbs, 0, -1)

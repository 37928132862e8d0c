"""Quantization Gamma_1 / Gamma_2 and Theorem-1 dequantization (paper §III-A).

The protocol fixes a common clipping range [zmin, zmax] up-front (Algorithm 1
line 3), so negative reals map to nonnegative integers Paillier can encrypt,
without a two's-complement sign space:

    Gamma_2(u) = round( Delta   (u - zmin) / (zmax - zmin)   )   in {0..Delta}
    Gamma_1(u) = round( Delta^2 (u - zmin) / (zmax - zmin)^2 )   in {0..Delta^2/s}

One homomorphic multiply-add chain  R = G1(u3) + G2(B) @ (G2(u1) + G2(u2))
dequantizes in closed form. NOTE (documented deviation): the paper's eq. (21)
drops the all-ones structure of the matrix offset — with
Gamma_2(B) = Delta (B - zmin * E)/s and E the all-ones matrix,

    E @ w = (sum w) * 1     and     E @ 1 = N * 1,

so the exact correction (validated numerically in tests/test_quantization.py) is

    u3 + B(u1+u2) = R s^2/Delta^2
                    + zmin * (1 + 2 * B@1 + sum(u1+u2)) - 2 N zmin^2 .

The paper's printed form ``(2 B 1 + u1 + u2 + 1) zmin - 2 zmin^2`` recovers
ours only when N = 1; we implement the N-dimensional-correct version (the
master knows B@1 row sums from the Initialization phase and u1+u2 = z - v).

int64 guard: the integer chain value is bounded by ~2 N Delta^2; keep
Delta <= sqrt(2^62 / (2 N)) for the in-JAX path (DEFAULT_DELTA below), and use
the Python-int gold path for the paper's Delta = 1e15 regime, where Gamma_1
codes are Python ints too.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

DEFAULT_DELTA = 1.0e6


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Protocol-level quantization parameters (shared by master and edges)."""
    delta: float = DEFAULT_DELTA
    zmin: float = -16.0
    zmax: float = 16.0

    @property
    def span(self) -> float:
        return self.zmax - self.zmin

    def int64_safe(self, n_dim: int) -> bool:
        """True if the Theorem-1 integer chain fits int64 for N=n_dim."""
        return 2.0 * n_dim * self.delta ** 2 < 2.0 ** 62

    def plaintext_bits(self, n_dim: int) -> int:
        """Upper bound on the homomorphic-result bit length (Remark 2)."""
        return int(np.ceil(np.log2(2.0 * n_dim * self.delta ** 2 + 1)))


def gamma2(u, spec: QuantSpec):
    """Gamma_2: reals -> {0..Delta} (eq. 14b-d), int64."""
    q = jnp.round(spec.delta * (jnp.asarray(u, jnp.float64) - spec.zmin) / spec.span)
    return q.astype(jnp.int64)


def gamma1(u, spec: QuantSpec):
    """Gamma_1: reals -> {0..Delta^2/s} (eq. 14a).

    int64 while the code range Delta^2/s fits it; above that (the paper's
    Delta = 1e15 gives ~3e28) an object array of exact Python ints, since
    a cast to int64 would saturate every code."""
    q = jnp.round(spec.delta ** 2 * (jnp.asarray(u, jnp.float64) - spec.zmin)
                  / spec.span ** 2)
    if spec.delta ** 2 / spec.span < 2.0 ** 62:
        return q.astype(jnp.int64)
    q = np.asarray(q)
    return np.array([int(v) for v in q.reshape(-1)],
                    dtype=object).reshape(q.shape)


def inv_gamma2(q, spec: QuantSpec):
    return jnp.asarray(q, jnp.float64) * spec.span / spec.delta + spec.zmin


def inv_gamma1(q, spec: QuantSpec):
    return jnp.asarray(q, jnp.float64) * spec.span ** 2 / spec.delta ** 2 + spec.zmin


def chain(u3, B, u1, u2, spec: QuantSpec):
    """The quantized integer chain R = G1(u3) + G2(B) @ (G2(u1) + G2(u2)).

    This is exactly the plaintext that the homomorphic evaluation (eq. 18)
    produces under the ciphertext; computing it directly gives the
    "functional simulation" path used at large scale (bit-identical to
    decrypting the real ciphertexts, tested in tests/test_protocol.py).
    """
    w = gamma2(u1, spec) + gamma2(u2, spec)
    return gamma1(u3, spec) + gamma2(B, spec) @ w


def dequantize_theorem1(R, B_row_sums, w_sum, n_dim: int, spec: QuantSpec):
    """Recover  u3 + B(u1+u2)  from the integer chain value R (Theorem 1).

    ``B_row_sums``: real row sums B @ 1 (known to the master from init phase).
    ``w_sum``: scalar sum of the real (u1 + u2) vector.
    """
    s = spec.span
    R = jnp.asarray(R, jnp.float64)
    return (R * s ** 2 / spec.delta ** 2
            + spec.zmin * (1.0 + 2.0 * jnp.asarray(B_row_sums, jnp.float64) + w_sum)
            - 2.0 * n_dim * spec.zmin ** 2)


def gamma2_saturation(q, spec: QuantSpec) -> tuple[int, int]:
    """Encode-clipping counters for a Gamma_2 code vector: ``(clipped,
    total)`` where clipped counts entries outside the code range
    ``[0, Delta]`` — i.e. inputs that violated the protocol's fixed
    ``[zmin, zmax]`` clipping contract (Algorithm 1 line 3).  Gamma_2
    does NOT clamp, so an out-of-range input silently produces an
    off-range code and a wrong Theorem-1 dequantization; the health
    monitor (``repro.obs.health``) watches these counters live."""
    q = np.asarray(q)
    clipped = int(np.count_nonzero((q < 0) | (q > spec.delta)))
    return clipped, int(q.size)


def gamma1_saturation(q, spec: QuantSpec) -> tuple[int, int]:
    """Same counters for a Gamma_1 code vector, whose code range is
    ``[0, Delta^2 / span]``."""
    q = np.asarray(q)
    hi = spec.delta ** 2 / spec.span
    clipped = int(np.count_nonzero((q < 0) | (q > hi)))
    return clipped, int(q.size)


def quantize_tensor(u, spec: QuantSpec):
    """Plain per-tensor Gamma_2 with its own min/max (eq. 14 as printed);
    used by the gradient-compression path, returns (q, tmin, tmax)."""
    u = jnp.asarray(u, jnp.float64)
    tmin, tmax = jnp.min(u), jnp.max(u)
    span = jnp.maximum(tmax - tmin, 1e-30)
    q = jnp.round(spec.delta * (u - tmin) / span).astype(jnp.int64)
    return q, tmin, tmax


def dequantize_tensor(q, tmin, tmax, spec: QuantSpec):
    span = jnp.maximum(tmax - tmin, 1e-30)
    return jnp.asarray(q, jnp.float64) * span / spec.delta + tmin

"""Pallas TPU kernel: fused big-integer modular multiply (conv+carry+Barrett).

One pallas_call computes ``(a * b) mod m`` for a batch of big integers held
as radix-256 int32 limb rows. The whole chain — limb convolution, carry
propagation, Barrett reduction (two extra convolutions) — stays resident in
VMEM per block, mirroring the paper's shared-memory strategy (§IV-A) and the
GME "keep ciphertexts in cache" insight it cites.

Block layout: grid over the ciphertext batch; each program instance owns a
``(block_b, L)`` tile of a/b/out plus the broadcast modulus row. VMEM use is
~10 int32 buffers of (block_b, 2L+2): for block_b=128, L=512 (4096-bit n^2)
that is ~5.5 MB — comfortably under the ~16 MB v5e VMEM budget.

Layout: little-endian radix-256 (2^8) int32 limbs kernel-side; the public
API (``kernels/ops.py``, ``core/bigint.py``) uses radix-2^16 limbs and
converts at the boundary. This is a building block of the batched fast path
(no exponentiation here — see ``kernels/modexp.py`` for the 4-bit-window
ladder); its scalar reference is plain Python-int arithmetic in
``core/paillier.py`` and the jnp oracle in ``kernels/ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import common as cm


def _mulmod_kernel(a_ref, b_ref, m_ref, mu_ref, o_ref):
    a = a_ref[...]
    b = b_ref[...]
    m = m_ref[...]
    mu = mu_ref[...]
    o_ref[...] = cm.mulmod2d(a, b, m, mu)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mulmod_pallas(a8: jax.Array, b8: jax.Array, m8: jax.Array, mu8: jax.Array,
                  block_b: int = 128, interpret: bool = True) -> jax.Array:
    """(B, L) x (B, L) mod m -> (B, L). Batch must be a block_b multiple.

    ``m8``: (1, L); ``mu8``: (1, Lmu >= L+1) = floor(256^{2L}/m).
    ``interpret=True`` validates on CPU.  ``interpret=False`` does not
    lower for the TPU yet (the limb helpers' ``dynamic_slice``).
    """
    bsz, L = a8.shape
    assert bsz % block_b == 0, "pad batch to a block multiple (ops.py does)"
    grid = (bsz // block_b,)
    return pl.pallas_call(
        _mulmod_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, L), lambda i: (i, 0)),
            pl.BlockSpec((block_b, L), lambda i: (i, 0)),
            pl.BlockSpec((1, m8.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, mu8.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, L), jnp.int32),
        interpret=interpret,
    )(a8, b8, m8, mu8)

"""Batched CRT fast path for the gold (Python-int) Paillier pipeline.

The scalar gold path (``core.paillier``) computes one Python-int ``pow`` per
scalar — the ROADMAP-named blocker for larger-N topology sweeps.  This module
removes every per-element ``pow`` from the protocol hot path: a whole batch
of ModExps is lowered onto the radix-2^16 limb kernels (``kernels/ops.py``,
4-bit fixed-window exponentiation by default) in the paper's two CRT
half-width spaces Z_{p^2} x Z_{q^2} (eqs. 35-40), and the eq. (38)
recombination is done ONCE per batch in limb space
(:func:`paillier_vec.crt_combine_batch`).

Unlike ``core.paillier_vec`` — whose ciphertexts live as limb arrays inside
the JAX graph and whose plaintexts must fit int64 — this module keeps the
gold representation (Python ints in, Python ints out, arbitrary plaintext
size < n), so :class:`~repro.core.protocol.GoldBox`, ``secure_agg`` and the
runtime's coalescing queue can adopt the batched kernels without changing
their ciphertext wire format.  Remaining per-element host work is limited to
cheap ring ops (``%``, ``*``, exact division) and the int<->limb conversion;
no ``pow`` survives.

Since the limb-resident pipeline (:mod:`core.cipher_tensor`) the int
boundary moved from the op to the phase: ``enc_ct``/``add_ct``/
``pow_c_ct``/``matvec_many``/``dec_vec`` consume and produce
:class:`~repro.core.cipher_tensor.CipherTensor` batches whose limbs never
leave the device between protocol ops — ``from_ints``/``to_ints`` runs once
where plaintexts enter or leave, not per homomorphic op.  The int-in/
int-out functions remain as thin materializing wrappers.

Bit-exactness: every function here returns exactly what the scalar gold
functions return for the same inputs and the same ``random.Random`` stream
(property-tested in tests/test_paillier_batch.py across key sizes, and
end-to-end across every protocol arm in tests/test_conformance.py).

Preconditions shared by all batched ModExps: bases must be units mod n
(ciphertexts and blinding factors are, by construction) — required for the
half-space exponent reduction ``e mod phi(p^2)`` to be exact.  Negative
exponents are handled exactly as CPython's ``pow``: the base is inverted
mod n^2 host-side (extended gcd, not a ModExp) and the ladder runs on
``-e`` — so quantized values that dip below the clipping range keep
producing bit-identical results to the scalar loops.
"""
from __future__ import annotations

import dataclasses
import functools
import random
import time
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import bigint as bi
from . import paillier as gold
from . import paillier_vec as pv
from .cipher_tensor import CipherTensor
from ..obs.trace import span
from ..kernels import ops

# Below this batch size the per-launch overhead dominates and callers keep
# the scalar gold path (the protocol boxes apply this threshold).
BATCH_MIN = 8


@dataclasses.dataclass(frozen=True, eq=False)
class BatchKey:
    """Gold key + the limb-packed material the kernels need."""
    key: gold.PaillierKey
    vk: pv.VecKey


@functools.lru_cache(maxsize=None)
def make_batch_key(key: gold.PaillierKey) -> BatchKey:
    """Limb-pack ``key`` (cached: repeated boxes share one kernel cache).

    Unbounded on purpose: ``paillier_vec._JIT_CACHE`` keys its compiled
    closures by ``id(vk)``, so evicting a BatchKey could free its VecKey
    and let a later allocation reuse the address — silently serving jitted
    kernels closed over the WRONG key's constants.  The jit cache already
    pins per-key executables for the process lifetime, so pinning the few
    KB of VecKey constants alongside adds nothing asymptotically.
    """
    return BatchKey(key=key, vk=pv.make_vec_key(key))


def rand_r_vec(key: gold.PaillierKey, count: int,
               rng: random.Random) -> list[int]:
    """``count`` blinding units r in Z*_n — same stream as repeated
    :func:`gold.rand_r`, so batched and scalar encryption draw identical r
    sequences (this is what makes the fast path ciphertext-identical)."""
    return [gold.rand_r(key, rng) for _ in range(count)]


# ---------------------------------------------------------------------------
# Core primitive: batched base^e mod n^2 via the CRT half spaces
# ---------------------------------------------------------------------------

# Ladder rows launched in this process, cumulative: ``rows`` (values,
# or the matvec's exponent rows) and ``one_chip`` (those of a launch that
# ran on one chip).  The runtime's coalescing queue keeps their
# differences per protocol round.
LAYOUT = {"rows": 0, "one_chip": 0}


def _shard_batch(*arrays, count: bool = True):
    """Lay ``(B, ...)`` operand arrays across the local ``batch`` device mesh.

    Single-device hosts (the common container) get the arrays back
    untouched.  On multi-chip hosts every limb kernel is batch-elementwise,
    so placing the leading axis on :func:`repro.launch.mesh.kernel_mesh`
    BEFORE the jitted CRT body runs lets XLA partition the whole ladder —
    K>=64 topologies use every chip with zero cross-device traffic until
    the caller gathers.  Batches not divisible by the device count stay
    unsharded (the jit still runs, just unpartitioned).

    With ``count`` the first array's rows are one ladder launch's rows in
    :data:`LAYOUT`, on one chip unless that array was spread.
    """
    from ..launch import mesh as mesh_mod
    m = mesh_mod.kernel_mesh()
    if count:
        rows = int(np.shape(arrays[0])[0])
        LAYOUT["rows"] += rows
        if m is None or rows % int(m.devices.size):
            LAYOUT["one_chip"] += rows
    if m is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        ndev = int(m.devices.size)
        sh = NamedSharding(m, PartitionSpec("batch"))
        arrays = tuple(
            jax.device_put(x, sh)
            if (getattr(x, "ndim", 0) or np.ndim(x)) >= 1
            and np.shape(x)[0] and np.shape(x)[0] % ndev == 0 else x
            for x in (jnp.asarray(a) for a in arrays))
    return arrays if len(arrays) != 1 else arrays[0]


def _norm_exps(exps, batch: int) -> list[int]:
    if isinstance(exps, (int, np.integer)):
        exps = [int(exps)] * batch
    else:
        exps = [int(e) for e in exps]
    if len(exps) != batch:
        raise ValueError(f"{len(exps)} exponents for a batch of {batch}")
    return exps


def modexp_crt_limbs(bk: BatchKey, bases: Sequence[int], exps,
                     fixed: bool = False) -> jnp.ndarray:
    """[b^e mod n^2] as (B, L16(n^2)) limbs; ``exps`` scalar or per-element.

    Per-element exponents run two half-space ModExp ladders, one after the
    other, with their exponent limbs sized to the batch maximum AFTER the
    phi reduction, so small exponents (quantized Gamma_2 values, ~20 bits)
    pay for ~2 limbs, not the full key width.

    ``fixed=True`` opts a SCALAR exponent into the host-known fixed-window
    ladder (``ops.modexp_fixed_pair``): the 4-bit schedules are baked into
    the trace, dropping the per-window oblivious table select, and on the
    Montgomery path both halves run stacked as ONE ladder over 2B rows
    (each launch is counted in ``ops.FIXED_CRT``).  Only pass it for
    KEY-CONSTANT exponents (enc's ``n``, dec's ``lam``) — every distinct
    exponent value compiles its own executable.  Per-element exponent
    lists ignore the flag.
    """
    key, vk = bk.key, bk.vk
    B = len(bases)
    bases = [int(b) for b in bases]
    scalar_e = int(exps) if isinstance(exps, (int, np.integer)) else None
    exps = _norm_exps(exps, B)
    for i, e in enumerate(exps):
        if e < 0:   # pow()-compatible: invert the base (egcd), negate e
            bases[i] = pow(bases[i], -1, key.n2)
            exps[i] = -e
    ep = [e % key.phi_p2 for e in exps]
    eq = [e % key.phi_q2 for e in exps]
    bp = bi.from_ints([b % key.p2 for b in bases], vk.pack_p2.L16)
    bq = bi.from_ints([b % key.q2 for b in bases], vk.pack_q2.L16)

    if fixed and scalar_e is not None:
        ep_s, eq_s = abs(scalar_e) % key.phi_p2, abs(scalar_e) % key.phi_q2

        def fixed_body(bp, bq):
            xp, xq = ops.modexp_fixed_pair(bp, ep_s, vk.pack_p2,
                                           bq, eq_s, vk.pack_q2)
            return pv.crt_combine_batch(vk, xp, xq)

        # the reduce impl resolves when the body TRACES, so it is part of
        # the cache identity — else flipping REPRO_REDUCE_IMPL mid-process
        # would silently replay the other impl's executable
        fn = pv._cached_jit(vk, ("crt_modexp_fixed", ep_s, eq_s,
                                 ops.active_reduce_impl()), fixed_body)
        ops.count_fixed_crt(vk.pack_p2, vk.pack_q2)
        return fn(*_shard_batch(bp, bq))

    le = max(1, max(bi.n_limbs_for(e) for e in ep + eq))

    def body(bp, ep, bq, eq):
        # the whole half-space ladder + eq. (38) recombination compiles to
        # ONE executable per (batch, exponent-width) shape — running the
        # combine eagerly costs ~10x in per-op dispatch
        xp = ops.modexp(bp, ep, vk.pack_p2)
        xq = ops.modexp(bq, eq, vk.pack_q2)
        return pv.crt_combine_batch(vk, xp, xq)

    fn = pv._cached_jit(
        vk, ("crt_modexp", ops.active_reduce_impl()), body)
    return fn(*_shard_batch(bp, bi.from_ints(ep, le),
                            bq, bi.from_ints(eq, le)))


def modexp_crt_limbs_in(bk: BatchKey, base_limbs: jnp.ndarray, exps,
                        fixed: bool = False) -> jnp.ndarray:
    """:func:`modexp_crt_limbs` for bases already resident in limb form.

    ``base_limbs`` is a ``(B, L16(n^2))`` array (a :class:`CipherTensor`'s
    payload); the reduction into the two half spaces happens IN-GRAPH
    (``paillier_vec._reduce_into``), so no host int<->limb conversion runs
    at all.  Exponents must be nonnegative (negative exponents need a
    host-side base inversion — callers materialize for that rare path).
    ``fixed`` as in :func:`modexp_crt_limbs` (scalar exponents only).
    """
    vk = bk.vk
    key = bk.key
    B = int(base_limbs.shape[0])
    scalar_e = int(exps) if isinstance(exps, (int, np.integer)) else None
    exps = _norm_exps(exps, B)
    if any(e < 0 for e in exps):
        raise ValueError("limb-resident ModExp needs nonnegative exponents")

    if fixed and scalar_e is not None:
        ep_s, eq_s = scalar_e % key.phi_p2, scalar_e % key.phi_q2

        def fixed_body(c):
            cp = pv._reduce_into(c, vk.pack_p2)
            cq = pv._reduce_into(c, vk.pack_q2)
            xp, xq = ops.modexp_fixed_pair(cp, ep_s, vk.pack_p2,
                                           cq, eq_s, vk.pack_q2)
            return pv.crt_combine_batch(vk, xp, xq)

        fn = pv._cached_jit(
            vk, ("crt_modexp_limbs_fixed", ep_s, eq_s,
                 ops.active_reduce_impl()), fixed_body)
        ops.count_fixed_crt(vk.pack_p2, vk.pack_q2)
        return fn(_shard_batch(base_limbs))

    ep = [e % key.phi_p2 for e in exps]
    eq = [e % key.phi_q2 for e in exps]
    le = max(1, max(bi.n_limbs_for(e) for e in ep + eq))

    def body(c, ep, eq):
        cp = pv._reduce_into(c, vk.pack_p2)
        cq = pv._reduce_into(c, vk.pack_q2)
        xp = ops.modexp(cp, ep, vk.pack_p2)
        xq = ops.modexp(cq, eq, vk.pack_q2)
        return pv.crt_combine_batch(vk, xp, xq)

    fn = pv._cached_jit(
        vk, ("crt_modexp_limbs", ops.active_reduce_impl()), body)
    return fn(*_shard_batch(base_limbs, bi.from_ints(ep, le),
                            bi.from_ints(eq, le)))


def modexp_crt_vec(bk: BatchKey, bases: Sequence[int], exps,
                   fixed: bool = False) -> list[int]:
    """Int-in/int-out batched ``pow(b, e, n^2)`` (see modexp_crt_limbs)."""
    if not len(bases):
        return []
    return bi.to_ints(modexp_crt_limbs(bk, bases, exps, fixed=fixed))


def pow_c_vec(bk: BatchKey, cs, ks,
              fixed: bool = False) -> list[int]:
    """Batched plaintext-constant multiply ⊗: [c^k mod n^2] elementwise.

    Bit-exact vs. scalar :func:`gold.c_mul_const` / ``c_mul_const_crt``
    (requires the private key holder, as all CRT-decomposed ops do).
    ``cs`` may be a :class:`CipherTensor` — nonnegative exponents then run
    limb-in without materializing the batch.  ``fixed`` opts a scalar ``ks``
    into the host-known-exponent ladder; OFF by default because per-round
    varying scalars would compile one executable per value.
    """
    if isinstance(cs, CipherTensor):
        return pow_c_ct(bk, cs, ks, fixed=fixed).to_ints()
    return modexp_crt_vec(bk, cs, ks, fixed=fixed)


def pow_c_ct(bk: BatchKey, cs: CipherTensor, ks,
             fixed: bool = False) -> CipherTensor:
    """Limb-in/limb-out ⊗ over a resident ciphertext batch.

    ``fixed`` as in :func:`pow_c_vec` (scalar ``ks``, stable across calls).
    """
    B = len(cs)
    exps = _norm_exps(ks, B)
    if any(e < 0 for e in exps):   # host base inversion: materialize once
        return CipherTensor(
            bk, modexp_crt_limbs(bk, cs.to_ints(), ks, fixed=fixed))
    return CipherTensor(bk, modexp_crt_limbs_in(bk, cs.limbs, ks, fixed=fixed))


# ---------------------------------------------------------------------------
# Encryption / decryption / homomorphic matvec
# ---------------------------------------------------------------------------

def _enc_ct_impl(bk: BatchKey, ms: list[int], rs: list[int]) -> CipherTensor:
    """g=n+1 encryption entirely in limb space: c = (1 + m n) * r^n mod n^2.

    r^n runs through the CRT half spaces; the (1 + m n) affine lift and the
    final blinding multiply stay in-graph, so the ciphertexts are BORN
    limb-resident (no host ring multiplies, no to_ints)."""
    key, vk = bk.key, bk.vk
    Ln, L2 = vk.pack_n.L16, vk.pack_n2.L16
    rn = modexp_crt_limbs(bk, rs, key.n, fixed=True)
    m_limbs = bi.from_ints([m % key.n for m in ms], Ln)

    def body(m_limbs, rn):
        n_row = jnp.broadcast_to(jnp.asarray(vk.n_limbs),
                                 (m_limbs.shape[0], L2))
        gm = bi.mul(m_limbs, n_row, out_limbs=L2)      # m*n < n^2, exact
        gm = bi.add(gm, jnp.zeros_like(gm).at[..., 0].set(1))  # 1 + m n
        return ops.mulmod(gm, rn, vk.pack_n2)

    fn = pv._cached_jit(vk, "enc_gold", body)
    return CipherTensor(bk, fn(jnp.asarray(m_limbs), rn))


def _no_backend(backend) -> None:
    if backend is not None:
        raise ValueError(f"unknown kernel backend {backend!r}; the limb "
                         "kernels have one")


def enc_ct(bk: BatchKey, ms, rng: random.Random,
           backend: None = None) -> CipherTensor:
    """Batched g=n+1 encryption, limb-out: one launch for all blindings.

    Draws r exactly like the scalar loop (same rng stream); the resulting
    :class:`CipherTensor` materializes to ints bit-identical to
    ``[gold.encrypt_crt(key, m, rand_r(key, rng)) for m in ms]`` —
    including for plaintexts outside [0, n), which ``encrypt_crt`` (unlike
    ``encrypt``) wraps mod n via (n+1)^m = 1 + (m mod n) n  (mod n^2).
    ``backend`` takes only ``None``; the benchmark's fault fakes pass it.
    """
    _no_backend(backend)
    key = bk.key
    if key.g != key.n + 1:
        raise NotImplementedError("batched path uses the g = n+1 fast path")
    ms = [int(m) for m in np.asarray(ms, dtype=object).reshape(-1)]
    if not ms:
        return CipherTensor(bk, jnp.zeros((0, bk.vk.pack_n2.L16), jnp.int32),
                            ints=[])
    rs = rand_r_vec(key, len(ms), rng)
    return _enc_ct_impl(bk, ms, rs)


def enc_vec(bk: BatchKey, ms, rng: random.Random) -> list[int]:
    """Int-out form of :func:`enc_ct` (same rng stream, same ciphertexts)."""
    return enc_ct(bk, ms, rng).to_ints()


def add_ct(bk: BatchKey, c1: CipherTensor, c2: CipherTensor) -> CipherTensor:
    """⊕ on resident batches: elementwise ciphertext product mod n^2.

    One batched Barrett mulmod launch; bit-identical to the per-element
    ``(a * b) % n2`` host loop it replaces."""
    return CipherTensor(bk, ops.mulmod(c1.limbs, c2.limbs, bk.vk.pack_n2))


def rn_pool_limbs(bk: BatchKey, rs: Sequence[int]) -> jnp.ndarray:
    """Blinding pool r -> r^n mod n^2 as (B, L16(n^2)) limbs.

    The batched replacement for :func:`gold.make_r_pool` on the ``vec``
    cipher path (which needs the pool in limb form anyway).
    """
    return modexp_crt_limbs(bk, rs, bk.key.n, fixed=True)


def dec_vec(bk: BatchKey, cs, backend: None = None) -> list[int]:
    """Batched decryption: c^lam for the whole batch in one CRT launch.

    The L(x) = (x-1)/n exact division and the mu multiply stay on the host
    (one divmod + one mulmod per element — no pow).  Bit-identical to
    ``[gold.decrypt_crt(key, c) for c in cs]``.  Limb-in: a
    :class:`CipherTensor` decrypts straight off its resident limbs (the
    bases reduce into the half spaces in-graph, no ciphertext to_ints).
    ``backend`` takes only ``None``; the benchmark's fault fakes pass it.
    """
    _no_backend(backend)
    key = bk.key
    if isinstance(cs, CipherTensor):
        if not len(cs):
            return []
        x = bi.to_ints(modexp_crt_limbs_in(bk, cs.limbs, key.lam, fixed=True))
    else:
        x = modexp_crt_vec(bk, cs, key.lam, fixed=True)
    with span("host:dec_finish"):
        return [(xi - 1) // key.n * key.mu % key.n for xi in x]


def matvec_many(bk: BatchKey, Ks, cs_list: Sequence) -> list:
    """Fused homomorphic matvecs: out[b][i] = prod_j cs[b][j]^{Ks[b,i,j]}.

    All B*(M, N) exponent blocks flatten into ONE batched CRT ModExp launch
    (the coalesced form used by the runtime's queue), then one shared
    log-depth mulmod tree reduces the rows mod n^2.  With B=1 this is the
    gold box's per-edge eq. (13) matvec.

    Limb-resident in, limb-resident out: when every entry of ``cs_list``
    is a :class:`CipherTensor`, the bases reduce into the CRT half spaces
    in-graph (zero host conversions) and each output row comes back as a
    CipherTensor, so chained protocol ops never touch Python ints.  Int
    sequences keep the int-in/int-out contract (B*N host conversions, one
    per ciphertext).  Negative exponents need per-element host base
    inversion and force the materialized general path either way.
    """
    key, vk = bk.key, bk.vk
    Ks = np.asarray(Ks, dtype=object)
    B, M, N = Ks.shape
    if len(cs_list) != B:
        raise ValueError(f"{len(cs_list)} ciphertext vectors for B={B}")
    if B == 0:
        return []          # empty fan-in: nothing to launch
    ct_in = all(isinstance(c, CipherTensor) for c in cs_list)
    for b, row in enumerate(cs_list):
        if len(row) != N:
            raise ValueError(f"ciphertext vector {b} has {len(row)} != {N}")
    exps = _norm_exps(Ks.reshape(-1), B * M * N)
    L2 = vk.pack_n2.L16
    if any(e < 0 for e in exps):
        rows = [int(c) for row in cs_list for c in row]  # materializes CTs
        bases = [rows[b * N + j] for b in range(B)
                 for _ in range(M) for j in range(N)]
        powed = modexp_crt_limbs(bk, bases, exps)
    else:
        ep = [e % key.phi_p2 for e in exps]
        eq = [e % key.phi_q2 for e in exps]
        le = max(1, max(bi.n_limbs_for(e) for e in ep + eq))
        ep_l, eq_l = _shard_batch(bi.from_ints(ep, le),
                                  bi.from_ints(eq, le))

        def bcast(x):
            x = x.reshape(-1, 1, N, x.shape[-1])
            x = jnp.broadcast_to(x, (x.shape[0], M, N, x.shape[-1]))
            return x.reshape(-1, x.shape[-1])

        if ct_in:
            c_limbs = _shard_batch(
                jnp.concatenate([c.limbs for c in cs_list], axis=0),
                count=False)

            def powed_ct_body(c, ep, eq):
                cp = pv._reduce_into(c, vk.pack_p2)
                cq = pv._reduce_into(c, vk.pack_q2)
                xp = ops.modexp(bcast(cp), ep, vk.pack_p2)
                xq = ops.modexp(bcast(cq), eq, vk.pack_q2)
                return pv.crt_combine_batch(vk, xp, xq)

            powed = pv._cached_jit(
                vk, ("crt_mv_limbs", M, N, ops.active_reduce_impl()),
                powed_ct_body)(c_limbs, ep_l, eq_l)
        else:
            rows = [int(c) for row in cs_list for c in row]
            bp = bi.from_ints([c % key.p2 for c in rows], vk.pack_p2.L16)
            bq = bi.from_ints([c % key.q2 for c in rows], vk.pack_q2.L16)

            def powed_body(bp, ep, bq, eq):
                xp = ops.modexp(bcast(bp), ep, vk.pack_p2)
                xq = ops.modexp(bcast(bq), eq, vk.pack_q2)
                return pv.crt_combine_batch(vk, xp, xq)

            bp, bq = _shard_batch(bp, bq, count=False)
            powed = pv._cached_jit(
                vk, ("crt_mv", M, N, ops.active_reduce_impl()),
                powed_body)(bp, ep_l, bq, eq_l)

    def tree(powed):
        return pv.mul_tree(vk, powed.reshape(-1, N, L2))

    out = pv._cached_jit(vk, f"crt_matvec_tree_{N}", tree)(powed)
    if ct_in:
        return [CipherTensor(bk, out[b * M:(b + 1) * M]) for b in range(B)]
    ints = bi.to_ints(out)
    return [ints[b * M:(b + 1) * M] for b in range(B)]


def matvec_vec(bk: BatchKey, K, cs):
    """Single homomorphic matvec (M, N) x (N,) -> (M,), batched kernels.

    Returns a :class:`CipherTensor` when ``cs`` is one (limb-resident
    end to end), a list of ints otherwise.
    """
    K = np.asarray(K, dtype=object)
    cs = cs if isinstance(cs, CipherTensor) else list(cs)
    return matvec_many(bk, K[None], [cs])[0]


# ---------------------------------------------------------------------------
# jit compile-cache warmup
# ---------------------------------------------------------------------------

def warmup(bk: BatchKey, shapes: Sequence) -> dict:
    """Pre-compile the batched-path executables for the given shapes.

    XLA compiles one executable per (op, batch shape, exponent width); a
    cold K=128 protocol run used to pay ~16 s of compiles on its first
    iteration.  Calling this hook first (``dispatch.calibrate`` and
    ``bench_topology`` do) moves those compiles out of the measured path —
    the jit caches are keyed by the shared :class:`VecKey`, so any
    box over an equal :class:`~repro.core.paillier.PaillierKey` hits them.

    ``shapes`` entries: an int ``B`` warms the elementwise ops (enc, dec,
    ⊕-add) at batch B; a ``(B, M, N)`` tuple warms the fused limb-resident
    matvec at both 1- and 2-limb exponent widths (the Gamma_2 value range).
    Dummy operands (m=0, r=1, c=1) exercise identical graph shapes to real
    traffic.  Returns ``{"calls", "seconds"}`` telemetry.

    Compiles persist across PROCESSES too: the persistent XLA compile
    cache (``kernels.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``, else
    ``<repo>/.jax_cache``) is enabled here, so a warm cache turns the
    lowering work below into deserialization.
    """
    from ..kernels import compile_cache
    compile_cache.enable()
    t0 = time.perf_counter()
    calls = 0
    for shape in shapes:
        if isinstance(shape, (tuple, list)):
            B, M, N = (int(s) for s in shape)
            if min(B, M, N) <= 0:
                continue
            ones = CipherTensor.from_ints(bk, [1] * N)
            for val in (3, 1 << 17):   # 1- and 2-limb exponent widths
                Ks = np.full((B, M, N), val, dtype=object)
                matvec_many(bk, Ks, [ones] * B)
                calls += 1
        else:
            B = int(shape)
            if B <= 0:
                continue
            _enc_ct_impl(bk, [0] * B, [1] * B)
            ones = CipherTensor.from_ints(bk, [1] * B)
            dec_vec(bk, ones)
            add_ct(bk, ones, ones)
            calls += 3
    out = {"calls": calls, "seconds": time.perf_counter() - t0}
    from ..obs.metrics import record_profile
    record_profile("warmup", **out)
    return out


# ---------------------------------------------------------------------------
# Multi-key "rows" layer (serving): one launch, many tenants' keys.
#
# The jit'd paths above are keyed per BatchKey — correct for a solo run,
# useless for a serving engine fusing ops across tenants with DIFFERENT
# keys.  These functions lower a whole cluster of same-WIDTH Paillier ops
# (same exact byte length of n^2 — :func:`rows_sig`) onto the per-row-
# modulus kernels (``ops.mulmod_rows``/``modexp_rows``), where each row
# carries its own tenant's modulus as an operand.  Per-tenant keys make
# the rows independent, so fusing them changes nothing but the launch
# count.
#
# They are PURE: no counter bumps, no rng draws — the coalescer replays
# the scalar boxes' telemetry and blinding-draw order around them so a
# fused tenant stays bit-identical (rng stream included) to its solo run.
# Formulas mirror ``paillier.encrypt_crt``/``decrypt_crt`` exactly; all
# arithmetic is exact integer math, so results are bit-identical to the
# scalar gold path regardless of execution route.
#
# ``items`` below is always one entry per tenant: ``(key, ...operands)``;
# returns are per-tenant lists in the same order.
# ---------------------------------------------------------------------------


def rows_sig(key: gold.PaillierKey) -> tuple:
    """Fusion signature: ops fuse across tenants iff this matches.

    The exact byte length of n^2 (Barrett requires the top radix-256 limb
    populated, so equal bit-class keys share a width)."""
    return ("pail", (key.n2.bit_length() + 7) // 8)


def _rows_cluster_width(items) -> int:
    widths = {rows_sig(item[0])[1] for item in items}
    if len(widths) != 1:
        raise ValueError(f"mismatched limb widths in one cluster: "
                         f"{sorted(widths)} (rows_sig must match)")
    return widths.pop()


def _split_sizes(vals: list, sizes: list[int]) -> list[list]:
    out, i = [], 0
    for s in sizes:
        out.append(vals[i:i + s])
        i += s
    return out


def _exp_bytes(x: int) -> int:
    return max(1, (int(x).bit_length() + 7) // 8)


def enc_rows(items: Sequence) -> list[list[int]]:
    """Fused encryption: ``items = [(key, ms, rs), ...]``.

    c = (1 + m*n) * r^n mod n^2 per row (g = n+1 form, exactly
    ``paillier.encrypt_crt``); blinding factors ``rs`` are drawn by the
    caller in each tenant's own rng order.
    """
    L8 = _rows_cluster_width(items)
    gms, bases, exps, mods, sizes = [], [], [], [], []
    le8 = max(_exp_bytes(key.n) for key, _, _ in items)
    for key, ms, rs in items:
        for m in ms:
            gms.append((1 + int(m) * key.n) % key.n2)
        bases.extend(int(r) for r in rs)
        exps.extend([key.n] * len(ms))
        mods.extend([key.n2] * len(ms))
        sizes.append(len(ms))
    m8, mu8 = ops.rows_modulus(mods, L8)
    rn = ops.modexp_rows(ops.pack_rows(bases, L8),
                         ops.pack_rows(exps, le8), m8, mu8)
    c8 = ops.mulmod_rows(ops.pack_rows(gms, L8), rn, m8, mu8)
    return _split_sizes(ops.unpack_rows(c8), sizes)


def dec_rows(items: Sequence) -> list[list[int]]:
    """Fused decryption: ``items = [(key, cs), ...]``.

    m = L(c^lam mod n^2) * mu mod n (exactly ``paillier.decrypt_crt``).
    """
    L8 = _rows_cluster_width(items)
    bases, exps, mods, sizes = [], [], [], []
    le8 = max(_exp_bytes(key.lam) for key, _ in items)
    for key, cs in items:
        bases.extend(int(c) for c in cs)
        exps.extend([key.lam] * len(cs))
        mods.extend([key.n2] * len(cs))
        sizes.append(len(cs))
    m8, mu8 = ops.rows_modulus(mods, L8)
    x8 = ops.modexp_rows(ops.pack_rows(bases, L8),
                         ops.pack_rows(exps, le8), m8, mu8)
    xs = _split_sizes(ops.unpack_rows(x8), sizes)
    with span("host:dec_finish"):
        return [[(x - 1) // key.n * key.mu % key.n for x in xi]
                for (key, _), xi in zip(items, xs)]


def add_rows(items: Sequence) -> list[list[int]]:
    """Fused ⊕: ``items = [(key, c1s, c2s), ...]`` -> (c1*c2) mod n^2."""
    L8 = _rows_cluster_width(items)
    a, b, mods, sizes = [], [], [], []
    for key, c1s, c2s in items:
        a.extend(int(c) for c in c1s)
        b.extend(int(c) for c in c2s)
        mods.extend([key.n2] * len(c1s))
        sizes.append(len(c1s))
    m8, mu8 = ops.rows_modulus(mods, L8)
    out8 = ops.mulmod_rows(ops.pack_rows(a, L8), ops.pack_rows(b, L8),
                           m8, mu8)
    return _split_sizes(ops.unpack_rows(out8), sizes)


def matvec_rows(items: Sequence) -> list[list[list[int]]]:
    """Fused homomorphic matvec: ``items = [(key, Ks, cs_list), ...]``.

    Per tenant, ``Ks`` is an (E, M, N) block of NON-NEGATIVE plaintext
    exponents and ``cs_list`` holds E length-N ciphertext int lists; the
    result is E lists of M ints: out[e][i] = prod_j cs[e][j]^K[e][i][j]
    mod n^2.  (M, N) must match across the cluster — it is part of the
    coalescer's group shape; callers route any negative exponent through
    the per-tenant path instead.
    """
    L8 = _rows_cluster_width(items)
    bases, exps, mods_red, sizes = [], [], [], []
    le8 = 1
    mm = nn = None
    for key, Ks, cs_list in items:
        Ks = np.asarray(Ks, dtype=object)
        e_cnt, m_rows, n_cols = Ks.shape
        if mm is None:
            mm, nn = m_rows, n_cols
        assert (m_rows, n_cols) == (mm, nn), "cluster shape mismatch"
        for e in range(e_cnt):
            cs = [int(c) for c in cs_list[e]]
            assert len(cs) == nn
            for i in range(m_rows):
                for j in range(n_cols):
                    k = int(Ks[e, i, j])
                    if k < 0:
                        raise ValueError("matvec_rows requires "
                                         "non-negative exponents")
                    bases.append(cs[j])
                    exps.append(k)
                    le8 = max(le8, _exp_bytes(k))
                mods_red.append(key.n2)
        sizes.append(e_cnt)
    mods = [m for m in mods_red for _ in range(nn)]
    m8, mu8 = ops.rows_modulus(mods, L8)
    pw = ops.modexp_rows(ops.pack_rows(bases, L8),
                         ops.pack_rows(exps, le8), m8, mu8)
    m8r, mu8r = ops.rows_modulus(mods_red, L8)
    out8 = ops.prod_rows(pw.reshape(len(mods_red), nn, L8), m8r, mu8r)
    flat = ops.unpack_rows(out8)
    out, i = [], 0
    for (_, Ks, _), e_cnt in zip(items, sizes):
        rows = []
        for _ in range(e_cnt):
            rows.append(flat[i:i + mm])
            i += mm
        out.append(rows)
    return out

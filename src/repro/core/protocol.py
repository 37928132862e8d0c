"""3P-ADMM-PC2 — the paper's three-phase master/edge privacy protocol.

Faithful implementation of Algorithms 1 & 3 with explicit message passing:

  * Initialization phase   — master splits A by columns, ships
    alpha_k = {A_k^T A_k, rho} (+ quantization range + Delta); edge k returns
    B_k = (A_k^T A_k + rho I)^{-1} and keeps the quantized Gamma_2(B_k rho).
  * Data-security-sharing  — master quantizes+encrypts B_k A_k^T y (eq. 11);
    edge k stores the ciphertext alpha-hat.
  * Parallel privacy-computing — per iteration the master encrypts
    Gamma_2(u1_k), Gamma_2(u2_k); edge k evaluates eq. (13) entirely in
    ciphertext (one ⊕, one ⊗-matvec, one ⊕); master decrypts, dequantizes by
    Theorem 1 and runs the workload's plaintext global update (10b-c).

The iteration loop is WORKLOAD-GENERIC (``repro.workloads``): which
vectors/matrices fill the (u1, u2, u3, C) slots of the affine ciphertext
map is the problem family's business — LASSO (the paper's problem,
bit-compatible with the historical hard-coded loop: u1 = z_k, u2 = -v_k,
C = rho B_k), ridge, elastic_net, logistic consensus training,
power_grid, the row-split consensus families (each edge's block is the
full model width and the master's state stacks K copies — the
``Workload.dims`` split-axis contract) and streaming families (the
``Workload.reshare`` hook re-runs the data-security-sharing phase for
the edges whose u3 changed mid-run).  The loop is generic over WHAT is
encrypted and over WHEN data enters it; the encrypted interaction
pattern, accounting and collaborative (Algorithm-3) machinery are
identical for all of them.

Cipher backends share one interface so the protocol logic is written once:

  * ``plain`` — the exact integer chain (no encryption). Because Paillier's
    homomorphism is exact while the plaintext stays < n, the decrypted value
    equals the plain integer chain bit-for-bit — this is the scale-out path
    and is asserted against the encrypted paths in tests.
  * ``gold``  — Python-int Paillier (arbitrary key size), incl. the
    Algorithm-3 *collaborative* mode (master computes the q^2 CRT space, the
    edge the masked p^2 space; Remark 4 information flow).
  * ``vec``   — the batched limb-kernel path (core/paillier_vec.py).

Stats: the protocol counts every crypto op and message byte per node/phase;
benchmarks/bench_latency.py turns those counts into the paper's Tables III-V
via measured per-op throughput, and bench_total_time.py into Fig. 8.

Straggler mitigation (fault-tolerance at the protocol level): with a
``deadline`` and a simulated per-edge latency model, the master proceeds with
stale x-hat blocks for late edges — sound because the update (10) is
blockwise (stale blocks delay convergence but never corrupt state).  The
``deadline``/``latency_fn`` knobs are kept on :class:`ProtocolConfig`, but
their implementation lives in the event-driven runtime
(``repro.runtime.runner``): ``run_protocol`` delegates there whenever a
deadline is set (and for the ``auto``-dispatch cipher), while the plain
synchronous loop below remains the bit-exactness reference.
"""
from __future__ import annotations

import dataclasses
import random
from collections import defaultdict
from typing import Callable

import numpy as np
import jax.numpy as jnp

from . import cipher_tensor as ct_mod
from . import paillier as gold
from . import paillier_batch as pb
from . import paillier_vec as pv
from . import bigint as bi
from .cipher_tensor import CipherTensor
from .quantization import (QuantSpec, gamma1, gamma2, gamma1_saturation,
                           gamma2_saturation, dequantize_theorem1)
from .. import workloads as workloads_mod
from ..obs import health as health_mod
from ..obs import ledger as ledger_mod
from ..obs import metrics as obs_metrics


# ---------------------------------------------------------------------------
# Cipher backends
# ---------------------------------------------------------------------------

class PlainBox:
    """Exact plaintext-integer simulation of the homomorphic ring ops.

    Bumps the same logical op counters as the encrypted boxes (the protocol's
    crypto-op STRUCTURE is cipher-independent), so latency/throughput models
    built on the counters work from fast plain runs."""

    name = "plain"

    def __init__(self, spec: QuantSpec, n_dim: int, counter=None):
        if not spec.int64_safe(n_dim):
            self._dtype = object     # python-int fallback for huge Delta
        else:
            self._dtype = np.int64
        self.counter = counter or OpCounter()

    def encrypt(self, m: np.ndarray) -> np.ndarray:
        m = np.asarray(m)
        self.counter.bump("enc", m.size)
        return m.astype(self._dtype)

    def add(self, c1, c2):
        self.counter.bump("mulmod", np.asarray(c1).size)
        return c1 + c2

    def matvec(self, K: np.ndarray, c):
        M, N = K.shape
        self.counter.bump("modexp", M * N)
        self.counter.bump("mulmod", M * (N - 1))
        return K.astype(self._dtype) @ c

    def decrypt(self, c) -> np.ndarray:
        self.counter.bump("dec", np.asarray(c).size)
        return np.asarray(c)

    def ct_bytes(self, n_el: int) -> int:
        return 8 * n_el  # plaintext int64 wire size


class GoldBox:
    """Python-int Paillier; optional Algorithm-3 collaborative split.

    Batches of ``batch_min`` (default 8) or more elements route through the
    batched CRT fast path (``core.paillier_batch``): the ModExps of a whole
    enc/dec/matvec call run as one limb-kernel launch and no per-element
    Python ``pow`` executes.  Batched ciphertexts stay RESIDENT in limb
    form (:class:`~repro.core.cipher_tensor.CipherTensor`): encrypt emits
    limbs, ⊕/⊗/matvec chain on them in-graph, and decrypt consumes them —
    so the int<->limb host conversion runs once per phase boundary, not
    per op.  ``batch=False`` keeps the scalar loops — the bit-exactness
    reference the fast path is property-tested against — and so does
    ``crt=False``, since the fast path IS the CRT decomposition and must
    not stand in for the direct (non-CRT) reference.  Ciphertext VALUES
    are identical either way (same rng stream; a CipherTensor
    materializes to exactly the scalar ints), and every method accepts
    both representations.
    """

    name = "gold"

    def __init__(self, key: gold.PaillierKey, rng: random.Random,
                 crt: bool = True, counter=None, batch: bool = True,
                 batch_min: int | None = None):
        self.key = key
        self.rng = rng
        self.crt = crt
        self.counter = counter or OpCounter()
        self.batch = batch
        self.batch_min = pb.BATCH_MIN if batch_min is None else batch_min
        self._bk: pb.BatchKey | None = None

    def batch_key(self) -> pb.BatchKey:
        if self._bk is None:
            self._bk = pb.make_batch_key(self.key)
        return self._bk

    def encrypt(self, m: np.ndarray):
        flat = np.asarray(m).reshape(-1)
        self.counter.bump("enc", flat.size)
        # batched enc implements encrypt_crt's semantics (m wraps mod n),
        # so it only stands in for the crt=True scalar loop — crt=False
        # means gold.encrypt, whose out-of-range ValueError must not
        # appear and disappear with the batch size
        if self.batch and self.crt and flat.size >= self.batch_min \
                and self.key.g == self.key.n + 1:
            return pb.enc_ct(self.batch_key(), flat, self.rng)
        enc = gold.encrypt_crt if self.crt else gold.encrypt
        return [enc(self.key, int(x), gold.rand_r(self.key, self.rng))
                for x in flat]

    def add(self, c1, c2):
        self.counter.bump("mulmod", len(c1))
        if self.batch and self.crt and isinstance(c1, CipherTensor) \
                and isinstance(c2, CipherTensor):
            return pb.add_ct(self.batch_key(), c1, c2)
        return [(a * b) % self.key.n2 for a, b in zip(c1, c2)]

    def matvec(self, K: np.ndarray, c):
        Km = np.asarray(K, dtype=object)
        M, N = Km.shape
        self.counter.bump("modexp", M * N)
        self.counter.bump("mulmod", M * (N - 1))
        if self.batch and self.crt and M * N >= self.batch_min:
            return pb.matvec_vec(self.batch_key(), Km, c)
        out = []
        for i in range(M):
            acc = 1
            for j in range(N):
                acc = (acc * pow(c[j], int(Km[i, j]), self.key.n2)) % self.key.n2
            out.append(acc)
        return out

    def decrypt(self, c) -> np.ndarray:
        self.counter.bump("dec", len(c))
        if self.batch and self.crt and len(c) >= self.batch_min:
            vals = pb.dec_vec(self.batch_key(), c)
        else:
            dec = gold.decrypt_crt if self.crt else gold.decrypt
            vals = [dec(self.key, x) for x in c]
        return np.array(vals, dtype=object)

    def ct_bytes(self, n_el: int) -> int:
        return (self.key.n2.bit_length() + 7) // 8 * n_el


class VecBox:
    """Batched limb-kernel Paillier (the accelerated EP path).

    ``plain_bits`` bounds the plaintexts this box will decrypt (the
    Theorem-1 chain width, ``QuantSpec.plaintext_bits``); when the bound
    fits int64 decryption keeps the in-graph ``limbs_to_int64`` fast
    path, otherwise plaintext limbs decode losslessly through the bulk
    ``bigint`` codec.  ``None`` falls back to the key width (safe for
    any plaintext the ring admits).
    """

    name = "vec"

    def __init__(self, key: gold.PaillierKey, rng: random.Random,
                 counter=None, plain_bits: int | None = None):
        # share the limb-packed key (and thus the per-VecKey jit caches)
        # with any GoldBox over the same key via the make_batch_key cache
        self._bk = pb.make_batch_key(key)
        self.vk = self._bk.vk
        self.key = key
        self.rng = rng
        self.counter = counter or OpCounter()
        self.plain_bits = key.n.bit_length() if plain_bits is None \
            else plain_bits

    def encrypt(self, m: np.ndarray):
        m = np.asarray(m).reshape(-1)
        if len(m) >= pb.BATCH_MIN:
            # r^n blinding pool batched through the CRT limb kernels (one
            # launch) instead of per-element Python pow (make_r_pool)
            rs = pb.rand_r_vec(self.key, len(m), self.rng)
            rn = pb.rn_pool_limbs(self._bk, rs)
        else:
            pool = gold.make_r_pool(self.key, len(m), self.rng)
            rn = jnp.asarray(bi.from_ints(pool, self.vk.pack_n2.L16))
        self.counter.bump("enc", len(m))
        return pv.encrypt_batch(self.vk, jnp.asarray(m.astype(np.int64)), rn)

    def add(self, c1, c2):
        self.counter.bump("mulmod", int(c1.shape[0]))
        return pv.c_add_batch(self.vk, c1, c2)

    def matvec(self, K: np.ndarray, c):
        M, N = K.shape
        self.counter.bump("modexp", M * N)
        self.counter.bump("mulmod", M * (N - 1))
        return pv.c_matvec(self.vk, jnp.asarray(np.asarray(K, np.int64)), c)

    def decrypt(self, c) -> np.ndarray:
        """Limb-in decryption with a full-width plaintext return path.

        Accepts a raw limb array or a :class:`CipherTensor` (decrypted
        straight off its resident limbs).  When the plaintext bound
        (``plain_bits``) exceeds 62 bits the plaintext limbs decode
        losslessly through the bulk ``bigint.to_ints`` codec (object-int
        array) instead of the wrapping ``limbs_to_int64`` narrowing —
        Theorem-1 chains above int64 (large Delta x large N) decrypt
        exactly, while the common small-chain case keeps the in-graph
        int64 path."""
        if isinstance(c, CipherTensor):
            c = c.limbs
        self.counter.bump("dec", int(c.shape[0]))
        m_limbs = pv.decrypt_batch_limbs(self.vk, c)
        if self.plain_bits <= 62:           # every plaintext fits int64
            return np.asarray(pv.limbs_to_int64(m_limbs))
        return np.array(bi.to_ints(np.asarray(m_limbs)), dtype=object)

    def ct_bytes(self, n_el: int) -> int:
        return (self.key.n2.bit_length() + 7) // 8 * n_el


# canonical protocol phase names — the OpCounter/RunReport vocabulary.
# Drivers and instrumentation use these constants (not ad-hoc strings) so
# per-phase accounting from both drivers lands under identical keys.
PHASE_INIT = "init"
PHASE_SHARE = "share"
PHASE_ITERATE = "iterate"
PHASES = (PHASE_INIT, PHASE_SHARE, PHASE_ITERATE)
#: ops bumped before any driver set a phase land here — visible in the
#: report instead of silently miscounted under "init" (the historical
#: default), which polluted the init phase with e.g. calibration traffic.
PHASE_UNSET = "unphased"


class OpCounter:
    """Per-phase crypto-op and traffic accounting.

    ``phase`` starts as ``None``: a ``bump`` before any phase is set is
    accounted under :data:`PHASE_UNSET` rather than leaking into ``init``.
    ``as_dict`` emits a stable key order — canonical :data:`PHASES` first
    (those present), then any extra phases sorted, ops sorted within each
    phase — so reports and conformance diffs are byte-stable.
    """

    def __init__(self):
        self.counts: dict[str, dict[str, int]] = defaultdict(lambda: defaultdict(int))
        self.phase: str | None = None

    def bump(self, op: str, n: int = 1):
        self.counts[self.phase if self.phase is not None
                    else PHASE_UNSET][op] += n

    def as_dict(self):
        order = [ph for ph in PHASES if ph in self.counts]
        order += sorted(ph for ph in self.counts if ph not in PHASES)
        return {ph: dict(sorted(self.counts[ph].items())) for ph in order}


# ---------------------------------------------------------------------------
# Protocol configuration / result
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    K: int = 3
    rho: float = 1.0
    lam: float = 1.0
    iters: int = 50
    spec: QuantSpec = QuantSpec()
    workload: str = "lasso"            # repro.workloads registry name
    cipher: str = "plain"              # plain | gold | vec | auto
    key_bits: int = 256
    crt: bool = True
    collaborative: bool = False        # Algorithm 3 master/edge CRT split
    gold_batch: bool = True            # gold cipher: batched CRT fast path
    #   (False = per-element scalar reference; bench_topology records the
    #   measured speedup between the two)
    y_scale: str = "consistent"
    seed: int = 0
    # straggler knobs — handled by the runtime's deadline mode. Setting a
    # deadline ALONE races it against the runtime's modeled latencies
    # (link models + CostModel compute charges); latency_fn, when given,
    # replaces the compute charge with an explicit per-(edge, iter)
    # response time — link hops and scheduler ticks still add on top, so
    # a latency within ~ms of the deadline can tip stale where the
    # retired inline check (bare latency_fn > deadline) did not.
    # (Historically deadline without latency_fn was a no-op.)
    deadline: float | None = None      # straggler cutoff (simulated seconds)
    latency_fn: Callable[[int, int], float] | None = None  # (edge, iter)->s
    # dynamic-membership knobs (ROADMAP item 5). ``churn`` is a
    # core.churn.ChurnSchedule of leave/rejoin/fail events both drivers
    # consume identically; fail events (silent crashes) need the
    # runtime's deadline machinery and are rejected by the synchronous
    # reference loop.  ``recycle`` enables Zhang-1910.04581 recycled
    # updates: an edge whose quantized (u1, u2) moved by at most
    # ``recycle_tol`` integer steps since its last encrypted round
    # reuses that round's decrypted chain — the enc/step/dec launches
    # are skipped entirely and priced as a "recycled" op.  tol=0 reuses
    # only bit-identical chains, so the trajectory is unchanged.
    churn: object | None = None        # core.churn.ChurnSchedule
    recycle: bool = False              # recycled-update mode
    recycle_tol: int = 0               # quantized-int reuse tolerance


@dataclasses.dataclass
class ProtocolResult:
    x: np.ndarray
    history: np.ndarray
    stats: dict
    stale_events: int


# ---------------------------------------------------------------------------
# Edge node — owns only what Remark 4 allows it to see
# ---------------------------------------------------------------------------

class EdgeNode:
    def __init__(self, k: int, spec: QuantSpec):
        self.k = k
        self.spec = spec
        self.Gb = None          # Gamma_2(B_k rho) integer matrix
        self.alpha_hat = None   # ciphertext of Gamma_1(B_k A_k^T y)
        # Algorithm-3 collaborative material (p^2 space only)
        self.p2 = None
        self.phi_p2 = None
        self.g_p = None
        # batched-kernel routing for the two Algorithm-3 edges (set from
        # ProtocolConfig.gold_batch via collab_setup; the edge needs no
        # key material for these — only p^2 itself)
        self.collab_batch = False

    # -- Initialization phase -------------------------------------------
    def init_phase(self, Qk: np.ndarray, mu: float,
                   scale: float | None = None) -> np.ndarray:
        """Invert the workload's shipped block: B_k = (Q_k + mu I)^{-1},
        keeping Gamma_2(scale * B_k) (for LASSO: Q = A_k^T A_k, mu =
        scale = rho — the historical signature's bit-exact behavior)."""
        Nk = Qk.shape[0]
        scale = mu if scale is None else scale
        Bk = np.linalg.inv(Qk + mu * np.eye(Nk))
        self.Gb = np.asarray(gamma2(Bk * scale, self.spec))
        return Bk

    # -- Data security sharing phase -------------------------------------
    def store_shared(self, alpha_hat):
        self.alpha_hat = alpha_hat

    # -- Parallel privacy-computing phase (eq. 13) ------------------------
    def private_step(self, z_hat, v_hat, box) -> object:
        s = box.add(z_hat, v_hat)            # z-hat ⊕ (-v-hat)
        t = box.matvec(self.Gb, s)           # Gamma_2(B-bar) ⊗ ...
        return box.add(self.alpha_hat, t)    # alpha-hat ⊕ ...

    # -- Algorithm 3: collaborative masked p^2-space ModExp ---------------
    def collab_setup(self, p2: int, phi_p2: int, g: int,
                     batch: bool = False):
        self.p2, self.phi_p2, self.g_p = p2, phi_p2, g % p2
        self.collab_batch = batch

    def collab_encrypt_half(self, masked_exp: np.ndarray) -> list[int]:
        """g'^{O(Gamma(z)) mod phi(p^2)} mod p^2 for each masked exponent.

        With batched routing (``gold_batch``) the whole batch runs as ONE
        limb-kernel ModExp mod p^2; otherwise the scalar ``pow`` loop —
        both bit-identical (tests/test_conformance.py)."""
        es = [int(e) % self.phi_p2
              for e in np.asarray(masked_exp).reshape(-1)]
        if self.collab_batch and len(es) >= pb.BATCH_MIN:
            return ct_mod.modexp_mod_vec(self.g_p, es, self.p2)
        return self._collab_half_scalar(es)

    def _collab_half_scalar(self, es: list[int]) -> list[int]:
        return [pow(self.g_p, e, self.p2) for e in es]

    def reduce_p2(self, x_hat) -> list[int]:
        """(x-hat)' = x-hat mod p^2 (decryption assist, round 1).

        A limb-resident batch reduces in one vectorized launch straight
        off its limbs; int lists batch-reduce too under ``gold_batch``
        routing, else fall back to the per-element host ``%`` loop."""
        if isinstance(x_hat, CipherTensor):
            return ct_mod.reduce_mod_vec(x_hat, self.p2)
        if self.collab_batch and len(x_hat) >= pb.BATCH_MIN:
            return ct_mod.reduce_mod_vec(x_hat, self.p2)
        return self._reduce_p2_scalar(x_hat)

    def _reduce_p2_scalar(self, x_hat) -> list[int]:
        return [int(c) % self.p2 for c in x_hat]


# ---------------------------------------------------------------------------
# Protocol driver (master node logic)
# ---------------------------------------------------------------------------

def check_plaintext_fits(key: gold.PaillierKey, spec: QuantSpec,
                         n_dim: int) -> None:
    """Raise unless the Theorem-1 integer chain stays below n (Remark 2)."""
    need = spec.plaintext_bits(n_dim)
    if need >= key.n.bit_length():
        raise ValueError(
            f"plaintext chain needs {need} bits but n has "
            f"{key.n.bit_length()}; raise key_bits or lower Delta")


def make_box(cfg: ProtocolConfig, n_dim: int, rng: random.Random,
             counter: "OpCounter"):
    """Key material + cipher box for ``cfg.cipher``; returns ``(box, key)``.

    ``auto`` (per-op adaptive dispatch) is resolved by the runtime —
    ``repro.runtime.runner`` builds an AdaptiveBox itself so this module
    never imports the runtime package.
    """
    if cfg.cipher == "plain":
        return PlainBox(cfg.spec, n_dim, counter=counter), None
    # g = n+1 fast path also serves Algorithm 3: the masked p^2-space
    # offload uses the raw g and retains correctness either way
    key = gold.keygen(cfg.key_bits, rng, g=None)
    check_plaintext_fits(key, cfg.spec, n_dim)
    if cfg.cipher == "gold":
        return GoldBox(key, rng, crt=cfg.crt, counter=counter,
                       batch=cfg.gold_batch), key
    if cfg.cipher == "vec":
        return VecBox(key, rng, counter=counter,
                      plain_bits=cfg.spec.plaintext_bits(n_dim)), key
    raise ValueError(cfg.cipher)


def resolve_workload(cfg: ProtocolConfig,
                     workload: "workloads_mod.Workload | None" = None
                     ) -> "workloads_mod.Workload":
    """The workload object for a run: an explicit instance wins, else the
    registry entry named by ``cfg.workload`` built from cfg.rho/cfg.lam."""
    if workload is not None:
        return workload
    return workloads_mod.get(cfg.workload, rho=cfg.rho, lam=cfg.lam)


def run_protocol(A: np.ndarray, y: np.ndarray, cfg: ProtocolConfig,
                 workload: "workloads_mod.Workload | None" = None,
                 health: "bool | health_mod.HealthMonitor" = False,
                 ) -> ProtocolResult:
    """Run 3P-ADMM-PC2 end to end; master-node state lives in this frame.

    The iteration is workload-generic (see ``repro.workloads``): the
    encrypted chain per edge per round is always enc(Γ₂ u1) ⊕ enc(Γ₂ u2),
    ⊗ by the edge's Γ₂(C_k), ⊕ the stored Γ₁(u3_k) — only WHICH vectors
    and matrices fill those slots is the workload's business.

    ``health`` turns on the live watchers this driver supports (MSE
    divergence/stall and quantizer-range saturation — see
    ``repro.obs.health``); a monitored run carries the fired alerts at
    ``stats["health"]`` (non-core, so sync-mode conformance is
    unaffected).  Default off: the NullMonitor path is allocation-free.
    """
    if cfg.deadline is not None or cfg.cipher == "auto":
        # straggler/deadline semantics and adaptive dispatch live in the
        # event-driven runtime; the loop below is the synchronous reference
        from ..runtime.runner import run_on_runtime
        return run_on_runtime(A, y, cfg, workload=workload, health=health)

    monitor = health_mod.as_monitor(health)
    wl = resolve_workload(cfg, workload)
    rng = random.Random(cfg.seed)
    K = cfg.K
    churn = cfg.churn
    if churn is not None:
        churn.check(K, cfg.iters)
        if churn.has_fails:
            raise ValueError(
                "fail events (silent crashes) need the runtime driver's "
                "deadline machinery; the synchronous reference loop only "
                "models graceful leave/rejoin")
    # split-axis contract: the stacked master iterate (N_state) and the
    # per-edge encrypted block (Nk) — column split keeps the historical
    # N, N//K; row-split consensus stacks K full-width copies
    N_state, Nk = wl.dims(A, K)
    spec = cfg.spec

    counter = OpCounter()
    box, key = make_box(cfg, Nk, rng, counter)

    traffic = defaultdict(int)

    # --- Initialization phase -------------------------------------------
    counter.phase = PHASE_INIT
    ys = y / K if cfg.y_scale == "consistent" else y
    st = wl.init_state(np.asarray(A, np.float64),
                       np.asarray(y, np.float64), ys, K,
                       y_scale=cfg.y_scale)
    agg_ctx = None
    if wl.uses_secure_agg:
        # row-split consensus: the z-update's cross-edge aggregate flows
        # through secure aggregation — encrypted whenever this run has
        # key material, through the bit-exact plaintext mirror otherwise
        # (dedicated rng stream so the box's blinding draws stay put);
        # its crypto ops and worker->aggregator bytes join the protocol
        # accounting below
        agg_ctx = workloads_mod.SecureAggContext.for_run(
            spec, key, cfg.seed, counter, box.ct_bytes(1))
        st.aux["secure_agg"] = agg_ctx
    edges = [EdgeNode(k, spec) for k in range(K)]
    C_rowsums, Bks, u3s = [], [], []
    for k, edge in enumerate(edges):
        Qk, mu, scale = wl.edge_setup(st, k)
        traffic["master->edge"] += Qk.nbytes
        Bk = edge.init_phase(Qk, mu, scale)
        traffic["edge->master"] += Bk.nbytes
        C_rowsums.append((Bk * scale) @ np.ones(Nk))
        Bks.append(Bk)
        u3s.append(wl.share_vector(st, k, Bk))
        if cfg.collaborative and key is not None:
            edge.collab_setup(key.p2, key.phi_p2, key.g,
                              batch=cfg.gold_batch)

    # --- Data security sharing phase -------------------------------------
    counter.phase = PHASE_SHARE
    for k, edge in enumerate(edges):
        q_alpha = np.asarray(gamma1(u3s[k], spec))
        if monitor.enabled:
            monitor.observe_quant(-1, *gamma1_saturation(q_alpha, spec))
        c_alpha = box.encrypt(q_alpha)
        traffic["master->edge"] += box.ct_bytes(Nk)
        edge.store_shared(c_alpha)

    # --- Parallel privacy-computing phase ---------------------------------
    counter.phase = PHASE_ITERATE
    history = np.zeros((cfg.iters, N_state))
    reshare_events = 0
    active = set(range(K))
    churn_counts = {"leaves": 0, "rejoins": 0}
    if churn is not None:
        st.aux["churn_active"] = np.ones(K, dtype=bool)
    # recycled-update cache: the quantized (u1, u2) pair of each edge's
    # last ENCRYPTED round and the decrypted integer chain it produced.
    # Invalidated whenever the edge's stored u3 changes (re-share or
    # rejoin re-run) — the cached chain embeds Gamma_1(u3).
    last_q: list = [None] * K
    last_R: list = [None] * K
    recycled = 0

    for t in range(cfg.iters):
        if churn is not None:
            # membership events apply at the top of the round, before
            # the streaming re-shares, in schedule order — the runtime
            # submits its coalesced encs in the same sequence, which
            # keeps the blinding rng streams aligned across drivers
            for ev in churn.events_at(t):
                k = ev.edge
                last_q[k] = last_R[k] = None
                if ev.kind == "leave":
                    # graceful handoff: the master already holds the
                    # block (it decrypts every round); the block just
                    # freezes (column split) or folds out of the
                    # consensus aggregate (row split) via the
                    # churn_active mask until the edge returns
                    active.discard(k)
                    st.aux["churn_active"][k] = False
                    churn_counts["leaves"] += 1
                    continue
                # rejoin: FULL init-phase re-run — re-ship (Q_k, mu,
                # scale), rebuild B_k / C_k row sums / u3_k and
                # re-encrypt Gamma_1(u3_k): the PR-5 reshare contract
                # generalized from u3-only to C_k/Q_k
                active.add(k)
                st.aux["churn_active"][k] = True
                churn_counts["rejoins"] += 1
                Qk, mu, scale = wl.edge_setup(st, k)
                traffic["master->edge"] += Qk.nbytes
                Bk = edges[k].init_phase(Qk, mu, scale)
                traffic["edge->master"] += Bk.nbytes
                C_rowsums[k] = (Bk * scale) @ np.ones(Nk)
                Bks[k] = Bk
                u3s[k] = wl.share_vector(st, k, Bk)
                c_alpha = box.encrypt(np.asarray(gamma1(u3s[k], spec)))
                traffic["master->edge"] += box.ct_bytes(Nk)
                edges[k].store_shared(c_alpha)
        if wl.streaming:
            # streaming contract: re-run the encrypted share phase for
            # the edges whose data moved this round (u3 only; C_k is
            # fixed per run).  Accounted in the "iterate" phase — a
            # re-share is round-synchronous work, and the runtime's
            # coalescing queue fuses these encs into the same launch as
            # the round's (u1, u2) encryptions.  Absent edges miss the
            # refresh (their next rejoin re-runs the whole init phase).
            for k in wl.reshare(st, t):
                if k not in active:
                    continue
                u3s[k] = wl.share_vector(st, k, Bks[k])
                c_alpha = box.encrypt(np.asarray(gamma1(u3s[k], spec)))
                traffic["master->edge"] += box.ct_bytes(Nk)
                edges[k].store_shared(c_alpha)
                reshare_events += 1
                last_q[k] = last_R[k] = None
        x_new = np.zeros(N_state)
        for k, edge in enumerate(edges):
            sl = slice(k * Nk, (k + 1) * Nk)
            if k not in active:
                x_new[sl] = st.x_prev[sl]      # frozen handoff block
                continue
            u1, u2 = wl.iter_inputs(st, k)
            qz = np.asarray(gamma2(u1, spec))
            qv = np.asarray(gamma2(u2, spec))
            if monitor.enabled:
                cz_n, tz_n = gamma2_saturation(qz, spec)
                cv_n, tv_n = gamma2_saturation(qv, spec)
                monitor.observe_quant(t, cz_n + cv_n, tz_n + tv_n)
            w_sum = float(np.sum(u1 + u2))
            if cfg.recycle and last_q[k] is not None \
                    and int(np.max(np.abs(qz - last_q[k][0]))) \
                    <= cfg.recycle_tol \
                    and int(np.max(np.abs(qv - last_q[k][1]))) \
                    <= cfg.recycle_tol:
                # recycled update (Zhang 1910.04581): the quantized
                # inputs (and the stored u3) match the edge's last
                # encrypted round, so its chain would decrypt to the
                # cached R — skip the enc/step/dec entirely and
                # re-dequantize with THIS round's w-sum (a plaintext
                # master-side scalar).  With tol=0 the reuse is exact.
                counter.bump("recycled", Nk)
                recycled += 1
                R = last_R[k]
            else:
                cz = box.encrypt(qz)
                cv = box.encrypt(qv)
                traffic["master->edge"] += 2 * box.ct_bytes(Nk)
                x_hat = edge.private_step(cz, cv, box)
                traffic["edge->master"] += box.ct_bytes(Nk)

                if cfg.collaborative and key is not None \
                        and cfg.cipher == "gold":
                    # decryption assist: edge ships (x-hat)' mod p^2
                    _ = edge.reduce_p2(x_hat)
                    traffic["edge->master"] += \
                        (key.p2.bit_length() + 7) // 8 * Nk

                R = box.decrypt(x_hat).astype(np.float64)
                if cfg.recycle:
                    last_q[k] = (qz, qv)
                    last_R[k] = R
            x_new[sl] = np.asarray(dequantize_theorem1(
                R, C_rowsums[k], w_sum, Nk, spec))
        if monitor.enabled:
            # iterate step vs the (t-1) iterate, BEFORE the global update
            # consumes it — the live convergence observable
            monitor.observe_round(t, float(np.mean((x_new - st.x_prev) ** 2)))
        # master updates (10b)/(10c) with the (t-1) iterate — Jacobi order
        wl.global_update(st, x_new)
        history[t] = x_new

    if agg_ctx is not None:
        traffic["edge->master"] += agg_ctx.traffic_bytes
    stats = obs_metrics.build_run_report(
        driver="protocol", ops=counter.as_dict(), traffic=traffic,
        key_bits=None if key is None else key.n.bit_length(),
        cipher=cfg.cipher, workload=wl.name,
        reshare_events=reshare_events, history=history,
        churn={**churn_counts, "recycled": recycled})
    if monitor.enabled:
        # non-core key: a monitored sync pair still compares bit-identical
        # on every CORE_SECTIONS entry
        stats["health"] = monitor.health_section()
    # run-history ledger: one compact record per completed run (no-op
    # when REPRO_LEDGER is off; never raises)
    ledger_mod.record_run(stats, cfg=cfg, mode="sync")
    return ProtocolResult(x=st.x_prev, history=history, stats=stats,
                          stale_events=0)


# ---------------------------------------------------------------------------
# Algorithm-3 collaborative encryption demo (masked p^2-space offload)
# ---------------------------------------------------------------------------

def collaborative_encrypt(key: gold.PaillierKey, edge: EdgeNode,
                          m: np.ndarray, rng: random.Random) -> list[int]:
    """Master encrypts plaintexts with the p^2 ModExp offloaded to an edge.

    Obfuscation O(m) = m + t with t uniform 64-bit (additive mask); the edge
    returns g'^{O(m) mod phi(p^2)} mod p^2 and the master unmasks by
    multiplying g'^{-t mod phi(p^2)}. The edge learns only p^2, phi(p^2) and
    a uniformly masked exponent (Remark 4).
    """
    m = np.asarray(m).reshape(-1)
    masks = [rng.getrandbits(64) for _ in m]
    masked = np.array([int(x) + t for x, t in zip(m, masks)], dtype=object)
    # --- edge side (p^2 space) ---
    e_half = edge.collab_encrypt_half(masked)
    # --- master side: unmask + q^2 space + CRT combine + blinding ---
    out = []
    for mi, ti, ep in zip(m, masks, e_half):
        un = pow(key.g, -ti % key.phi_p2, key.p2)
        gp = (ep * un) % key.p2                       # g^m mod p^2
        gq = pow(key.g, int(mi) % key.phi_q2, key.q2)  # g^m mod q^2
        gm = gold.crt_combine(key, gp, gq)
        rn = pow(gold.rand_r(key, rng), key.n, key.n2)
        out.append((gm * rn) % key.n2)
    return out


def collab_encrypt_vec(key: gold.PaillierKey, edge: EdgeNode,
                       m: np.ndarray, rng: random.Random) -> list[int]:
    """Whole-batch :func:`collaborative_encrypt`: no Python ``pow`` loops.

    Same Remark-4 information flow, same rng stream, bit-identical
    ciphertexts (tests/test_conformance.py): masks draw first, the edge
    answers its (batched, if routed) p^2 half, then the master's three
    ModExp batches — unmask factors mod p^2, the q^2 half, and the r^n
    blindings in the CRT half spaces — each run as one kernel launch.
    """
    m = np.asarray(m).reshape(-1)
    masks = [rng.getrandbits(64) for _ in m]
    masked = np.array([int(x) + t for x, t in zip(m, masks)], dtype=object)
    # --- edge side (p^2 space) ---
    e_half = edge.collab_encrypt_half(masked)
    # --- master side, batched ---
    uns = ct_mod.modexp_mod_vec(key.g, [-t % key.phi_p2 for t in masks],
                                key.p2)
    gqs = ct_mod.modexp_mod_vec(key.g, [int(x) % key.phi_q2 for x in m],
                                key.q2)
    bk = pb.make_batch_key(key)
    rs = pb.rand_r_vec(key, len(m), rng)
    rns = pb.modexp_crt_vec(bk, rs, key.n)
    out = []
    for ep, un, gq, rn in zip(e_half, uns, gqs, rns):
        gp = (ep * un) % key.p2                       # g^m mod p^2
        gm = gold.crt_combine(key, gp, gq)
        out.append(gm * rn % key.n2)
    return out

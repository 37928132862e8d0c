"""Adaptive cipher-backend dispatch — the paper's "adaptive GPU
acceleration" made explicit.

At startup :func:`calibrate` measures per-element seconds for each crypto
op (enc / add / matvec / dec) on every requested backend over a
``key_bits x batch_size`` grid, and persists the table as JSON (default
``~/.cache/repro/dispatch_calib.json``, override with
``$REPRO_CALIB_CACHE``).  Entries are keyed by the device kind that
measured them (``cpu/gold/128/16`` — see :func:`device_kind` and
docs/runtime.md for the cache format), so one cache file holds separate
CPU/GPU/TPU grids and numbers from one device never price another's
routing.  Subsequent runs on the same device load the cache and skip the
measurement entirely.

:class:`AdaptiveBox` then implements the protocol's cipher-box interface
and routes *each call* to the cheapest backend.  ``gold`` (scalar
Python-int Paillier), ``gold_batch`` (the batched CRT fast path —
identical Python-int ciphertexts, so switching between the two golds is
free) and ``vec`` (in-graph limb kernels) share one key and one
ciphertext space, so a per-op switch is at most a representation change
(ints <-> limb arrays) whose cost is part of the routing decision.  On a
CPU the table typically keeps scalar ``gold``; on an accelerator the
batched backends win — which is why entries are device-keyed.  ``plain``
is calibrated too — it prices the functional-simulation path for the
cost model — but is never mixed into an encrypted run: its "ciphertexts"
are bare integers in a different ring.

:class:`CostModel` turns calibration entries (or analytic defaults) into
virtual-clock charges for the scheduler.
"""
from __future__ import annotations

import json
import os
import random
import time
from collections import Counter

import numpy as np
import jax.numpy as jnp

from ..core import bigint as bi
from ..core import cipher_tensor as ct_mod
from ..core import paillier as gold
from ..core import paillier_batch as pb
from ..core.quantization import QuantSpec
from ..obs import trace as trace_mod
from ..obs.metrics import record_profile

TABLE_VERSION = 3   # v3: entries keyed by device kind (cpu/gpu/tpu) so one
                    # cache file holds per-device grids, and the batched
                    # CRT fast path (paillier_batch) is calibrated as its
                    # own "gold_batch" backend beside scalar "gold" — both
                    # invalidate v2 numbers.
                    # v2: matvec calibrated with realistic Gamma_2-sized
                    # exponents (v1's all-ones exponents short-circuited
                    # pow() and underpriced the gold backend ~10x)
OPS = ("enc", "add", "matvec", "dec")
DEFAULT_BACKENDS = ("plain", "gold", "gold_batch", "vec")
# which ciphertext representation each routable backend produces/consumes
# (scalar and batched gold share the Python-int representation, so routing
# between them is free of conversion cost)
BACKEND_REP = {"gold": "gold", "gold_batch": "gold", "vec": "vec"}


def cache_path() -> str:
    return os.path.expanduser(
        os.environ.get("REPRO_CALIB_CACHE",
                       "~/.cache/repro/dispatch_calib.json"))


def device_kind() -> str:
    """Calibration-cache device key: the active jax backend (cpu/gpu/tpu).

    Throughput tables are device-specific — the limb kernels that lose to
    Python-int pow on a CPU win on an accelerator — so entries measured on
    one device kind must never price another's dispatch decisions.

    Multi-chip hosts get a ``xN`` device-count suffix (``tpux4``): the
    batched ops shard their leading axis across the local mesh
    (``paillier_batch._shard_batch``), so measured throughput scales with
    the chip count and a 4-chip table must not price a 1-chip host.
    """
    import jax
    kind = jax.default_backend()
    n = jax.local_device_count()
    return f"{kind}x{n}" if n > 1 else kind


def _entry_key(backend: str, key_bits: int, batch: int,
               device: str | None = None) -> str:
    return f"{device or device_kind()}/{backend}/{key_bits}/{batch}"


def _median_seconds(fn, reps: int = 3) -> float:
    fn()  # warmup (jit compile / cache fill)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _measure_backend(backend: str, key_bits: int, batch: int,
                     mat_rows: int, seed: int) -> dict:
    """Per-element seconds for one grid point (built fresh, no cache)."""
    from ..core import protocol  # deferred: protocol lazily imports us back

    rng = random.Random(seed)
    spec = QuantSpec(delta=1e6, zmin=-8.0, zmax=8.0)
    m = np.arange(batch, dtype=np.int64) % 1000
    # exponents must look like real Gamma_2 values (~20 bits): pow() with
    # trivial exponents short-circuits and underestimates gold's matvec
    K = np.array([rng.randrange(1, 1 << 20)
                  for _ in range(mat_rows * batch)],
                 dtype=np.int64).reshape(mat_rows, batch)
    if backend == "plain":
        box = protocol.PlainBox(spec, batch)
        convert = 0.0
    else:
        key = gold.keygen(key_bits, rng)
        if backend == "gold":
            box = protocol.GoldBox(key, rng, batch=False)   # scalar loops
        elif backend == "gold_batch":
            # batch_min=1 mirrors AdaptiveBox's gold_batch box: the table
            # must price the kernel path even at sub-8 batch grid points,
            # not silently fall back to (and mis-price as) the scalar loop
            box = protocol.GoldBox(key, rng, batch=True, batch_min=1)
        elif backend == "vec":
            # price the common case: chains that fit int64 (the wide
            # object-int decode is the big-Delta exception, not the rule)
            box = protocol.VecBox(key, rng, plain_bits=48)
        else:
            raise ValueError(backend)
    c = box.encrypt(m)
    out = {
        "enc": _median_seconds(lambda: box.encrypt(m)) / batch,
        "add": _median_seconds(lambda: box.add(c, c)) / batch,
        "matvec": _median_seconds(lambda: box.matvec(K, c))
        / (mat_rows * batch),
        "dec": _median_seconds(lambda: box.decrypt(c)) / batch,
    }
    if backend in ("gold", "gold_batch"):
        # cost to lift this representation into the vec limb space; a
        # limb-resident CipherTensor (the batched gold output) is already
        # there, so its conversion is free by construction
        if isinstance(c, ct_mod.CipherTensor):
            convert = 0.0
        else:
            L16 = (key.n2.bit_length() + 15) // 16
            convert = _median_seconds(lambda: bi.from_ints(c, L16)) / batch
    elif backend == "vec":
        arr = np.asarray(c)
        convert = _median_seconds(lambda: bi.to_ints(arr)) / batch
    out["convert"] = convert
    return out


def calibrate(key_bits=(128,), batch_sizes=(8, 64),
              backends=DEFAULT_BACKENDS, path: str | None = None,
              force: bool = False, mat_rows: int = 8, seed: int = 0,
              warm_key: "gold.PaillierKey | None" = None,
              warm_shapes=None) -> dict:
    """Fill (and persist) the throughput table for the requested grid.

    Only missing grid points are measured; everything already in the
    on-disk cache is reused, so the second run of any entry point starts
    instantly.  A corrupted or partial cache file (truncated JSON, wrong
    top-level type, missing/ill-typed ``entries``, version skew) never
    crashes the load — it falls back to measuring fresh and rewrites the
    file.

    ``warm_key`` additionally pre-compiles the batched-CRT executables for
    that key via :func:`paillier_batch.warmup` — on a cache HIT nothing
    else touches the kernels, so without this the first adaptive run pays
    the XLA compiles the calibration skipped.  ``warm_shapes`` defaults to
    ``batch_sizes`` (ints warm enc/dec/⊕; ``(B, M, N)`` tuples warm the
    fused matvec).
    """
    from ..kernels import compile_cache
    compile_cache.enable()    # measured compiles persist across processes
    path = path or cache_path()
    table: dict = {"version": TABLE_VERSION, "entries": {}}
    if not force and os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
        except (OSError, json.JSONDecodeError):
            loaded = None
        if (isinstance(loaded, dict)
                and loaded.get("version") == TABLE_VERSION
                and isinstance(loaded.get("entries"), dict)
                and all(isinstance(v, dict)
                        for v in loaded["entries"].values())):
            table = loaded
    dirty = False
    t0 = time.perf_counter()
    n_measured = n_cached = 0
    for backend in backends:
        for bits in key_bits:
            b = 0 if backend == "plain" else bits
            for batch in batch_sizes:
                k = _entry_key(backend, b, batch)
                if k not in table["entries"]:
                    table["entries"][k] = _measure_backend(
                        backend, b, batch, mat_rows, seed)
                    dirty = True
                    n_measured += 1
                else:
                    n_cached += 1
    record_profile("calibrate", measured=n_measured, cached=n_cached,
                   seconds=time.perf_counter() - t0, device=device_kind())
    if dirty:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    if warm_key is not None:
        shapes = list(warm_shapes) if warm_shapes is not None \
            else list(batch_sizes)
        pb.warmup(pb.make_batch_key(warm_key), shapes)
    return table


def lookup(table: dict, backend: str, key_bits: int, batch: int,
           device: str | None = None) -> dict:
    """Nearest grid entry for ``backend`` on this device kind: closest key
    bits, then closest batch (plain entries are stored under 0 bits and
    match any key).  Entries keyed ``device/backend/bits/batch`` only match
    their own device; legacy 3-part keys act as device wildcards (used by
    tests and hand-built tables)."""
    device = device or device_kind()
    bits = 0 if backend == "plain" else key_bits
    best, best_d = None, None
    for k, v in table.get("entries", {}).items():
        parts = k.split("/")
        if len(parts) == 4:
            dev, b, kb, bt = parts
            if dev != device:
                continue
        else:
            b, kb, bt = parts
        if b != backend:
            continue
        d = (abs(int(kb) - bits), abs(int(bt) - batch))
        if best_d is None or d < best_d:
            best, best_d = v, d
    if best is None:
        raise KeyError(f"no calibration for {backend!r} on {device!r} "
                       f"(run dispatch.calibrate first)")
    return best


# ---------------------------------------------------------------------------
# Serving admission knee cache
# ---------------------------------------------------------------------------
# The multi-tenant engine (repro.serve.protocol_engine) tunes how many
# tenants to admit concurrently — the knee of the aggregate rounds/sec
# curve — and persists the result here so later ``admission="auto"`` runs
# skip the sweep.  Entries share the dispatch cache file under the
# backend name "serve" (``cpu/serve/<key_bits>/<nk>``): :func:`lookup`
# filters on backend before parsing, and :func:`calibrate`'s
# load-validation only requires dict values, so the two families coexist.

def _serve_key(key_bits: int, nk: int, device: str | None = None) -> str:
    return _entry_key("serve", key_bits, nk, device=device)


def save_serve_knee(key_bits: int, nk: int, window: int,
                    curve: dict | None = None,
                    path: str | None = None) -> None:
    """Persist the tuned admission window for ``(device, key_bits, nk)``.

    ``curve`` optionally records the measured width -> rounds/sec sweep
    for later inspection.  Write is atomic (tmp + rename), merging into
    whatever calibration entries already live in the file; a corrupt
    existing file is replaced rather than crashing.
    """
    path = path or cache_path()
    table: dict = {"version": TABLE_VERSION, "entries": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
        except (OSError, json.JSONDecodeError):
            loaded = None
        if (isinstance(loaded, dict)
                and loaded.get("version") == TABLE_VERSION
                and isinstance(loaded.get("entries"), dict)
                and all(isinstance(v, dict)
                        for v in loaded["entries"].values())):
            table = loaded
    entry: dict = {"window": int(window)}
    if curve is not None:
        entry["rounds_per_sec"] = {str(k): float(v)
                                   for k, v in curve.items()}
    table["entries"][_serve_key(key_bits, nk)] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def load_serve_knee(key_bits: int, nk: int,
                    path: str | None = None) -> int | None:
    """Tuned admission window for ``(device, key_bits, nk)``, or ``None``.

    ``None`` on any defect — missing file, unreadable JSON, version skew,
    absent entry, non-dict entry, missing/non-positive/ill-typed window —
    so callers can always fall back to sequential admission without
    try/except.
    """
    path = path or cache_path()
    try:
        with open(path) as f:
            loaded = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not (isinstance(loaded, dict)
            and loaded.get("version") == TABLE_VERSION
            and isinstance(loaded.get("entries"), dict)):
        return None
    entry = loaded["entries"].get(_serve_key(key_bits, nk))
    if not isinstance(entry, dict):
        return None
    window = entry.get("window")
    if not isinstance(window, int) or isinstance(window, bool) \
            or window < 1:
        return None
    return window


# ---------------------------------------------------------------------------
# Virtual-clock cost model
# ---------------------------------------------------------------------------

# analytic fallback (seconds/op) in OpCounter vocabulary; roughly a small
# edge CPU on a 1024-bit key — only relative magnitudes matter for the
# simulated wall-clock.
DEFAULT_UNIT = {"enc": 2e-4, "dec": 2e-4, "modexp": 1e-4, "mulmod": 1e-7}


class CostModel:
    """Seconds charged to the virtual clock per OpCounter-style op dict."""

    def __init__(self, unit: dict | None = None):
        self.unit = dict(DEFAULT_UNIT, **(unit or {}))

    @classmethod
    def from_table(cls, table: dict, backend: str, key_bits: int,
                   batch: int) -> "CostModel":
        e = lookup(table, backend, key_bits, batch)
        return cls({"enc": e["enc"], "dec": e["dec"],
                    "modexp": e["matvec"], "mulmod": e["add"]})

    def cost(self, ops: dict) -> float:
        return sum(self.unit.get(op, 0.0) * n for op, n in ops.items())

    def edge_step_cost(self, n_dim: int) -> float:
        """eq. (13): one add, one (N x N) matvec, one add."""
        return self.cost({"mulmod": 2 * n_dim + n_dim * (n_dim - 1),
                          "modexp": n_dim * n_dim})


# ---------------------------------------------------------------------------
# Adaptive box
# ---------------------------------------------------------------------------

class ACipher:
    """Ciphertext vector tagged with its current representation."""

    __slots__ = ("rep", "data")

    def __init__(self, rep: str, data):
        self.rep = rep      # "gold" (list[int] | CipherTensor) | "vec" (limbs)
        self.data = data

    def __len__(self) -> int:
        return len(self.data) if self.rep == "gold" else int(self.data.shape[0])


class AdaptiveBox:
    """Protocol cipher box routing every op to the cheapest backend.

    Holds a scalar GoldBox, a batched-CRT GoldBox (``gold_batch`` — same
    key, same Python-int ciphertexts, zero conversion cost between the
    two) and a VecBox, all bumping one shared OpCounter, and consults the
    calibration table per call; the per-element conversion cost is added
    when an operand is in the other representation.  Backends missing
    from the table (e.g. hand-built two-backend tables) are simply not
    routable.  ``choices`` records every routing decision for reporting.
    """

    name = "auto"

    def __init__(self, key: gold.PaillierKey, rng: random.Random,
                 table: dict, counter=None, plain_bits: int | None = None):
        from ..core import protocol  # deferred: avoids import cycle
        self.key = key
        self.table = table
        self.gold = protocol.GoldBox(key, rng, crt=True, counter=counter,
                                     batch=False)
        self.counter = self.gold.counter
        self.boxes = {
            "gold": self.gold,
            "gold_batch": protocol.GoldBox(
                key, rng, crt=True, counter=self.counter, batch=True,
                batch_min=1),
            "vec": protocol.VecBox(key, rng, counter=self.counter,
                                   plain_bits=plain_bits),
        }
        self.vec = self.boxes["vec"]
        self.choices: Counter = Counter()
        # observability: the runner wires a tracer + virtual clock in so
        # every routing decision becomes a "dispatch" span
        self.tracer: "trace_mod.Tracer | trace_mod.NullTracer" = trace_mod.NULL
        self.clock = None   # callable -> virtual seconds (else wall 0.0)

    # -- routing ---------------------------------------------------------
    def _entry(self, backend: str, batch: int) -> dict:
        return lookup(self.table, backend, self.key.n.bit_length(), batch)

    def _pick(self, op: str, n_el: int, reps: tuple[str, ...] = (),
              conv_el: int | None = None) -> str:
        """Cheapest backend for ``op`` over ``n_el`` elements; operands in
        another representation charge conversion on their own length
        ``conv_el`` (a matvec touches M*N exponents but converts only the
        N-element ciphertext vector)."""
        conv_el = n_el if conv_el is None else conv_el
        costs = {}
        for backend, rep_b in BACKEND_REP.items():
            try:
                c = self._entry(backend, n_el)[op] * n_el
                for rep in reps:
                    if rep != rep_b:  # operand must change representation
                        c += self._entry(rep, conv_el)["convert"] * conv_el
            except KeyError:
                continue    # backend (or its conversion) not calibrated
            costs[backend] = c
        if not costs:
            raise KeyError(f"no calibrated encrypted backend for {op!r} "
                           f"(run dispatch.calibrate first)")
        pick = min(costs, key=costs.get)
        self.choices[(op, pick)] += 1
        if self.tracer.enabled:
            self.tracer.add(f"dispatch:{op}", "dispatch",
                            t=self.clock() if self.clock else 0.0,
                            op=op, backend=pick, n_el=n_el)
        return pick

    def _coerce(self, c: ACipher, rep: str) -> object:
        if c.rep == rep:
            return c.data
        if rep == "vec":
            if isinstance(c.data, ct_mod.CipherTensor):
                return c.data.limbs        # already resident: free
            return jnp.asarray(bi.from_ints(list(c.data),
                                            self.vec.vk.pack_n2.L16))
        # to "gold": wrap the vec limb array — the batched gold box stays
        # limb-resident and scalar consumers materialize ints lazily
        return ct_mod.CipherTensor(self.boxes["gold_batch"].batch_key(),
                                   c.data)

    def _box(self, backend: str):
        return self.boxes[backend]

    # -- box interface ---------------------------------------------------
    def encrypt(self, m: np.ndarray) -> ACipher:
        m = np.asarray(m).reshape(-1)
        b = self._pick("enc", m.size)
        return ACipher(BACKEND_REP[b], self._box(b).encrypt(m))

    def add(self, c1: ACipher, c2: ACipher) -> ACipher:
        b = self._pick("add", len(c1), reps=(c1.rep, c2.rep))
        rep = BACKEND_REP[b]
        return ACipher(rep, self._box(b).add(self._coerce(c1, rep),
                                             self._coerce(c2, rep)))

    def matvec(self, K: np.ndarray, c: ACipher) -> ACipher:
        M, N = K.shape
        b = self._pick("matvec", M * N, reps=(c.rep,), conv_el=N)
        rep = BACKEND_REP[b]
        return ACipher(rep, self._box(b).matvec(K, self._coerce(c, rep)))

    def decrypt(self, c: ACipher) -> np.ndarray:
        b = self._pick("dec", len(c), reps=(c.rep,))
        return self._box(b).decrypt(self._coerce(c, BACKEND_REP[b]))

    def ct_bytes(self, n_el: int) -> int:
        return (self.key.n2.bit_length() + 7) // 8 * n_el

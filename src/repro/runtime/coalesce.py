"""Crypto-op batching queue — one kernel launch per tick, not per edge.

Actors never call the cipher box directly: they ``submit`` ops to this
queue with a callback.  Submissions accumulate until the next tick
boundary (``tick_s`` of virtual time), then :meth:`flush` groups them by
``(op, element shape)`` and executes each group as ONE batched box call:

* ``enc`` / ``add`` / ``dec`` are elementwise — K edges' vectors are
  concatenated, run through a single ``paillier_vec`` launch (or one
  gold/plain call), and split back;
* same-shaped ``matvec`` groups on the vec backend go through
  :func:`c_matvec_many`, which flattens all K ``(M, N)`` ModExp blocks
  into one kernel launch and shares the log-tree row reduction; on the
  gold backend the same fusion runs through the batched CRT fast path
  (``paillier_batch.matvec_many`` — limb-resident CipherTensors in and
  out, one launch).

Because the underlying ops are exact modular arithmetic, coalescing is
bit-transparent: results and OpCounter totals are identical to issuing
each op alone (asserted in tests/test_dispatch.py).  Boxes that cannot
concatenate opaque ciphertexts (the AdaptiveBox wrapper) fall back to
per-entry execution inside the same flush event.

Gold-cipher groups concatenate LIMB-RESIDENT: batched GoldBox ciphertexts
are :class:`~repro.core.cipher_tensor.CipherTensor` batches, so `_cat`/
`_split` slice and join limb arrays directly — no int materialization at
the queue boundary (the ints-per-op round-trip was ~10-15% of batched
gold time).

``counter.phase`` is captured at submit time and restored per group at
flush time, so per-phase accounting survives the deferred execution.

``hold_ticks > 0`` relaxes the flush-every-tick rule: while every pending
group is a singleton (nothing to coalesce), the flush defers up to that
many ticks waiting for company — the moment a second same-shaped op
arrives the queue flushes at the next tick, and a hold horizon bounds the
added latency.  This lets late edges' ops (heterogeneous links, deadline
mode) share a launch with their peers — or with the NEXT iteration's ops
— instead of flushing alone.  Results stay bit-identical; only timing
and launch counts change.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from ..core import cipher_tensor as ct_mod
from ..core import paillier_batch as pbatch
from ..core import paillier_vec as pv
from ..core.cipher_tensor import CipherTensor
from ..kernels import ops
from ..obs import health as health_mod
from ..obs import metrics as obs_metrics
from ..obs import trace as trace_mod
from .scheduler import Scheduler

_MATVEC_JIT: dict = {}
_MATVEC_JIT_MAX = 32   # FIFO-bounded: sweeps over many keys/shapes must
                       # not pin compiled executables (and their key
                       # material) for the process lifetime


def c_matvec_many(vk, Ks: jnp.ndarray, cs: jnp.ndarray,
                  exp_limbs: int = 4):
    """Batched homomorphic matvec: out[b, i] = prod_j cs[b, j]^{Ks[b,i,j]}.

    The (B, M, N) exponent block becomes a single flattened ModExp launch
    — the coalesced form of ``paillier_vec.c_matvec`` — followed by one
    shared log-depth mulmod tree over j.
    """
    B, M, N = Ks.shape
    L2 = vk.pack_n2.L16

    def body(Ks, cs):
        bases = jnp.broadcast_to(cs[:, None, :, :], (B, M, N, L2))
        powed = ops.modexp(bases.reshape(B * M * N, L2),
                           pv.int64_to_limbs(Ks.reshape(-1), exp_limbs),
                           vk.pack_n2)
        out = pv.mul_tree(vk, powed.reshape(B * M, N, L2))
        return out.reshape(B, M, L2)

    key = (id(vk), "cmv_many", exp_limbs, (B, M, N))
    fn = _MATVEC_JIT.get(key)
    if fn is None:
        import jax
        while len(_MATVEC_JIT) >= _MATVEC_JIT_MAX:
            _MATVEC_JIT.pop(next(iter(_MATVEC_JIT)))
        fn = _MATVEC_JIT[key] = jax.jit(body)
    return fn(Ks, cs)


@dataclasses.dataclass
class _Entry:
    args: tuple
    phase: str
    cb: Callable


def _cat(parts):
    if all(isinstance(p, CipherTensor) for p in parts):
        return ct_mod.concat(parts)        # stays limb-resident
    if isinstance(parts[0], (list, CipherTensor)):
        out = []                           # mixed reps: join as ints
        for p in parts:
            out.extend(p)
        return out
    if isinstance(parts[0], np.ndarray):
        return np.concatenate(parts)
    return jnp.concatenate(parts)


def _split(data, sizes):
    out, i = [], 0
    for n in sizes:
        out.append(data[i:i + n])
        i += n
    return out


class CoalesceQueue:
    def __init__(self, sched: Scheduler, box, counter=None,
                 tick_s: float = 1e-4, hold_ticks: int = 0,
                 tracer: "trace_mod.Tracer | trace_mod.NullTracer" = trace_mod.NULL,
                 monitor=health_mod.NULL_MONITOR):
        self.sched = sched
        self.box = box
        self.counter = counter if counter is not None \
            else getattr(box, "counter", None)
        self.tick_s = tick_s
        self.hold_ticks = hold_ticks   # max ticks a lone op waits for company
        self.tracer = tracer
        self.monitor = monitor     # health watcher for queue-depth blowup
        self.pending: dict[tuple, list[_Entry]] = {}
        self._flush_posted = False
        self._horizon_posted = False   # a hold-horizon event is in flight
        self._win = 0                  # flush-window id (stale-event guard)
        self.launches = 0          # batched box/kernel invocations
        self.coalesced_ops = 0     # ops that shared a launch with others
        self.held_flushes = 0      # flushes deferred waiting for company
        # per-launch observability: coalesce width per launch (the
        # ops-per-launch histogram) and host wall per launch split
        # cold/warm — the first launch of an (op, element-shape) group
        # pays any jit compile the warmup didn't cover
        self.launch_widths: list[int] = []
        self.launch_walls: dict[str, dict[str, list[float]]] = {}
        self._warm_shapes: set[tuple] = set()
        # the protocol round the master is in (set by the runner); labels
        # the profiler-clock launch spans, None before the first round
        self.round: int | None = None
        # per protocol round, how its launches laid their ladder rows over
        # the chips: the differences of ``paillier_batch.LAYOUT``
        self.layout: dict[int, dict[str, int]] = {}

    # -- submission ------------------------------------------------------
    def submit(self, op: str, args: tuple, cb: Callable) -> None:
        """Queue ``op`` (enc/add/dec/matvec) for the next tick flush."""
        if op == "matvec":
            shape = tuple(np.asarray(args[0]).shape)
        else:
            shape = (self._size(args[0]),)
        phase = self.counter.phase if self.counter is not None else "?"
        entries = self.pending.setdefault((op, shape), [])
        entries.append(_Entry(args=args, phase=phase, cb=cb))
        if self.monitor.enabled:
            self.monitor.observe_queue_depth(
                sum(len(es) for es in self.pending.values()))
        if not self._flush_posted:
            self._flush_posted = True
            self._post_flush()
        elif self._horizon_posted and len(entries) == 2:
            # a held singleton just got company: flush at the next tick
            # (the now-stale horizon event no-ops via its window id)
            self._post_flush()

    def _post_flush(self) -> None:
        w = self._win
        self.sched.at(self._tick_time(1), lambda: self.flush(win=w),
                      label="coalesce.flush")

    def _tick_time(self, n_ticks: int) -> float:
        # n_ticks strictly after now; float division can put an exact
        # boundary a hair below its integer index, so snap before adding
        q = self.sched.now / self.tick_s
        idx = round(q) if abs(q - round(q)) < 1e-9 else int(q)
        return (idx + n_ticks) * self.tick_s

    @staticmethod
    def _size(x) -> int:
        if isinstance(x, list):
            return len(x)
        if hasattr(x, "shape"):
            return int(np.asarray(x.shape[0]))
        return len(x)

    # -- execution -------------------------------------------------------
    def flush(self, force: bool = False, win: int | None = None) -> None:
        if win is not None and win != self._win:
            return    # event of a window that already flushed
        if not self.pending:
            return
        if (self.hold_ticks and not force
                and all(len(es) == 1 for es in self.pending.values())):
            # nothing coalesces yet — hold for company, bounded by the
            # horizon posted below (deadline-mode late edges' ops get to
            # share a launch with their peers or the next iteration)
            if not self._horizon_posted:
                self._horizon_posted = True
                self.held_flushes += 1
                w = self._win
                self.sched.at(self._tick_time(self.hold_ticks),
                              lambda: self.flush(force=True, win=w),
                              label="coalesce.hold")
            return
        groups, self.pending = self.pending, {}
        self._flush_posted = False
        self._horizon_posted = False
        self._win += 1
        self._dispatch_groups(groups)
        # callbacks may have queued follow-up ops for the next tick

    def _dispatch_groups(self, groups: dict) -> None:
        """Execute one flush's groups (deterministic repr-sorted order).

        Subclasses (the serving engine's :class:`TenantQueue`) override
        this to hand the groups to a shared cross-tenant collector
        instead of executing them locally."""
        for (op, shape), entries in sorted(groups.items(),
                                           key=lambda kv: repr(kv[0])):
            self._exec_group(op, shape, entries)

    def _exec_group(self, op: str, shape: tuple,
                    entries: list[_Entry]) -> None:
        """Run one (op, shape) group exactly as a solo flush would."""
        if self.counter is not None:
            self.counter.phase = entries[0].phase
        batchable = getattr(self.box, "name", "") in ("plain", "gold", "vec")
        # matvec truly fuses on the vec backend and on the gold box's
        # batched CRT path (other boxes loop per entry inside the group
        # runner) — keep the telemetry honest
        fused = batchable and len(entries) > 1 and \
            (op != "matvec" or self._matvec_fuses(entries))
        # a launch span closes before the callbacks, which run the
        # driver's next steps
        if not fused:
            for e in entries:
                t0 = time.perf_counter()
                with self._launch_span(op, 1, False) as sp:
                    before = dict(pbatch.LAYOUT)
                    res = self._run_one(op, e.args)
                    self._record_layout(before, sp)
                self._observe_launch(op, shape, [e],
                                     (time.perf_counter() - t0) * 1e3,
                                     fused=False)
                self.launches += 1
                e.cb(res)
            return
        self.coalesced_ops += len(entries)
        self.launches += 1
        t0 = time.perf_counter()
        with self._launch_span(op, len(entries), True) as sp:
            before = dict(pbatch.LAYOUT)
            results = self._run_group(op, entries)
            self._record_layout(before, sp)
        self._observe_launch(op, shape, entries,
                             (time.perf_counter() - t0) * 1e3, fused=True)
        for e, res in zip(entries, results):
            e.cb(res)

    def _launch_span(self, op: str, width: int, fused: bool):
        known = {} if self.round is None else {"round": self.round}
        return trace_mod.span(f"launch:{op}", op=op, width=width,
                              fused=fused, **known)

    def _record_layout(self, before: dict, sp) -> None:
        """Adds a launch's ladder rows, as ``paillier_batch.LAYOUT`` moved
        during it, to its round's count, and to its span the chips its
        batch was spread over; a launch that ran no ladder (``add``)
        records nothing."""
        moved = {k: n - before[k] for k, n in pbatch.LAYOUT.items()}
        if not moved["rows"]:
            return
        sp.set_metadata(chips=1 if moved["one_chip"] == moved["rows"]
                        else jax.local_device_count())
        if self.round is not None:
            tally = self.layout.setdefault(self.round, dict.fromkeys(moved, 0))
            for k, n in moved.items():
                tally[k] += n

    def _observe_launch(self, op: str, shape: tuple, entries: list[_Entry],
                        wall_ms: float, fused: bool) -> None:
        """Record one executed launch: width, cold/warm wall, spans."""
        width = len(entries)
        self.launch_widths.append(width)
        kind = "cold" if (op, shape) not in self._warm_shapes else "warm"
        self._warm_shapes.add((op, shape))
        walls = self.launch_walls.setdefault(op, {"cold": [], "warm": []})
        walls[kind].append(wall_ms)
        if self.tracer.enabled:
            self.tracer.add(
                f"launch:{op}", "launch", t=self.sched.now, wall_ms=wall_ms,
                op=op, shape=shape, width=width, fused=fused, jit=kind,
                backend=getattr(self.box, "name", "?"),
                phase=entries[0].phase)
            for e in entries:
                self.tracer.add(op, "crypto_op", t=self.sched.now,
                                op=op, shape=shape, phase=e.phase,
                                coalesced=fused)

    def metrics_section(self) -> dict:
        """Coalescing telemetry for the RunReport ``runtime`` section."""
        return {
            "launches": self.launches,
            "coalesced_ops": self.coalesced_ops,
            "held_flushes": self.held_flushes,
            "ops_per_launch": obs_metrics.summary(self.launch_widths),
            "launch_wall_ms": {
                op: {k: obs_metrics.summary(v)
                     for k, v in walls.items() if v}
                for op, walls in sorted(self.launch_walls.items())},
        }

    def _run_one(self, op: str, args: tuple):
        if op == "enc":
            return self.box.encrypt(args[0])
        if op == "add":
            return self.box.add(args[0], args[1])
        if op == "dec":
            return self.box.decrypt(args[0])
        if op == "matvec":
            return self.box.matvec(args[0], args[1])
        raise ValueError(op)

    def _run_group(self, op: str, entries: list[_Entry]) -> list:
        if op == "enc":
            sizes = [np.asarray(e.args[0]).size for e in entries]
            big = self.box.encrypt(np.concatenate(
                [np.asarray(e.args[0]).reshape(-1) for e in entries]))
            return _split(big, sizes)
        if op == "add":
            sizes = [self._size(e.args[0]) for e in entries]
            big = self.box.add(_cat([e.args[0] for e in entries]),
                               _cat([e.args[1] for e in entries]))
            return _split(big, sizes)
        if op == "dec":
            sizes = [self._size(e.args[0]) for e in entries]
            big = self.box.decrypt(_cat([e.args[0] for e in entries]))
            return _split(big, sizes)
        if op == "matvec":
            return self._run_matvec_group(entries)
        raise ValueError(op)

    def _matvec_fuses(self, entries: list[_Entry]) -> bool:
        name = getattr(self.box, "name", "")
        if name == "vec":
            return True
        if name == "gold" and getattr(self.box, "batch", False) \
                and getattr(self.box, "crt", True):
            # the fused path is the CRT decomposition; crt=False boxes
            # keep their direct per-entry reference loops
            M, N = np.asarray(entries[0].args[0]).shape
            return len(entries) * M * N >= self.box.batch_min
        return False

    def _run_matvec_group(self, entries: list[_Entry]) -> list:
        name = getattr(self.box, "name", "")
        if not self._matvec_fuses(entries):
            out = []
            for e in entries:
                out.append(self.box.matvec(e.args[0], e.args[1]))
            return out
        if name == "gold":
            # one fused batched-CRT launch over every edge's (M, N) block
            Ks = np.stack([np.asarray(e.args[0], dtype=object)
                           for e in entries])
            B, M, N = Ks.shape
            if self.counter is not None:  # same totals box.matvec would bump
                self.counter.bump("modexp", B * M * N)
                self.counter.bump("mulmod", B * M * (N - 1))
            return pbatch.matvec_many(self.box.batch_key(), Ks,
                                      [e.args[1] for e in entries])
        # one fused launch for all same-shaped (M, N) blocks
        vk = self.box.vk
        Ks = jnp.stack([jnp.asarray(np.asarray(e.args[0], np.int64))
                        for e in entries])
        cs = jnp.stack([e.args[1] for e in entries])
        B, M, N = Ks.shape
        if self.counter is not None:  # same totals box.matvec would bump
            self.counter.bump("modexp", B * M * N)
            self.counter.bump("mulmod", B * M * (N - 1))
        out = c_matvec_many(vk, Ks, cs)
        return [out[i] for i in range(B)]


# ---------------------------------------------------------------------------
# Cross-tenant coalescing (the serving engine's shared launch queue).
#
# Every tenant keeps its OWN TenantQueue — own box, counter, tracer —
# so solo semantics (group sort order, phase restore, telemetry) are
# byte-preserved; but instead of executing its flush locally, each queue
# hands its groups to one shared CrossTenantCoalescer.  The collector
# runs once per tick (a same-timestamp event posted during the first
# tenant flush — the scheduler's FIFO seq guarantees it runs after every
# tenant's flush at that tick), clusters groups by (op, shape,
# fuse_sig), and executes each cluster as ONE multi-key rows launch
# (``paillier_batch.enc_rows``/...): per-tenant moduli ride as operands,
# so tenants with DIFFERENT keys share the launch.
#
# Bit-transparency: the collector replays each tenant box's telemetry
# (size-based counter bumps under the entry phase) and blinding-factor
# draws (tenant rng, solo order) around the pure rows call, and demuxes
# results into exactly the representation the solo box would have
# returned (CipherTensor vs int list vs object ndarray, per the box's
# own batch/batch_min rules).  Groups with no fusion signature — plain,
# vec, adaptive boxes, non-batch gold matvec, negative matvec exponents
# — run through the tenant's own ``_exec_group``, i.e. literally the
# solo code path.
# ---------------------------------------------------------------------------

from ..core import paillier as gold  # noqa: E402  (serving layer below)

ROWS_OPS = ("enc", "dec", "add", "matvec")


def fuse_sig(box, op: str):
    """Cross-tenant fusion signature for one tenant's (box, op).

    Ops fuse across tenants iff signatures match: same op kind and same
    exact byte length of n^2 (``paillier_batch.rows_sig``).  ``None``
    means "never fuse — run the solo path"."""
    if op not in ROWS_OPS or getattr(box, "name", "") != "gold":
        return None
    key = box.key
    if not getattr(box, "crt", False) or key.g != key.n + 1:
        return None
    if op == "matvec" and not getattr(box, "batch", False):
        return None
    return pbatch.rows_sig(key)


def _ints_of(x) -> list[int]:
    if isinstance(x, CipherTensor):
        return [int(v) for v in x.to_ints()]
    if isinstance(x, np.ndarray):
        return [int(v) for v in x.reshape(-1)]
    return [int(v) for v in x]


class TenantQueue(CoalesceQueue):
    """Per-tenant CoalesceQueue that defers execution to the shared
    cross-tenant collector (falls back to solo behavior without one)."""

    def __init__(self, *args, tenant=None, collector=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.tenant = tenant
        self.collector = collector
        if collector is not None:
            collector.register(self)

    def _dispatch_groups(self, groups: dict) -> None:
        if self.collector is None:
            super()._dispatch_groups(groups)
            return
        self.collector.collect(self, groups)


class CrossTenantCoalescer:
    """Shared launch queue: clusters all tenants' same-tick groups by
    (op, shape, fuse_sig) and executes each cluster as one launch."""

    def __init__(self, sched: Scheduler,
                 tracer: "trace_mod.Tracer | trace_mod.NullTracer" = trace_mod.NULL,
                 max_log: int = 4096):
        self.sched = sched
        self.tracer = tracer
        self.max_log = max_log
        self._pending: list[tuple] = []   # (tq, op, shape, entries)
        self._posted = False
        self.queues: list[TenantQueue] = []
        self.total_launches = 0    # every launch the collector executed
        self.rows_launches = 0     # launches through the multi-key rows path
        self.fused_launches = 0    # rows launches spanning >= 2 tenants
        self.fused_ops = 0         # ops riding those cross-tenant launches
        self.fused_log: list[dict] = []
        self.fused_log_dropped = 0

    def register(self, tq: TenantQueue) -> None:
        self.queues.append(tq)

    # -- collection ------------------------------------------------------
    def collect(self, tq: TenantQueue, groups: dict) -> None:
        # keep each tenant's solo group order (repr-sorted) so its
        # callbacks and rng draws replay in the solo sequence
        for (op, shape), entries in sorted(groups.items(),
                                           key=lambda kv: repr(kv[0])):
            self._pending.append((tq, op, shape, entries))
        if not self._posted:
            self._posted = True
            # same-timestamp event: runs after every tenant flush already
            # queued at this tick (monotonic event seq), so one cluster
            # pass sees the whole tick's ops
            self.sched.at(self.sched.now, self._execute, label="serve.fuse")

    # -- execution -------------------------------------------------------
    def _execute(self) -> None:
        self._posted = False
        pending, self._pending = self._pending, []
        clusters: dict[tuple, list] = {}
        for tq, op, shape, entries in pending:
            sig = fuse_sig(tq.box, op)
            clusters.setdefault((op, shape, sig), []).append((tq, entries))
        # sorted by repr: within one tenant, (op, shape, sig) order equals
        # the solo flush's (op, shape) order — sig is a function of
        # (box, op), so two same-tenant groups never differ only in sig
        for (op, shape, sig), parts in sorted(clusters.items(),
                                              key=lambda kv: repr(kv[0])):
            if sig is None or not self._rows_ok(op, parts):
                for tq, entries in parts:
                    before = tq.launches
                    tq._exec_group(op, shape, entries)
                    self.total_launches += tq.launches - before
                continue
            self._exec_rows(op, shape, sig, parts)

    @staticmethod
    def _rows_ok(op: str, parts: list) -> bool:
        if op != "matvec":
            return True
        for _, entries in parts:
            for e in entries:
                flat = np.asarray(e.args[0], dtype=object).reshape(-1)
                if any(int(v) < 0 for v in flat):
                    return False   # host base inversion: solo path
        return True

    def _exec_rows(self, op: str, shape: tuple, sig: tuple,
                   parts: list) -> None:
        total = sum(len(es) for _, es in parts)
        t0 = time.perf_counter()
        with trace_mod.span(f"serve:launch:{op}", width=total,
                            tenants=len(parts)):
            if op == "enc":
                items = []
                for tq, entries in parts:
                    box = tq.box
                    flat = [int(v) for e in entries
                            for v in np.asarray(e.args[0]).reshape(-1)]
                    # blinding draws: tenant's own rng, solo (entry) order
                    rs = [gold.rand_r(box.key, box.rng) for _ in flat]
                    items.append((box.key, flat, rs))
                outs = pbatch.enc_rows(items)
            elif op == "dec":
                items = [(tq.box.key,
                          _ints_of(_cat([e.args[0] for e in entries])))
                         for tq, entries in parts]
                outs = pbatch.dec_rows(items)
            elif op == "add":
                items = [(tq.box.key,
                          _ints_of(_cat([e.args[0] for e in entries])),
                          _ints_of(_cat([e.args[1] for e in entries])))
                         for tq, entries in parts]
                outs = pbatch.add_rows(items)
            else:   # matvec
                items = []
                for tq, entries in parts:
                    Ks = np.stack([np.asarray(e.args[0], dtype=object)
                                   for e in entries])
                    cs = [_ints_of(e.args[1]) for e in entries]
                    items.append((tq.box.key, Ks, cs))
                outs = pbatch.matvec_rows(items)
        wall_ms = (time.perf_counter() - t0) * 1e3
        for (tq, entries), out in zip(parts, outs):
            self._demux(tq, op, shape, entries, out, wall_ms, total)
        self.total_launches += 1
        self.rows_launches += 1
        if len(parts) > 1:
            self.fused_launches += 1
            self.fused_ops += total
        if len(self.fused_log) < self.max_log:
            self.fused_log.append({
                "op": op, "shape": tuple(shape), "limb_bytes": sig[1],
                "tenants": [tq.tenant for tq, _ in parts],
                "widths": [len(es) for _, es in parts]})
        else:
            self.fused_log_dropped += 1
        if self.tracer.enabled:
            self.tracer.add(f"serve:launch:{op}", "serve", t=self.sched.now,
                            wall_ms=wall_ms, op=op, shape=shape, width=total,
                            tenants=len(parts), limb_bytes=sig[1])

    def _demux(self, tq: TenantQueue, op: str, shape: tuple,
               entries: list[_Entry], out, wall_ms: float,
               total: int) -> None:
        """Rebuild exactly the representation + telemetry the tenant's
        solo box call would have produced, then fire the callbacks."""
        known = {} if tq.round is None else {"round": tq.round}
        with trace_mod.span("serve:demux", op=op, tenant=str(tq.tenant),
                            **known):
            box = tq.box
            if tq.counter is not None:
                tq.counter.phase = entries[0].phase
            if op == "enc":
                sizes = [int(np.asarray(e.args[0]).size) for e in entries]
                if tq.counter is not None:
                    tq.counter.bump("enc", len(out))
                if box.batch and len(out) >= box.batch_min:
                    big = CipherTensor.from_ints(box.batch_key(), out)
                else:
                    big = out
                results = _split(big, sizes)
            elif op == "dec":
                sizes = [CoalesceQueue._size(e.args[0]) for e in entries]
                if tq.counter is not None:
                    tq.counter.bump("dec", len(out))
                results = _split(np.array(out, dtype=object), sizes)
            elif op == "add":
                sizes = [CoalesceQueue._size(e.args[0]) for e in entries]
                if tq.counter is not None:
                    tq.counter.bump("mulmod", len(out))
                all_ct = all(isinstance(e.args[0], CipherTensor)
                             and isinstance(e.args[1], CipherTensor)
                             for e in entries)
                if box.batch and all_ct:
                    big = CipherTensor.from_ints(box.batch_key(), out)
                else:
                    big = out
                results = _split(big, sizes)
            else:   # matvec — mirror _matvec_fuses + box.matvec rep rules
                M, N = shape
                E = len(entries)
                if tq.counter is not None:
                    tq.counter.bump("modexp", E * M * N)
                    tq.counter.bump("mulmod", E * M * (N - 1))
                results = []
                if E * M * N >= box.batch_min:
                    ct_in = all(isinstance(e.args[1], CipherTensor)
                                for e in entries)
                    for ints in out:
                        results.append(
                            CipherTensor.from_ints(box.batch_key(), ints)
                            if ct_in else ints)
                else:
                    for e, ints in zip(entries, out):
                        if M * N >= box.batch_min \
                                and isinstance(e.args[1], CipherTensor):
                            results.append(
                                CipherTensor.from_ints(box.batch_key(), ints))
                        else:
                            results.append(ints)
            tq.launches += 1
            if total > 1:
                tq.coalesced_ops += len(entries)
            tq._observe_launch(op, shape, entries, wall_ms,
                               fused=total > 1 or len(entries) > 1)
        for e, res in zip(entries, results):
            e.cb(res)

    def metrics_section(self) -> dict:
        """Engine-level fusion telemetry (stats["serve"] feed)."""
        return {"launches": self.total_launches,
                "rows_launches": self.rows_launches,
                "fused_launches": self.fused_launches,
                "fused_ops": self.fused_ops,
                "fused_log_dropped": self.fused_log_dropped}

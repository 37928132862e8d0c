"""Topology x node-count sweep on the edge-network runtime.

For each {star, ring, hierarchical} x K in {4, 8, 16, 32} configuration,
runs the protocol (plain backend — the bit-exact functional simulation,
so the sweep is fast at K=32) on the simulated network and records

  * iterations until the iterate reaches the MSE target (1.05x the final
    MSE of that K's own converged run — convergence depends on K, not on
    the topology, so the target is shared across topologies at each K), and
  * the simulated wall-clock at that iteration (virtual seconds charged
    by the link models and the per-op cost model — this is where star /
    ring / hierarchical actually differ).

Two extensions ride on the batched CRT gold fast path
(``core/paillier_batch.py``), which removed the per-element Python ``pow``
hot loops that previously capped the sweep at K=64:

  * a larger-N star sweep at K in {64, 128} (N=128), and
  * a ``gold_fastpath`` section: the K=128 star configuration run with the
    REAL gold cipher — batched vs. scalar — plus per-op microbenchmarks,
    recording the measured host wall-clock speedup of the batched path
    over the scalar gold path (values < 1 mean the scalar path is faster
    on this device — expected on CPU-interpret containers, where the
    adaptive dispatcher keeps routing to scalar gold; see
    benchmarks/README.md).  Since the limb-resident pipeline the batched
    runs are preceded by ``paillier_batch.warmup`` (the XLA compiles move
    into a recorded ``warmup_s`` instead of poisoning the first
    measurement) and the section also records ``host_conversions`` —
    zero CipherTensor int<->limb crossings during the warm run.

Emits ``BENCH_topology.json`` plus the harness' CSV rows.  Run directly::

  PYTHONPATH=src python benchmarks/bench_topology.py

or via ``python -m benchmarks.run --only topo``.
"""
from __future__ import annotations

import dataclasses
import json
import random
import time

import numpy as np

from repro.core import cipher_tensor as ct_mod
from repro.core import paillier as gold
from repro.core import paillier_batch as pb
from repro.core import protocol
from repro.core.quantization import QuantSpec
from repro.data.synthetic import make_lasso
from repro.runtime import LinkModel, topology as topo_mod
from repro.runtime.runner import run_on_runtime
from repro.obs import metrics as obs_metrics
try:
    from .common import BENCH_SCHEMA_VERSION, emit, timeit
except ImportError:          # direct script run: python benchmarks/bench_topology.py
    from common import BENCH_SCHEMA_VERSION, emit, timeit

TOPOLOGIES = ("star", "ring", "hierarchical")
EDGE_COUNTS = (4, 8, 16, 32)
M, N = 48, 64            # N divisible by every K in the sweep
LARGE_EDGE_COUNTS = (64, 128)
M_LARGE, N_LARGE = 96, 128
ITERS = 60
SPEC = QuantSpec(delta=1e6, zmin=-8.0, zmax=8.0)
LINK = LinkModel(bytes_per_s=125e6, latency_s=1e-3)
GOLD_KEY_BITS = 128
GOLD_ITERS = 3
GOLD_BATCH = 128
OUT = "BENCH_topology.json"


def _mse_curve(history: np.ndarray, x_true: np.ndarray) -> np.ndarray:
    return np.mean((history - x_true[None, :]) ** 2, axis=1)


def _sweep(rows: list, inst, edge_counts, topologies, iters) -> tuple[list, dict]:
    results, targets = [], {}
    for K in edge_counts:
        cfg = protocol.ProtocolConfig(K=K, lam=0.05, iters=iters,
                                      spec=SPEC, cipher="plain", seed=0)
        for kind in topologies:
            r = run_on_runtime(inst.A, inst.y, cfg,
                               topology=topo_mod.make(kind, K), link=LINK)
            mse = _mse_curve(r.history, inst.x_true)
            if K not in targets:  # convergence is topology-independent
                targets[K] = 1.05 * float(mse[-1])
            hit = np.nonzero(mse <= targets[K])[0]
            it = int(hit[0]) if hit.size else None
            iter_times = r.stats["runtime"]["iter_times"]
            t_hit = iter_times[it] if it is not None else None
            results.append({
                "topology": kind, "edges": K,
                "mse_target": targets[K],
                "iters_to_target": it,
                "virtual_s_to_target": t_hit,
                "virtual_s_total": r.stats["runtime"]["virtual_time"],
                "final_mse": float(mse[-1]),
                "traffic_bytes": r.stats["traffic_bytes"],
                "events": r.stats["runtime"]["events"],
                # driver-independent RunReport core (ops, bytes, MSE curve)
                "report": obs_metrics.report_core(r.stats),
            })
            emit(rows, f"topo_{kind}_K{K}",
                 t_hit if t_hit is not None else float("nan"),
                 derived=f"iters_to_target={it}")
    return results, targets


def _op_micro(rows: list) -> dict:
    """Per-op us/element: batched CRT fast path vs. scalar gold loops."""
    key = gold.keygen(GOLD_KEY_BITS, random.Random(7))
    bk = pb.make_batch_key(key)
    rng = random.Random(8)
    ms = [rng.randrange(1 << 40) for _ in range(GOLD_BATCH)]
    cs = pb.enc_vec(bk, ms, rng)
    ks = [rng.randrange(1 << 21) for _ in range(GOLD_BATCH)]

    def scalar_enc():
        r = random.Random(9)    # one stream, like rand_r_vec inside enc_vec
        return [gold.encrypt_crt(key, m, gold.rand_r(key, r)) for m in ms]

    pairs = {
        "enc": (lambda: pb.enc_vec(bk, ms, random.Random(9)), scalar_enc),
        "dec": (lambda: pb.dec_vec(bk, cs),
                lambda: [gold.decrypt_crt(key, c) for c in cs]),
        "pow_c": (lambda: pb.pow_c_vec(bk, cs, ks),
                  lambda: [pow(c, k, key.n2) for c, k in zip(cs, ks)]),
    }
    out = {}
    for op, (batched, scalar) in pairs.items():
        tb, ts = timeit(batched), timeit(scalar)
        out[op] = {"batched_us_per_el": tb / GOLD_BATCH * 1e6,
                   "scalar_us_per_el": ts / GOLD_BATCH * 1e6,
                   "speedup_vs_scalar": ts / tb,
                   "batched_timing": tb.as_dict(),
                   "scalar_timing": ts.as_dict()}
        emit(rows, f"topo_goldfast_{op}", tb / GOLD_BATCH,
             derived=f"speedup_vs_scalar={ts / tb:.3f}")
    return out


def _reduce_impl_micro(rows: list) -> dict:
    """Montgomery vs Barrett at the kernel boundary, same operands both arms.

    Times ``ops.mulmod`` (always Barrett — the domain enter/leave
    conversions don't amortize over a single product, so there is no
    Montgomery arm to race) plus the variable-exponent ladder
    (``ops.modexp``) and the host-known fixed-window ladder
    (``ops.modexp_fixed``) under each ``reduce_impl``, on the CRT
    half-space modulus the protocol actually launches on (p^2 of the
    ``GOLD_KEY_BITS`` key) at batch ``GOLD_BATCH`` — the K=128 coalesced
    width.  Every arm is checked bit-exact against Python-int ``pow`` on
    the same operands; ``scripts/check_bench_schema.py`` FAILS the bench
    if an arm lost exactness or Montgomery lost the race.
    """
    import jax.numpy as jnp
    from repro.core import bigint as bi
    from repro.kernels import ops as kops

    key = gold.keygen(GOLD_KEY_BITS, random.Random(7))
    pack = pb.make_batch_key(key).vk.pack_p2
    rng = random.Random(11)
    B = GOLD_BATCH
    bases = [rng.randrange(1, pack.m_int) for _ in range(B)]
    exps = [rng.randrange(1 << 21) for _ in range(B)]   # Gamma_2-width
    e_fix = key.n % pack.m_int                          # key-constant width
    b16 = jnp.asarray(bi.from_ints(bases, pack.L16))
    le = max(1, max(bi.n_limbs_for(e) for e in exps))
    e16 = jnp.asarray(bi.from_ints(exps, le))
    want = {
        "mulmod": [b * b % pack.m_int for b in bases],
        "modexp": [pow(b, e, pack.m_int) for b, e in zip(bases, exps)],
        "modexp_fixed": [pow(b, e_fix, pack.m_int) for b in bases],
    }

    def launch(op, impl):
        if op == "mulmod":
            return kops.mulmod(b16, b16, pack, backend="ref")
        if op == "modexp":
            return kops.modexp(b16, e16, pack, backend="ref",
                               reduce_impl=impl)
        return kops.modexp_fixed(b16, e_fix, pack, backend="ref",
                                 reduce_impl=impl)

    out = {"batch": B, "key_bits": GOLD_KEY_BITS,
           "modulus_bits": pack.m_int.bit_length(),
           "ops": {}}
    for op in ("mulmod", "modexp", "modexp_fixed"):
        arms = ("barrett",) if op == "mulmod" \
            else ("barrett", "montgomery")
        per = {}
        for impl in arms:
            t = timeit(lambda: launch(op, impl).block_until_ready(),
                       repeat=5)
            per[impl] = {"wall_s": float(t),
                         "bit_exact": bi.to_ints(launch(op, impl))
                         == want[op],
                         "timing": t.as_dict()}
        entry = dict(per)
        if "montgomery" in per:
            entry["speedup_montgomery_vs_barrett"] = (
                per["barrett"]["wall_s"] / per["montgomery"]["wall_s"])
            emit(rows, f"topo_reduce_impl_{op}",
                 per["montgomery"]["wall_s"] / B,
                 derived="speedup_vs_barrett="
                         f"{entry['speedup_montgomery_vs_barrett']:.3f};"
                         f"bit_exact={per['montgomery']['bit_exact']}")
        out["ops"][op] = entry
    return out


def _gold_protocol_speedup(rows: list, inst) -> dict:
    """K=128 star with the REAL gold cipher: batched vs. scalar wall-clock.

    Before the batched runs, ``paillier_batch.warmup`` pre-compiles the
    limb-kernel executables for exactly the shapes this configuration
    coalesces into (the keygen rng is deterministic, so the pre-derived
    key IS the protocol's key and the jit caches are shared).  The first
    batched run is therefore the *warmup-enabled first run* — what a
    production launch pays after calibration — recorded beside the
    one-off ``warmup_s`` and the warm steady-state number the
    ``speedup_vs_scalar`` uses.  ``host_conversions`` counts
    CipherTensor int<->limb crossings during the warm run: the
    limb-resident pipeline keeps it at zero (conversions happen at the
    plaintext phase boundaries only, inside the kernels' own I/O).
    """
    K = LARGE_EDGE_COUNTS[-1]
    nk = N_LARGE // K
    # same draw sequence as make_box inside run_on_runtime (seed=0)
    key = gold.keygen(GOLD_KEY_BITS, random.Random(0))
    warm_shapes = (K * nk, 2 * K * nk, (K, nk, nk))
    warm = pb.warmup(pb.make_batch_key(key), warm_shapes)
    runs = {}
    conversions = None
    for batched in (True, False):
        cfg = protocol.ProtocolConfig(
            K=K, lam=0.05, iters=GOLD_ITERS, spec=SPEC,
            cipher="gold", key_bits=GOLD_KEY_BITS, seed=0,
            gold_batch=batched)
        walls = []
        for _ in range(2 if batched else 1):
            ct_mod.reset_conversion_stats()
            t0 = time.perf_counter()
            r = run_on_runtime(inst.A, inst.y, cfg,
                               topology=topo_mod.make("star", cfg.K),
                               link=LINK)
            walls.append(time.perf_counter() - t0)
            if batched:
                conversions = dict(ct_mod.CONVERSIONS)
        runs[batched] = (walls, r)
    bit_exact = bool(np.array_equal(runs[True][1].history,
                                    runs[False][1].history))
    speedup = runs[False][0][-1] / runs[True][0][-1]
    emit(rows, f"topo_goldfast_star_K{K}",
         runs[True][0][-1],
         derived=f"speedup_vs_scalar={speedup:.3f};bit_exact={bit_exact}")
    return {
        "edges": K, "iters": GOLD_ITERS,
        "key_bits": GOLD_KEY_BITS,
        "warmup_s": warm["seconds"],
        "warmup_calls": warm["calls"],
        "batched_first_wall_s": runs[True][0][0],   # warmup-enabled first run
        "batched_wall_s": runs[True][0][-1],
        "scalar_wall_s": runs[False][0][-1],
        "speedup_vs_scalar": speedup, "bit_exact": bit_exact,
        # achieved-vs-peak limb-ops priced by the ACTIVE ladder schedule
        # (method + reduce_impl) — the corrected roofline accounting
        "roofline": runs[True][1].stats["runtime"].get("roofline"),
        "host_conversions": conversions,
        "coalesced_ops": runs[True][1].stats["runtime"]["coalesced_ops"],
        "launches": runs[True][1].stats["runtime"]["launches"],
        # full coalescing telemetry from the warm batched run: width
        # histogram + cold/warm launch wall distributions
        "coalesce": runs[True][1].stats["runtime"]["coalesce"],
    }


def run(rows: list) -> None:
    inst = make_lasso(M, N, sparsity=0.1, noise=0.01, seed=3)
    results, targets = _sweep(rows, inst, EDGE_COUNTS, TOPOLOGIES, ITERS)

    # larger-N sweep unlocked by the vectorized gold hot path (star-only:
    # ring/hierarchical event counts grow superlinearly in K and measure
    # the same topology effects already captured at K <= 32)
    inst_l = make_lasso(M_LARGE, N_LARGE, sparsity=0.1, noise=0.01, seed=3)
    results_l, targets_l = _sweep(rows, inst_l, LARGE_EDGE_COUNTS,
                                  ("star",), ITERS)

    gold_fastpath = {
        "batch": GOLD_BATCH,
        "ops": _op_micro(rows),
        "reduce_impl": _reduce_impl_micro(rows),
        "protocol_star": _gold_protocol_speedup(rows, inst_l),
        "note": ("speedup_vs_scalar < 1 means the scalar Python-int path "
                 "is faster on this device (typical on CPU, where the "
                 "adaptive dispatcher keeps scalar gold); the batched path "
                 "is the accelerator-resident form of the paper's "
                 "low-bitwidth GPU transform"),
    }

    with open(OUT, "w") as f:
        json.dump({"schema_version": BENCH_SCHEMA_VERSION,
                   "mse_targets": {str(k): v for k, v in targets.items()},
                   "link": dataclasses.asdict(LINK),
                   "results": results,
                   "large_n": {"M": M_LARGE, "N": N_LARGE,
                               "mse_targets": {str(k): v
                                               for k, v in targets_l.items()},
                               "results": results_l},
                   "gold_fastpath": gold_fastpath}, f, indent=1)


if __name__ == "__main__":
    rows: list = []
    run(rows)
    print("\n".join(rows))
    print(f"wrote {OUT}")

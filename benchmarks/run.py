"""Benchmark harness: one function per paper table/figure.

``python -m benchmarks.run [--only fig6,tab2,...]`` prints
``name,us_per_call,derived`` CSV rows (and tees them per-bench as it goes).
A bench that raises leaves a ``<key>_ERROR`` row, the others still run,
and the process exits nonzero.
``--help`` / ``--list`` show every registered bench; benchmarks/README.md
documents what each one reproduces, its expected runtime and its output
schema.
"""
from __future__ import annotations

import argparse
import sys
import time

# (key, module, one-line description) — the registry of record; --help and
# --list render it, and tests/test_docs.py asserts benchmarks/README.md
# documents every key.
BENCHES = [
    ("fig5", "bench_quant",
     "quantization precision loss vs Delta (paper Fig. 5)"),
    ("fig6", "bench_mse",
     "MSE: Cen/Dis/DP/3P-ADMM (+beyond-paper variants) (Fig. 6)"),
    ("fig7", "bench_sparsity",
     "sparsity x edge-count convergence sweep (Fig. 7)"),
    ("tab2", "bench_throughput",
     "ModMult/ModExp/EP throughput by key length (Table II)"),
    ("fig8", "bench_total_time",
     "T_pre / T_total by scheme and key length (Fig. 8)"),
    ("tab345", "bench_latency",
     "per-node latency decomposition (Tables III-V)"),
    ("fig10", "bench_power_grid",
     "power-network reconstruction AUROC/AUPRC (Fig. 10)"),
    ("roofline", "bench_roofline",
     "roofline rows from the dry-run report (deliverable g)"),
    ("kernels", "bench_kernels",
     "limb-kernel micro: Barrett vs Montgomery ladders, bit-exact gate"),
    ("topo", "bench_topology",
     "topology x K sweep (K<=128) + batched-gold speedup (beyond-paper)"),
    ("workloads", "bench_workloads",
     "ADMM workload zoo x K sweep through the protocol (beyond-paper)"),
    ("serving", "bench_serving",
     "multi-tenant engine: cross-tenant coalescing vs sequential "
     "(beyond-paper)"),
]


def _registry_lines() -> list[str]:
    return [f"  {key:<9} {mod:<18} {desc}" for key, mod, desc in BENCHES]


def main() -> None:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description="Run the paper-reproduction benchmark suite.",
        epilog="registered benches (see benchmarks/README.md for what each\n"
               "reproduces, expected runtimes and output schemas):\n\n"
               + "\n".join(_registry_lines()))
    ap.add_argument("--only", "--bench", dest="only", default=None,
                    metavar="KEYS",
                    help="comma-separated bench keys, e.g. fig5,tab2,topo")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-dims mode for benches that support it "
                         "(currently: kernels, workloads, serving) — "
                         "CI-sized smoke runs")
    ap.add_argument("--list", action="store_true",
                    help="print the registered bench keys and exit")
    args = ap.parse_args()
    if args.list:
        print("\n".join(_registry_lines()))
        return
    want = set(args.only.split(",")) if args.only else None
    unknown = (want or set()) - {k for k, _, _ in BENCHES}
    if unknown:
        ap.error(f"unknown bench keys {sorted(unknown)} "
                 f"(--list shows the registry)")

    import importlib
    import inspect
    rows: list[str] = ["name,us_per_call,derived"]
    print(rows[0])
    failed = []
    for key, mod_name, _ in BENCHES:
        if want and key not in want:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        t0 = time.time()
        before = len(rows)
        kw = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kw["smoke"] = True
        try:
            mod.run(rows, **kw)
        except Exception as e:  # noqa: BLE001 — record it, run the rest
            rows.append(f"{key}_ERROR,0,{type(e).__name__}:{e}")
            failed.append(key)
        for r in rows[before:]:
            print(r, flush=True)
        _ledger_rows(key, rows[before:])
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benches raised: {', '.join(failed)}")


def _ledger_rows(bench: str, rows: list[str]) -> None:
    """Append each CSV row to the run-history ledger (repro.obs.ledger)
    so the regression sentinel can band-check us_per_call across runs.
    Best-effort: a disabled ledger or an unparsable row is skipped."""
    try:
        from repro.obs import ledger
    except Exception:  # noqa: BLE001 — benches may run without src on path
        return
    if ledger.ledger_path() is None:
        return
    for row in rows:
        parts = row.split(",", 2)
        if len(parts) != 3 or parts[0].endswith("_ERROR"):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        ledger.append(ledger.record_bench_row(bench, parts[0], us, parts[2]))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Readings that the correctness limits are set from, many seeds in one
process (the benchmark's own runs do not run this).

    python3 perfbench/readings.py --workload fig6_k3_2048.solo \
        --seconds 10 --seeds 11 12 13 [--control]

For each seed it runs the cell's traffic for a short window and prints one
JSON line with the numbers the check compares.  ``--control`` runs the
program at the next precision below the configuration's: the quantizer's
Delta cut to 2^24, float32's fraction bits for float64's.  Its readings
have to fail the limits.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROL_DELTA = float(2 ** 24)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from perfbench import bench, check, drive

    bench.own_compile_cache()
    bm = bench.load_benchmark()
    wl = bench._by_name(bm["workloads"], args.workload, "workload")
    cfg = bench.load_config(bm, wl["config"])
    if args.control:
        cfg = dict(cfg, delta=CONTROL_DELTA)
    mix = bench.load_traffic(wl["traffic"])
    for seed in args.seeds:
        deps, win, rec, _ = drive.run_traffic(cfg, mix, seed, args.seconds)
        picked = drive.sample_rounds(deps, win, seed, bench.CHECK_ROUNDS)
        verdict = check.check(cfg, deps, rec, picked,
                              check.keys_of(cfg, deps))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": args.control,
            "delta": cfg["delta"], "rounds": [len(d.round_ends) for d in deps],
            "correct": verdict["correct"],
            "answers_checked": verdict["answers_checked"],
            **{k: v["value"] for k, v in verdict["numbers"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

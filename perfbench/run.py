#!/usr/bin/env python3
"""Run one cell of the private-ADMM chip benchmark once.

    python3 perfbench/run.py --workload fig6_k3_2048.solo --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json``; the configuration (``perfbench/configs/<name>.json``),
the mix (``perfbench/traffic/<name>.json``) and each metric's reader
(``perfbench/metrics/<name>.py``) are files of their own.  A run sets up
the deployments (keys from the configuration's key seeds, data and
blinding from ``--seed``), runs the init and share phases and one warm
round, measures complete rounds for at least ``--seconds``, checks the
answers against the plain reference, and prints one JSON line last on
standard output.  ``--trace 1`` profiles the window and reports the
per-layer metrics instead of the end-to-end ones.

Exits nonzero, with no result line, without a TPU, with fewer chips than
the cell asks for, on a device kind missing from ``perfbench/peaks.json``,
or without the program's sources beside it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="cell name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="makes the data and the blinding factors")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window, report per-layer metrics")
    return ap.parse_args(argv)


def main(argv=None, *, accelerator: bool = True) -> int:
    args = parse(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"perfbench: no program sources under {src}", file=sys.stderr)
        return 2
    for path in (ROOT, src):
        if path not in sys.path:
            sys.path.insert(0, path)
    from perfbench import bench
    return bench.run(args, t0=T0, accelerator=accelerator)


if __name__ == "__main__":
    sys.exit(main())

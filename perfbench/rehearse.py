"""CPU rehearsal of the benchmark at a tiny key and problem size.

Builds a benchmark root in a scratch directory that holds this package's
traffic mixes and metric readers, tiny configurations of the same
deployments and a ``BENCHMARK.json`` naming them, then drives cells
through ``bench.run`` with the look for a chip skipped.  Nothing it
reports is a device measurement.  The tests under ``perfbench/tests``
use it.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))

#: a deployment of the same shape as the chip cells' at a CPU-sized key
TINY = {
    "name": "tiny", "source": "rehearsal", "problem": "lasso",
    "M": 3, "N": 9, "K": 3, "key_bits": 128, "delta": 1e15,
    "zmin": -16.0, "zmax": 16.0, "rho": 1.0, "lam": 1.0, "iters": 100,
    "cipher": "gold", "gold_batch": True, "crt": True,
    "key_seeds": [11, 12, 13, 14], "sparsity": 0.1, "noise": 0.01,
    "reduced": ["M", "N", "key_bits"],
    "limits": {"wrong_answers": 0, "x_gap": 1e-6},
}


def make_root(path: str, config: dict | None = None,
              traffics=("solo", "tenants4")) -> str:
    """A benchmark root under ``path`` with the tiny configuration, one
    cell per traffic mix, and this package's mixes and readers."""
    config = dict(TINY if config is None else config)
    bench_dir = os.path.join(path, "perfbench")
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(bench_dir, sub),
                        dirs_exist_ok=True)
    os.makedirs(os.path.join(bench_dir, "configs"), exist_ok=True)
    cfg_file = f"perfbench/configs/{config['name']}.json"
    with open(os.path.join(path, cfg_file), "w") as f:
        json.dump(config, f)
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = [f"{config['name']}.{t}" for t in traffics]
    bm = {
        "configs": [{"name": config["name"], "file": cfg_file}],
        "workloads": [{"name": c, "config": config["name"],
                       "traffic": c.split(".", 1)[1], "chips": 1}
                      for c in cells],
        # every metric of the real benchmark, reported in every cell
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in real["per_layer"]],
    }
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return path


def program_on_path() -> None:
    """Make the program's package importable, as ``run.py`` does."""
    src = os.path.abspath(os.path.join(HERE, os.pardir, "src"))
    if src not in map(os.path.abspath, sys.path):
        sys.path.insert(0, src)


def run_cell(root: str, workload: str, seed: int = 1, seconds: float = 0.5,
             trace: int = 0) -> tuple[int, dict | None, str]:
    """``bench.run`` on the CPU; returns (exit code, result line, stderr)."""
    from . import bench
    program_on_path()
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench.run(args, t0=time.perf_counter(), accelerator=False,
                       root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

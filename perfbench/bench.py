"""One run of one cell: discovery by name, the window, the check, the line.

Everything particular to a configuration, a traffic mix or a metric lives
in a file of its own that this module finds by the name that
``BENCHMARK.json`` gives:

  perfbench/configs/<config>.json   the deployment (the entry's ``file``)
  perfbench/traffic/<traffic>.json  the mix, read by ``drive.run_traffic``
  perfbench/metrics/<metric>.py     ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_ROUNDS = 2          # rounds per deployment whose answers are checked


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(bm: dict, name: str, root: str = ROOT) -> dict:
    entry = _by_name(bm["configs"], name, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "perfbench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``perfbench/metrics/<name>.py``."""
    path = os.path.join(root, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bm: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


# ---------------------------------------------------------------------------
# what the readers see
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """A finished run, as the metric readers see it."""
    cfg: dict
    traffic: dict
    setup_s: float
    window: object               # drive.Window
    deployments: list
    launches: int                # coalescer / engine launches in the window
    rounds: int                  # deployment rounds ended in the window
    work_ops: int                # counted int8 work of those rounds
    trace: dict | None           # tracing.reduce(...) of the window
    peaks: dict | None

    @property
    def window_s(self) -> float:
        return self.window.end - self.window.start

    @property
    def engine(self) -> bool:
        return bool(self.traffic["engine"])

    @property
    def engine_rounds(self) -> float:
        """Rounds of the whole fleet: deployment rounds over tenants."""
        return self.rounds / self.traffic["tenants"]


def window_work(rec, deps, win, keys) -> int:
    """Counted work (``work.op_ops``) of every operation of the rounds
    that ended inside the window."""
    from . import work
    inside = {d.index: {t for t, at in d.round_ends if at > win.start}
              for d in deps}
    total = 0
    for i, t, op, args, _ in rec.ops:
        if t in inside[i]:
            total += work.op_ops(op, args, keys[i].n, keys[i].lam)
    return total


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def own_compile_cache() -> None:
    """The benchmark's persistent compile cache: one fixed directory inside
    the checkout, never evicted (an evicting cache that the host's
    environment sets up loses entries), and no entry in the program's run
    history."""
    import jax
    from repro.kernels import compile_cache
    os.environ["REPRO_LEDGER"] = "off"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_max_size", -1)
    compile_cache.enable()


def _fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 1


def _device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(args, *, t0: float, accelerator: bool = True,
        root: str = ROOT) -> int:
    """One run; returns the process exit code.

    ``accelerator=False`` skips the look for a chip and the persistent
    compile cache (CPU rehearsals): no device metric is then reported.
    ``root`` holds ``BENCHMARK.json`` and the files it names."""
    bm = load_benchmark(root)
    wl = _by_name(bm["workloads"], args.workload, "workload")
    cfg = load_config(bm, wl["config"], root)
    traffic = load_traffic(wl["traffic"], root)
    import jax
    from . import check, drive, tracing, work
    from .compile_clock import CompileClock

    devs = jax.devices()
    device = _device_info(devs)
    peaks = None
    if accelerator:
        if device["platform"] == "cpu":
            return _fail("no accelerator found")
        if device["count"] < wl["chips"]:
            return _fail(f"{device['count']} chips, the cell needs "
                         f"{wl['chips']}")
        try:
            peaks = work.peaks(device["kind"])
        except KeyError as e:
            return _fail(str(e))
        own_compile_cache()

    tracer = None
    if args.trace and accelerator:
        tracer = tracing.WindowTrace(tempfile.mkdtemp(prefix="perfbench-"))
    setup_clock = CompileClock().__enter__()

    def on_open():
        setup_clock.__exit__()
        if tracer:
            tracer.start()
    deps, win, rec, _ = drive.run_traffic(
        cfg, traffic, args.seed, args.seconds, on_open=on_open,
        on_close=tracer.stop if tracer else (lambda: None))
    setup_s = win.start - t0
    memory_peak = _memory_peak(devs)

    # the check runs once the window has closed and the peak is read
    t_check = time.perf_counter()
    picked = drive.sample_rounds(deps, win, args.seed, CHECK_ROUNDS)
    keys = check.keys_of(cfg, deps)
    verdict = check.check(cfg, deps, rec, picked, keys)
    check_s = time.perf_counter() - t_check
    red = tracer.reduce() if tracer is not None else None
    run_ = Run(cfg=cfg, traffic=traffic, setup_s=setup_s, window=win,
               deployments=deps, launches=win.launches1 - win.launches0,
               rounds=win.rounds, work_ops=window_work(rec, deps, win, keys),
               trace=red, peaks=peaks)

    metrics = {}
    for m in metrics_for(bm, args.workload, bool(args.trace)):
        value = load_reader(m["name"], root)(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = memory_peak
    line = {"correct": verdict["correct"], "attempted": win.rounds,
            "failed": 0 if verdict["correct"] else win.rounds,
            "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = red["breakdown"]
    line["checks"] = verdict["numbers"]

    print(f"perfbench: setup {setup_s:.3f} s with {setup_clock.compiles} "
          f"backend compiles ({setup_clock.seconds:.3f} s)", file=sys.stderr)
    print(f"perfbench: window {win.end - win.start:.3f} s, {win.rounds} "
          f"rounds, {win.compiles} backend compiles inside "
          f"({win.compile_s:.3f} s), check "
          f"{check_s:.3f} s, {verdict['answers_checked']} answers checked "
          f"in rounds {verdict['rounds_checked']}", file=sys.stderr)
    for name, v in verdict["numbers"].items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0

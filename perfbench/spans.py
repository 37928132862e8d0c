#!/usr/bin/env python3
"""Program spans in a traced window: device time by protocol op, and idle
time by the host step that caused it.

The program marks its own steps with spans on the profiler's clock
(``repro.obs.trace.span``): ``launch:<op>`` and ``serve:launch:<op>``
around each coalesced launch, ``serve:demux``, ``host:*`` around the
int<->limb conversions, with ``host:fetch`` around each copy of a device
array to the host, and ``driver:*`` around the protocol driver's own
steps.  They land on the host plane beside the TPU runtime's enqueue and
completion events that ``perfbench/tracing.py`` pairs into executions, so
both share one clock.  :func:`attribute` reduces them:

  busy_by_span  device seconds of each execution, charged to the
                innermost program span that encloses its enqueue
  busy_under    the same, charged to every span name that encloses it
  idle_by_span  each idle moment of the traced extent, charged to the
                innermost program span open at that moment
  self_by_span  host seconds in which each span name is the innermost
                program span open

Time outside every program span goes to ``"(none)"``.  The readers of
the ``*_ms_per_round`` metrics in ``perfbench/metrics`` read the result
from ``run.trace["spans"]``.

Run as a script it makes one ``run.py --trace 1`` run with the spans
wired in for that process only, since the accepted benchmark does not call
:func:`attribute` yet: its readings come from outside the harness.  It
prints the run's result line, with the readers of :data:`METRICS` and
``breakdown["idle_by_span"]``, then one JSON line with the whole
attribution; ``--fixture`` also writes the window's runtime events and
program spans to a file (the test fixture under ``perfbench/tests/data``
was made so):

    python3 perfbench/spans.py --workload fig6_k3_1024.solo --seed 7 \
        --seconds 51 [--fixture out.json]
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIXES = ("launch:", "serve:launch:", "serve:demux", "host:", "driver:")
NONE = "(none)"
TOP = 10
#: readers of ``perfbench/metrics`` that read this reduction
METRICS = tuple(f"{m}.{v}" for m in (
    "enc_ms_per_round", "dec_ms_per_round", "matvec_ms_per_round",
    "host_path_ms_per_round", "driver_ms_per_round")
    for v in ("solo", "serve"))


def program_spans(host: list) -> list:
    """The program's spans among the host events ``[name, start, dur]``."""
    return [h for h in host if h[0].startswith(PREFIXES)]


def segments(spans: list) -> list:
    """``[start, end, names]``: the timeline cut wherever the set of open
    program spans changes, ``names`` the open spans' names from the
    outermost in; time with no span open is left out."""
    marks = []
    for i, (_, s, d) in enumerate(spans):
        marks.append((s, 1, -d, i))          # at one instant: ends first,
        marks.append((s + d, 0, 0, i))       # then the longer start
    marks.sort()
    out, stack, prev = [], [], None
    for t, start, _, i in marks:
        if stack and t > prev:
            out.append([prev, t, tuple(spans[j][0] for j in stack)])
        prev = t
        if start:
            stack.append(i)
        else:
            stack.remove(i)
    return out


def _open_at(segs: list, starts: list, t: float) -> tuple:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and segs[i][0] <= t < segs[i][1]:
        return segs[i][2]
    return ()


def _idle(ex: list, t0: float, t1: float) -> list:
    """``[start, end]`` of every moment in [t0, t1] with no execution."""
    out, prev = [], t0
    for _, s, e in ex:
        if s > prev:
            out.append([prev, s])
        prev = max(prev, e)
    if t1 > prev:
        out.append([prev, t1])
    return out


def attribute(host: list) -> dict | None:
    """Busy, idle and self seconds by program span of one traced window;
    ``None`` when the trace holds no execution."""
    from perfbench import tracing
    ex = tracing.executions(host)
    if not ex:
        return None
    enq = [h[1] for h in host if h[0] == tracing.ENQUEUE][:len(ex)]
    segs = segments(program_spans(host))
    starts = [s for s, _, _ in segs]
    busy, under, unlaunched = {}, {}, 0
    for (name, s, e), t in zip(ex, enq):
        names = _open_at(segs, starts, t)
        secs = max(0.0, e - s) * 1e-9
        inner = names[-1] if names else NONE
        busy[inner] = busy.get(inner, 0.0) + secs
        for n in set(names) or (NONE,):
            under[n] = under.get(n, 0.0) + secs
        launches = sum(n.startswith(("launch:", "serve:launch:"))
                       for n in names)
        if name in tracing.LADDER_PROGRAMS and launches != 1:
            unlaunched += 1
    idle = {}
    t0 = min(h[1] for h in host)
    t1 = max(h[1] + h[2] for h in host)
    j = 0
    for a, b in _idle(ex, t0, t1):
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, names = segs[k]
            over = min(b, e) - max(a, s)
            if over > 0:
                idle[names[-1]] = idle.get(names[-1], 0.0) + over * 1e-9
                covered += over
            k += 1
        if b - a > covered:
            idle[NONE] = idle.get(NONE, 0.0) + (b - a - covered) * 1e-9
    own = {}
    for s, e, names in segs:
        own[names[-1]] = own.get(names[-1], 0.0) + (e - s) * 1e-9
    return {"busy_by_span": busy, "busy_under": under, "idle_by_span": idle,
            "self_by_span": own, "idle_s": sum(idle.values()),
            "ladder_unlaunched": unlaunched}


def top_idle(red: dict) -> list:
    """``[name, seconds]`` of the ``TOP`` span names with the most idle
    seconds, then ``"(none)"``."""
    named = sorted(((k, v) for k, v in red["idle_by_span"].items()
                    if k != NONE), key=lambda kv: -kv[1])[:TOP]
    return [list(kv) for kv in named] + \
        [[NONE, red["idle_by_span"].get(NONE, 0.0)]]


def host_path_s(red: dict) -> float:
    """Self seconds of the host-path spans, the device fetches left out."""
    return sum(v for k, v in red["self_by_span"].items()
               if k.startswith("host:") and k != "host:fetch")


def driver_s(red: dict) -> float:
    """Self seconds of the protocol driver's spans."""
    return sum(v for k, v in red["self_by_span"].items()
               if k.startswith("driver:"))


def per_round(run, engine: bool, value) -> float | None:
    """``value(reduction)`` seconds as milliseconds per round (per fleet
    round under the engine); ``None`` for the other mix, or without a
    trace, its span reduction, or rounds."""
    if run.engine != engine or run.trace is None or not run.rounds \
            or run.trace.get("spans") is None:
        return None
    rounds = run.engine_rounds if engine else run.rounds
    return 1e3 * value(run.trace["spans"]) / rounds


# ---------------------------------------------------------------------------
# the script: run.py --trace 1 with the spans wired in, outside the harness
# ---------------------------------------------------------------------------

def span_events(pd) -> list:
    """``[name, start_ns, dur_ns, attrs]`` of every program span."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [[e.name, float(e.start_ns), float(e.duration_ns),
                         dict(e.stats)]
                        for e in line.events if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda h: h[1])


FIXTURE_NOTE = (
    "a short traced window (TRACE_ONLY_HOST) with the program's spans: host "
    "holds the TPU runtime's enqueue/completion marks and the jit dispatches "
    "as [name, start_ns, dur_ns]; spans holds the program's spans as [name, "
    "start_ns, dur_ns, attrs]; written by perfbench/spans.py --fixture")


def main(argv=None) -> int:
    """One ``run.py --trace 1`` run (``bench.run``: set-up, window, check,
    result line) with what the accepted benchmark lacks put in for this
    process only: ``WindowTrace.reduce`` also calls :func:`attribute`,
    and the traced metrics include the readers of :data:`METRICS`.  Then
    one more line with the whole attribution; ``--fixture`` writes the
    window's runtime events and program spans to a file."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", help="write the window's events here")
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from perfbench import bench, drive, tracing
    got = {}

    class SpanTrace(tracing.WindowTrace):
        def reduce(self):
            from jax.profiler import ProfileData
            paths = glob.glob(os.path.join(self.logdir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            pd = ProfileData.from_file(paths[0]) if paths else None
            red = super().reduce()
            if red is not None:
                host = tracing.extract(pd)
                got["spans"] = red["spans"] = attribute(host)
                red["breakdown"]["idle_by_span"] = top_idle(red["spans"])
                got["events"] = host, span_events(pd)
                got["window_s"] = self.t_stop - self.t_start
            return red

    def run_traffic(*a, **kw):
        out = real_traffic(*a, **kw)
        got["rounds"] = out[1].rounds
        return out

    def metrics_for(bm, workload, trace):
        extra = [{"name": n, "unit": "ms"} for n in METRICS] if trace else []
        return real_metrics(bm, workload, trace) + extra

    real_traffic, real_metrics = drive.run_traffic, bench.metrics_for
    tracing.WindowTrace, drive.run_traffic = SpanTrace, run_traffic
    bench.metrics_for = metrics_for
    rc = bench.run(argparse.Namespace(**vars(args), trace=1), t0=T0)
    if "spans" not in got:
        return rc or 1
    if args.fixture:
        import jax
        host, program = got["events"]
        keep = [h for h in host if h[0] in (tracing.ENQUEUE, tracing.DONE)
                or h[0].startswith(tracing.DISPATCH)]
        with open(args.fixture, "w") as f:
            json.dump({"cell": args.workload,
                       "device": jax.devices()[0].device_kind,
                       "tpu_trace_mode": tracing.TRACE_MODE,
                       "window_s": got["window_s"], "rounds": got["rounds"],
                       "note": FIXTURE_NOTE, "host": keep, "spans": program},
                      f)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "rounds": got["rounds"], "spans": got["spans"]}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""Profiler trace of the measured window, and its reduction to metrics.

The ladders are ``while`` loops of millions of steps, and the TPU
profiler's device tracer writes an event for every operation of every
step: a second of ladder work fills the profiler's 2 GB and the rest of
the window, program events included, is dropped.  So the window is
traced in the profiler's ``TRACE_ONLY_HOST`` mode, where the TPU
runtime still records, for every program execution, its enqueue
(``tpu::System::Execute``) inside the host's dispatch of the jitted
function (``PjitFunction(<name>)``) and the device's completion
(``tpu::System::Execute=>Done``).  One device runs its programs in
order, so execution i occupies the device from the later of its enqueue
and the previous completion to its own completion.  Set against the
device's own module events of a short traced window, this reads the
ladders' device time to within a tenth of a percent; a tiny program is
charged the runtime's completion latency (~0.2 ms) instead of its few
microseconds.  The reduction is:

  busy_s     the union of the execution intervals, over the window
  idle share 1 - busy_s / window_s
  ladder_s   seconds of the executions of the ladder programs
  breakdown  seconds per program, and the longest idle gaps named by the
             host event that covers most of each and the program that
             the gap waited for
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import time

#: jitted functions that run the modular-exponentiation ladders, read by
#: hand off a TPU trace: the per-key CRT fixed-exponent ladders of enc's
#: r^n and dec's c^lam (``paillier_batch.modexp_crt_limbs[_in]``), the
#: per-element matvec ladder (``paillier_batch.matvec_many``) and the
#: multi-modulus rows ladder (``kernels.ops.modexp_rows``)
LADDER_PROGRAMS = ("fixed_body", "powed_ct_body", "modexp2d_win4")
TRACE_MODE = "TRACE_ONLY_HOST"
ENQUEUE = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
DISPATCH = "PjitFunction("
TOP = 10


class WindowTrace:
    """Starts the JAX profiler when the window opens and stops it when it
    closes; :meth:`reduce` reads the trace and deletes it.  The Python
    tracer stays off: it would slow the host code being measured."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.advanced_configuration = {"tpu_trace_mode": TRACE_MODE}
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self):
        """Metrics of the window; ``None`` when the trace holds none."""
        try:
            from jax.profiler import ProfileData
            paths = glob.glob(os.path.join(self.logdir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not paths:
                return None
            events = extract(ProfileData.from_file(paths[0]))
            return reduce_events(events, self.t_stop - self.t_start)
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


def extract(pd) -> list:
    """Every host event of the trace as ``[name, start_ns, dur_ns]``,
    sorted by start."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events]
    return sorted(host, key=lambda h: h[1])


def executions(host: list) -> list:
    """``[program, start_ns, end_ns]`` of every program execution: the
    i-th enqueue pairs with the i-th completion, and is named by the
    innermost jitted-function dispatch that encloses it."""
    dispatch = [h for h in host if h[0].startswith(DISPATCH)]
    starts = [h[1] for h in dispatch]
    enq = [h[1] for h in host if h[0] == ENQUEUE]
    done = [h[1] for h in host if h[0] == DONE]
    out, prev = [], float("-inf")
    for t_enq, t_done in zip(enq, done):
        name = "?"
        for j in range(bisect.bisect_right(starts, t_enq) - 1, -1, -1):
            n, s, d = dispatch[j]
            if s <= t_enq <= s + d:
                name = n[len(DISPATCH):-1]
                break
        start = max(t_enq, prev)
        out.append([name, start, t_done])
        prev = t_done
    return out


def reduce_events(host: list, window_s: float) -> dict | None:
    """Busy and ladder seconds, and the breakdown, of one traced window."""
    ex = executions(host)
    if not ex:
        return None
    busy = sum(max(0.0, e - s) for _, s, e in ex) * 1e-9
    per_program: dict = {}
    for name, s, e in ex:
        per_program[name] = per_program.get(name, 0.0) + max(0.0, e - s) * 1e-9
    ladder = sum(v for k, v in per_program.items() if k in LADDER_PROGRAMS)
    gaps = sorted(([e0, s1, nxt] for (_, _, e0), (nxt, s1, _)
                   in zip(ex, ex[1:]) if s1 > e0),
                  key=lambda g: g[0] - g[1])[:TOP]
    named = [[f"{_covering(host, e0, s1)} -> {nxt}", (s1 - e0) * 1e-9]
             for e0, s1, nxt in gaps]
    device_ops = sorted(per_program.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy, "window_s": window_s, "ladder_s": ladder,
            "executions": len(ex),
            "breakdown": {"device_ops": [list(kv) for kv in device_ops],
                          "idle_gaps": named}}


def _covering(host: list, s: float, e: float) -> str:
    """The host event, other than the runtime's own enqueue and completion
    marks, that overlaps [s, e] the most."""
    best, most = "host", 0.0
    for name, hs, hd in host:
        if hs > e:
            break
        if name in (ENQUEUE, DONE):
            continue
        over = min(e, hs + hd) - max(s, hs)
        if over > most:
            best, most = name, over
    return best

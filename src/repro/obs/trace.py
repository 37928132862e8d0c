"""Zero-dependency span tracer for the encrypted ADMM stack.

A :class:`Span` is one structured event on the run's timeline: a protocol
phase, a crypto op, a coalesced kernel launch, a network message, a
dispatch decision, a streaming re-share, a secure-aggregation round, or
a churn event (leave / rejoin / fail injection / failure detection /
recycled-update skip), or a health alert fired by a
:class:`repro.obs.health.HealthMonitor` watcher.
Spans carry the *virtual-clock* start/duration (the runtime's simulated
seconds) plus, for real kernel launches, the host wall time of the call —
the two clocks are deliberately separate fields so determinism pins can
compare span streams with the wall clock excluded.

Two tracer implementations share the interface:

* :class:`Tracer` — records spans in order; ``signature()`` returns the
  deterministic view (wall-clock fields stripped) that
  ``tests/test_runtime.py`` pins byte-identical across seeded runs, and
  ``obs.chrome_trace`` exports the full view for ``chrome://tracing``.
* :class:`NullTracer` — the default everywhere; ``enabled`` is False and
  every method is a no-op, so the untraced hot path pays one attribute
  check per potential span and nothing else.

Instrumented call sites guard with ``if tracer.enabled:`` before building
attr dicts, keeping the disabled path allocation-free.

:func:`span` is the other view: a span on the *profiler's* clock, the one
the device's executions are recorded on.  It wraps
``jax.profiler.TraceAnnotation``, so it costs a few microseconds and
records only while a ``jax.profiler`` session runs; the session holds the
spans and writes them out with the device trace.  Where a boundary also
has a virtual-clock span, both views give it the same name
(``launch:<op>``, ``serve:launch:<op>``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

#: the closed set of span categories; chrome_trace gives each its own lane
CATEGORIES = ("phase", "crypto_op", "launch", "message", "dispatch",
              "reshare", "agg", "churn", "alert", "serve")


@dataclasses.dataclass
class Span:
    """One structured trace event.

    ``t``/``dur`` are virtual-clock seconds; ``wall_ms`` is measured host
    milliseconds of a launch call: its dispatch, plus any conversion that
    waits for the device (launches only, ``None`` elsewhere; not kernel
    time, which :func:`span` and the profiler give).  ``attrs``
    hold the category-specific payload (op, shape, bytes, edge,
    coalesce width, backend, ...) as JSON-safe scalars.
    """

    name: str
    cat: str
    t: float
    dur: float = 0.0
    wall_ms: float | None = None
    attrs: dict = dataclasses.field(default_factory=dict)

    def key(self) -> tuple:
        """Timing-free identity (used for counting/diffing spans)."""
        return (self.name, self.cat, tuple(sorted(self.attrs.items())))

    def as_dict(self) -> dict:
        d = {"name": self.name, "cat": self.cat,
             "t": self.t, "dur": self.dur, "attrs": dict(self.attrs)}
        if self.wall_ms is not None:
            d["wall_ms"] = self.wall_ms
        return d


class Tracer:
    """Collects :class:`Span`s in emission order."""

    enabled = True

    def __init__(self):
        self.spans: list[Span] = []

    def add(self, name: str, cat: str, t: float, dur: float = 0.0,
            wall_ms: float | None = None, **attrs) -> None:
        if cat not in CATEGORIES:
            raise ValueError(f"unknown span category {cat!r} "
                             f"(one of {CATEGORIES})")
        self.spans.append(Span(name=name, cat=cat, t=t, dur=dur,
                               wall_ms=wall_ms, attrs=attrs))

    # -- views -----------------------------------------------------------
    def signature(self) -> list[tuple]:
        """The deterministic span stream: everything except wall-clock.

        Virtual times stay in — the scheduler's clock is seeded, so two
        identical runs must agree on them — while ``wall_ms`` (host
        timing, never reproducible) is excluded.  This is the object the
        determinism tests pin equal across repeated seeded runs.
        """
        return [(s.name, s.cat, s.t, s.dur, tuple(sorted(s.attrs.items())))
                for s in self.spans]

    def as_dicts(self) -> list[dict]:
        return [s.as_dict() for s in self.spans]

    def by_cat(self, cat: str) -> list[Span]:
        return [s for s in self.spans if s.cat == cat]

    def count(self, cat: str) -> int:
        return sum(1 for s in self.spans if s.cat == cat)


class NullTracer:
    """Disabled tracer: the overhead-free default path."""

    enabled = False
    spans: tuple = ()

    def add(self, *a, **kw) -> None:
        pass

    def signature(self) -> list:
        return []

    def as_dicts(self) -> list:
        return []

    def by_cat(self, cat: str) -> list:
        return []

    def count(self, cat: str) -> int:
        return 0


#: shared no-op instance — safe to alias anywhere (it holds no state)
NULL = NullTracer()


def as_tracer(trace) -> "Tracer | NullTracer":
    """Normalize a ``trace`` knob: Tracer instance, truthy, or falsy."""
    if isinstance(trace, (Tracer, NullTracer)):
        return trace
    return Tracer() if trace else NULL


@functools.cache
def _annotation():
    from jax.profiler import TraceAnnotation   # only once a span is made
    return TraceAnnotation


def span(name: str, **attrs):
    """Context manager: one span named ``name`` on the profiler's clock.

    ``attrs`` (ints, bools or strings) arrive in the profiler trace as
    the event's stats.  Spans go at call level, never inside a jitted
    function or per element; docs/observability.md lists the names."""
    return _annotation()(name, **attrs)


def spans_from_dicts(dicts: Iterable[dict]) -> list[Span]:
    """Rehydrate spans exported by :meth:`Tracer.as_dicts`."""
    return [Span(name=d["name"], cat=d["cat"], t=d["t"],
                 dur=d.get("dur", 0.0), wall_ms=d.get("wall_ms"),
                 attrs=dict(d.get("attrs", {})))
            for d in dicts]

"""Drives one cell's traffic through the program's own entry points.

A traffic mix is a data file (``perfbench/traffic/<name>.json``) read by
:func:`run_traffic`, the one generator:

  tenants    deployments run at once, each with its own key and data
  engine     false: one deployment through ``runtime.runner.build_runtime``
             (the runner behind ``repro.launch.edge_sim``); true: every
             deployment admitted to ``serve.protocol_engine.ProtocolEngine``
             (behind ``repro.launch.serve_sim``)
  admission  the engine's admission policy
  arrival    "closed": every deployment runs its rounds back to back

Round boundaries are read through the health-monitor hook that both entry
points accept (``observe_round`` fires once the master holds a round's
decrypted floats); a deployment is stopped at the first round that ends
after the window closes, through ``MasterActor.cancel_after``.  The
answers of every homomorphic operation are recorded at the crypto queue's
``submit`` boundary for the correctness check, which reads them once the
window has closed.
"""
from __future__ import annotations

import dataclasses
import random
import time

import numpy as np

from . import reference
from .compile_clock import CompileClock


@dataclasses.dataclass
class Deployment:
    """One tenant: its inputs, key seed and the program's objects."""
    index: int
    key_seed: int
    A: np.ndarray
    y: np.ndarray
    rt: object = None
    master: object = None
    round_ends: list = dataclasses.field(default_factory=list)  # (t, clock)


@dataclasses.dataclass
class Window:
    """What the measured window saw; filled by :class:`RoundClock`."""
    seconds: float
    start: float | None = None       # host clock at the window's start
    end: float | None = None         # host clock of the last round's end
    rounds: int = 0                  # rounds of all deployments ended in it
    launches0: int = 0               # launch counter at the start
    launches1: int = 0               # and at the end
    compiles: int = 0                # backend compiles inside the window
    compile_s: float = 0.0


class RoundClock:
    """Health-monitor stand-in that timestamps round ends on the host
    clock, opens the window once every deployment has finished its warm
    round, and cuts each deployment at its first round that ends after the
    window has closed."""

    def __init__(self, deployments, window: Window, launches, on_open,
                 on_close):
        self.deps = deployments
        self.win = window
        self.launches = launches       # () -> the launch counter to read
        self.on_open, self.on_close = on_open, on_close
        self._compile = CompileClock()
        self._closing = False

    def monitor(self, dep: Deployment):
        from repro.obs.health import NullMonitor
        clock = self

        class _Monitor(NullMonitor):
            enabled = True

            def observe_round(self, t, *_a, **_kw):
                clock._round_end(dep, t)

        return _Monitor()

    def _round_end(self, dep: Deployment, t: int) -> None:
        now = time.perf_counter()
        dep.round_ends.append((t, now))
        win = self.win
        if win.start is None:
            if all(d.round_ends for d in self.deps):
                win.start = now
                win.launches0 = self.launches()
                self.on_open()
                self._compile.__enter__()
            return
        win.rounds += 1
        win.end = now
        if not self._closing and now - win.start >= win.seconds:
            self._closing = True
        if self._closing:
            dep.master.cancel_after = t + 1
            if all(d.master.cancel_after is not None for d in self.deps):
                # every deployment has ended its last round: close
                self._compile.__exit__()
                win.compiles = self._compile.compiles
                win.compile_s = self._compile.seconds
                win.launches1 = self.launches()
                self.on_close()


class Recorder:
    """Keeps each operation's inputs and answer, tagged by deployment and
    round, from the deployment's crypto queue."""

    def __init__(self):
        self.ops: list = []   # (dep index, round, op, args, answer)

    def attach(self, dep: Deployment) -> None:
        cq = dep.rt.cq
        submit = cq.submit
        ops = self.ops

        def recorded_submit(op, args, cb):
            tag = (dep.index, dep.master.t)

            def answered(res):
                ops.append((*tag, op, args, res))
                cb(res)
            submit(op, args, answered)

        cq.submit = recorded_submit


def protocol_config(cfg: dict, key_seed: int):
    from repro.core import protocol
    from repro.core.quantization import QuantSpec
    return protocol.ProtocolConfig(
        K=cfg["K"], rho=cfg["rho"], lam=cfg["lam"], iters=cfg["iters"],
        spec=QuantSpec(delta=cfg["delta"], zmin=cfg["zmin"],
                       zmax=cfg["zmax"]),
        workload=cfg["problem"], cipher=cfg["cipher"],
        key_bits=cfg["key_bits"], gold_batch=cfg["gold_batch"], crt=cfg["crt"],
        seed=key_seed)


def make_deployments(cfg: dict, traffic: dict, seed: int) -> list:
    """Each deployment's key seed comes from the configuration, its data
    from ``seed`` (same seed, same inputs)."""
    deps = []
    for i in range(traffic["tenants"]):
        A, y, _ = reference.make_lasso(cfg["M"], cfg["N"], cfg["sparsity"],
                                       cfg["noise"], seed=[seed, i])
        deps.append(Deployment(index=i, key_seed=cfg["key_seeds"][i],
                               A=A, y=y))
    return deps


def _prepare(dep: Deployment, wl, seed: int, rec: Recorder) -> None:
    """Blinding from the seed, answers recorded, and the z-update's device
    ops warmed: a round's global update runs after its end is observed, so
    round 0's would otherwise compile inside the window."""
    dep.rt.box.rng.seed(f"blinding:{seed}:{dep.index}")
    rec.attach(dep)
    wl.prox_z(np.zeros_like(dep.master.wst.x_prev))


def run_traffic(cfg: dict, traffic: dict, seed: int, seconds: float,
                on_open=lambda: None, on_close=lambda: None):
    """Run the mix until the window closes.  Returns (deployments, window,
    recorder, engine-or-None)."""
    from repro.runtime.runner import build_runtime

    if traffic["arrival"] != "closed":
        raise ValueError(f"arrival {traffic['arrival']!r} is not supported")
    deps = make_deployments(cfg, traffic, seed)
    win = Window(seconds=seconds)
    rec = Recorder()
    if not traffic["engine"]:
        if len(deps) != 1:
            raise ValueError("without the engine a mix runs one deployment")
        dep = deps[0]
        holder = {}
        clock = RoundClock(deps, win, lambda: holder["rt"].cq.launches,
                           on_open, on_close)
        rt, master, wl, _ = build_runtime(
            dep.A, dep.y, protocol_config(cfg, dep.key_seed),
            health=clock.monitor(dep))
        holder["rt"] = rt
        dep.rt, dep.master = rt, master
        _prepare(dep, wl, seed, rec)
        master.start()
        rt.sched.run()
        engine = None
    else:
        from repro.serve.protocol_engine import ProtocolEngine
        engine = ProtocolEngine(seed=cfg["key_seeds"][0],
                                admission=traffic["admission"])
        clock = RoundClock(deps, win,
                           lambda: engine.collector.total_launches,
                           on_open, on_close)
        for dep in deps:
            tid = engine.admit(dep.A, dep.y,
                               protocol_config(cfg, dep.key_seed),
                               tid=f"t{dep.index}",
                               health=clock.monitor(dep))
            ten = engine.tenants[tid]
            dep.rt, dep.master = ten.rt, ten.master
            _prepare(dep, ten.wl, seed, rec)
        engine.run()
    if win.end is None or not all(d.master.done for d in deps):
        raise RuntimeError(
            "the deployments ran out of iterations before the window closed")
    return deps, win, rec, engine


def sample_rounds(deps, win: Window, seed: int, per_dep: int) -> dict:
    """Rounds of each deployment whose answers are checked: ``per_dep``
    drawn from the seed among those that ended inside the window."""
    rng = random.Random(f"check:{seed}")
    picked = {}
    for d in deps:
        inside = [t for t, at in d.round_ends if at > win.start]
        picked[d.index] = sorted(rng.sample(inside, min(per_dep, len(inside))))
    return picked

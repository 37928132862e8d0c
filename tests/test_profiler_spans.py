"""Spans on the profiler's clock (``repro.obs.trace.span``): a tiny solo
run and a tiny engine run under ``jax.profiler`` emit the named spans with
their attributes, no span encloses a whole round, launch spans close
before their callbacks, host-path spans never cover a wait for the device,
every copy of a device array to the host lies under ``host:fetch``, and the
spans per round stay within the budget."""
import contextlib
import glob
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bigint, paillier, paillier_batch, protocol
from repro.core.cipher_tensor import CipherTensor
from repro.core.quantization import QuantSpec
from repro.data.synthetic import make_lasso
from repro.obs import trace as trace_mod
from repro.runtime.runner import run_on_runtime
from repro.serve.protocol_engine import ProtocolEngine

PREFIXES = ("launch:", "serve:launch:", "serve:demux", "host:", "driver:")
OPS = ("enc", "add", "matvec", "dec")
ITERS = 2
BUDGET = 100          # program spans per round (per fleet round, engine)
#: the driver's float steps, which wait for their own small programs
#: inside the ``driver:*`` spans; every other module is on the crypto path
DRIVER_FLOAT = ("repro.runtime.runner", "repro.core.protocol",
                "repro.core.quantization", "repro.workloads.")


def _cfg(seed):
    return protocol.ProtocolConfig(
        K=3, rho=1.0, lam=1.0, iters=ITERS,
        spec=QuantSpec(delta=1e15, zmin=-16.0, zmax=16.0), workload="lasso",
        cipher="gold", key_bits=128, gold_batch=True, crt=True, seed=seed)


def _lasso(seed):
    inst = make_lasso(3, 9, seed=seed)
    return inst.A, inst.y


def _profiled(logdir, fn) -> list:
    """``[name, start_ns, end_ns, attrs]`` of every program span that
    ``fn()`` emits under a profiler session, and the copies of device
    arrays to the host that it makes (:func:`_watch_copies`)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with _watch_copies() as copies, \
            jax.profiler.trace(str(logdir), profiler_options=opts):
        fn()
    path, = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [[e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)]
                        for e in line.events if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda s: s[1]), copies


@contextlib.contextmanager
def _watch_copies():
    """Count the crypto path's copies of a device array to the host:
    ``np.asarray`` or ``np.array`` of a ``jax.Array`` called from a
    ``repro`` module outside ``DRIVER_FLOAT``, under ``bigint.fetch``
    (``"fenced"``) or outside it (``"stray"``, the calling modules).  The
    profiler cannot show them on the CPU, whose arrays reach numpy
    through the buffer protocol."""
    copies = {"fenced": 0, "stray": []}
    depth = [0]
    real_fetch = bigint.fetch

    def fetch(x):
        depth[0] += 1
        try:
            return real_fetch(x)
        finally:
            depth[0] -= 1

    def watched(real):
        def call(a, *args, **kwargs):
            caller = sys._getframe(1).f_globals.get("__name__", "")
            if isinstance(a, jax.Array) and caller.startswith("repro.") \
                    and not caller.startswith(DRIVER_FLOAT):
                if depth[0]:
                    copies["fenced"] += 1
                else:
                    copies["stray"].append(caller)
            return real(a, *args, **kwargs)
        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bigint, "fetch", fetch)
        mp.setattr(np, "asarray", watched(np.asarray))
        mp.setattr(np, "array", watched(np.array))
        yield copies


@pytest.fixture(scope="module")
def solo_events(tmp_path_factory):
    A, y = _lasso(1)
    return _profiled(tmp_path_factory.mktemp("solo"),
                     lambda: run_on_runtime(A, y, _cfg(11)))


@pytest.fixture(scope="module")
def engine_events(tmp_path_factory):
    def serve():
        eng = ProtocolEngine(seed=11, admission="concurrent")
        for i in range(2):
            A, y = _lasso(10 + i)
            eng.admit(A, y, _cfg(11 + i), tid=f"t{i}")
        eng.run()
    return _profiled(tmp_path_factory.mktemp("engine"), serve)


@pytest.fixture(scope="module")
def solo(solo_events):
    return solo_events[0]


@pytest.fixture(scope="module")
def engine(engine_events):
    return engine_events[0]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _encloses(outer, inner) -> bool:
    return outer is not inner and outer[1] <= inner[1] and inner[2] <= outer[2]


def _rounds_enclosed(spans):
    """Spans that enclose both the start of some round's quantize and
    the end of the same round's global update."""
    by_round = {}
    for s in spans:
        if s[0] in ("driver:quantize", "driver:global_update"):
            by_round.setdefault((s[3].get("tenant"), s[3]["round"]),
                                {})[s[0]] = s
    bad = []
    for steps in by_round.values():
        if len(steps) == 2:
            a, b = steps["driver:quantize"], steps["driver:global_update"]
            bad += [s for s in spans if s[1] <= a[1] and b[2] <= s[2]]
    return bad


def _callbacks_outside_launches(spans, launch_prefix):
    launches = [s for s in spans if s[0].startswith(launch_prefix)]
    later = [s for s in spans if s[0].startswith(("driver:", "serve:demux"))]
    return [(o[0], i[0]) for o in launches for i in later if _encloses(o, i)]


def _waits_covered(spans):
    fetches = _named(spans, "host:fetch")
    hosts = [s for s in spans if s[0].startswith("host:")
             and s[0] != "host:fetch"]
    return [(o[0], f[0]) for o in hosts for f in fetches if _encloses(o, f)]


def test_span_is_a_profiler_annotation():
    with trace_mod.span("host:to_ints", round=3, tenant="t0") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_solo_run_emits_the_named_spans(solo):
    names = {s[0] for s in solo}
    assert {f"launch:{op}" for op in OPS} <= names
    assert {"host:to_limbs", "host:to_ints", "host:fetch",
            "host:dec_finish"} <= names
    assert {"driver:quantize", "driver:dequantize",
            "driver:global_update"} <= names
    assert not any(n.startswith("serve:") for n in names)
    for op in OPS:
        for _, _, _, attrs in _named(solo, f"launch:{op}"):
            assert attrs["op"] == op and attrs["width"] >= 1
            assert attrs["fused"] in (0, 1)
    rounds = {s[3]["round"] for s in _named(solo, "launch:dec")}
    assert rounds == set(range(ITERS))
    share, *_ = _named(solo, "launch:enc")     # before the first round
    assert "round" not in share[3]
    for name in ("driver:quantize", "driver:global_update"):
        got = _named(solo, name)
        assert [s[3] for s in got] == [{"round": t} for t in range(ITERS)]


def test_engine_run_emits_the_named_spans(engine):
    names = {s[0] for s in engine}
    assert {f"serve:launch:{op}" for op in OPS} <= names
    assert {"serve:demux", "host:pack_rows", "host:unpack_rows",
            "host:fetch", "host:dec_finish"} <= names
    for op in OPS:
        for _, _, _, attrs in _named(engine, f"serve:launch:{op}"):
            assert attrs["tenants"] == 2 and attrs["width"] >= 2
    demux = _named(engine, "serve:demux")
    assert {s[3]["tenant"] for s in demux} == {"t0", "t1"}
    assert {s[3]["op"] for s in demux} == set(OPS)
    for name in ("driver:quantize", "driver:dequantize",
                 "driver:global_update"):
        got = _named(engine, name)
        assert {(s[3]["tenant"], s[3]["round"]) for s in got} == \
            {(f"t{i}", t) for i in range(2) for t in range(ITERS)}


@pytest.mark.parametrize("run,launch", [("solo", "launch:"),
                                        ("engine", "serve:launch:")])
def test_span_nesting_rules(run, launch, request):
    spans = request.getfixturevalue(run)
    assert _rounds_enclosed(spans) == []
    assert _callbacks_outside_launches(spans, launch) == []
    assert _waits_covered(spans) == []


@pytest.mark.parametrize("run,tenants", [("solo", 1), ("engine", 2)])
def test_spans_per_round_stay_within_budget(run, tenants, request):
    spans = request.getfixturevalue(run)
    first = _named(spans, "driver:quantize")[0][1]
    last = _named(spans, "driver:global_update")[-1][2]
    inside = [s for s in spans if first <= s[1] and s[2] <= last]
    assert 0 < len(inside) / ITERS <= BUDGET


@pytest.mark.parametrize("run", ["solo_events", "engine_events"])
def test_device_waits_lie_under_host_fetch(run, request):
    _, copies = request.getfixturevalue(run)
    assert copies["fenced"] > 0
    assert copies["stray"] == []


def test_a_cipher_tensor_fetches_its_limbs_under_host_fetch():
    bk = paillier_batch.make_batch_key(
        paillier.keygen(128, random.Random(5)))
    cs = [5, 7, 11]
    ct = CipherTensor(bk, jnp.asarray(bigint.from_ints(
        cs, bk.vk.pack_n2.L16)))
    with _watch_copies() as copies:
        assert ct.to_ints() == cs
    assert copies == {"fenced": 1, "stray": []}

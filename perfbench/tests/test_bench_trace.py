"""The reduction from a profiler trace to the device metrics."""
import json
import os

import pytest

from perfbench import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ENQ, DONE = tracing.ENQUEUE, tracing.DONE


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_executions_pair_in_order_and_take_the_dispatch_name():
    ms = 1e6
    host = sorted([
        ["PjitFunction(fixed_body)", 0.0, 1 * ms], [ENQ, 0.5 * ms, 0.1 * ms],
        ["PjitFunction(body)", 2 * ms, 1 * ms], [ENQ, 2.5 * ms, 0.1 * ms],
        [DONE, 400 * ms, 0.1 * ms],         # the ladder ends at 400 ms
        [DONE, 401 * ms, 0.1 * ms],         # body queued behind it
        ["TransferFromDevice", 401.5 * ms, 8 * ms],
        ["PjitFunction(powed_ct_body)", 410 * ms, 1 * ms],
        [ENQ, 410.5 * ms, 0.1 * ms], [DONE, 470 * ms, 0.1 * ms],
    ], key=lambda h: h[1])
    ex = tracing.executions(host)
    assert [e[0] for e in ex] == ["fixed_body", "body", "powed_ct_body"]
    assert ex[1][1] == 400 * ms             # starts when the ladder is done
    red = tracing.reduce_events(host, window_s=0.5)
    assert red["busy_s"] == pytest.approx(0.46)
    assert red["ladder_s"] == pytest.approx(0.3995 + 0.0595)
    (label, secs), = red["breakdown"]["idle_gaps"]
    assert label == "TransferFromDevice -> powed_ct_body"
    assert secs == pytest.approx(0.0095)
    assert red["breakdown"]["device_ops"][0][0] == "fixed_body"


def test_nothing_to_read_returns_nothing():
    assert tracing.reduce_events([], 1.0) is None
    assert tracing.reduce_events([["PjitFunction(x)", 0.0, 5.0]], 1.0) is None


def test_recorded_chip_trace():
    """A traced window of fig6_k3_1024.solo on a TPU v5e: one round's
    enc and dec fixed ladders, its matvec ladder and the small programs."""
    host = _load("runtime_events_fig6_k3_1024_solo.json")["host"]
    ex = tracing.executions(host)
    assert len(ex) == 122
    assert "?" not in {e[0] for e in ex}
    red = tracing.reduce_events(host, window_s=1.460792891)
    assert red["ladder_s"] == pytest.approx(1.3835, abs=1e-4)
    assert red["busy_s"] == pytest.approx(1.4215, abs=1e-4)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fixed_body"] == pytest.approx(1.3217, abs=1e-4)
    assert ops["powed_ct_body"] == pytest.approx(0.0619, abs=1e-4)
    assert 0 < 1 - red["busy_s"] / red["window_s"] < 0.05


def test_runtime_events_agree_with_the_device_modules():
    """The device's own module events of another run of the same cell (a
    default-mode trace, cut short by the profiler's buffer limit) against
    the reconstruction: the enc fixed ladder, the matvec ladder and its
    product tree, whose device time does not depend on the data."""
    mods = _load("module_events_fig6_k3_1024_solo.json")["modules"]
    ex = tracing.executions(_load("runtime_events_fig6_k3_1024_solo.json")
                            ["host"])
    for name in ("fixed_body", "powed_ct_body", "tree"):
        device = next(d for n, _, d in mods if n.startswith(f"jit_{name}("))
        rebuilt = next(e - s for n, s, e in ex if n == name)
        assert rebuilt == pytest.approx(device, rel=0.02), name

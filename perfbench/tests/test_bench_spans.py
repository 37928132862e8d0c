"""Attribution of device and idle time to the program's own spans."""
import dataclasses
import json
import os

import pytest

from perfbench import bench, spans, tracing

ENQ, DONE = tracing.ENQUEUE, tracing.DONE
MS = 1e6


def _host():
    """Three launches and a driver step of one round, in milliseconds:
    enc's ladder is enqueued under launch:enc, dec's under launch:dec
    (whose fetch waits for it), a small program under a conversion inside
    driver:quantize, and the matvec ladder after 10 ms outside any span."""
    ev = [
        ["launch:enc", 0, 10], ["host:to_limbs", 1, 1],
        ["PjitFunction(fixed_body)", 3, 1], [ENQ, 3.5, 0.1],
        [DONE, 400, 0.1],
        ["launch:dec", 401, 99], ["PjitFunction(fixed_body)", 402, 1],
        [ENQ, 402.5, 0.1], ["host:fetch", 404, 91], [DONE, 490, 0.1],
        ["host:dec_finish", 495, 4],
        ["driver:quantize", 500, 20], ["host:to_limbs", 504, 3],
        ["PjitFunction(multiply)", 505, 1], [ENQ, 505.5, 0.1],
        [DONE, 506, 0.1],
        ["launch:matvec", 530, 10], ["PjitFunction(powed_ct_body)", 531, 1],
        [ENQ, 531, 0.1], [DONE, 600, 0],
    ]
    return sorted([[n, s * MS, d * MS] for n, s, d in ev], key=lambda h: h[1])


def test_executions_go_to_the_innermost_span_of_their_enqueue():
    red = spans.attribute(_host())
    busy = red["busy_by_span"]
    assert busy["launch:enc"] == pytest.approx(0.3965)
    assert busy["launch:dec"] == pytest.approx(0.0875)
    assert busy["host:to_limbs"] == pytest.approx(0.0005)   # not quantize
    assert busy["launch:matvec"] == pytest.approx(0.069)
    assert "driver:quantize" not in busy
    assert red["busy_under"]["driver:quantize"] == pytest.approx(0.0005)
    assert red["busy_under"]["host:to_limbs"] == pytest.approx(0.0005)
    assert red["ladder_unlaunched"] == 0


def test_idle_goes_to_the_innermost_span_open_at_each_moment():
    red = spans.attribute(_host())
    idle = red["idle_by_span"]
    want = {"launch:enc": 2.5, "host:to_limbs": 3.5, "(none)": 11.0,
            "launch:dec": 2.5, "host:fetch": 5.0, "host:dec_finish": 4.0,
            "driver:quantize": 17.0, "launch:matvec": 1.0}
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms * 1e-3), name
    busy = tracing.reduce_events(_host(), 0.6)["busy_s"]
    assert red["idle_s"] == pytest.approx(0.6 - busy)
    top = spans.top_idle(red)
    assert top[0] == ["driver:quantize", pytest.approx(0.017)]
    assert top[-1] == ["(none)", pytest.approx(0.011)]


def test_self_time_leaves_out_inner_spans_and_fetches():
    red = spans.attribute(_host())
    own = red["self_by_span"]
    assert own["launch:dec"] == pytest.approx(0.004)
    assert own["driver:quantize"] == pytest.approx(0.017)
    assert spans.host_path_s(red) == pytest.approx(0.008)
    assert spans.driver_s(red) == pytest.approx(0.017)


def test_a_ladder_outside_every_launch_is_counted():
    host = _host() + [["PjitFunction(modexp2d_win4)", 700 * MS, MS],
                      [ENQ, 700.5 * MS, 0.1 * MS], [DONE, 750 * MS, 0.0]]
    red = spans.attribute(sorted(host, key=lambda h: h[1]))
    assert red["ladder_unlaunched"] == 1
    assert red["busy_by_span"]["(none)"] == pytest.approx(0.0495)


def test_nothing_to_read_returns_nothing():
    assert spans.attribute([]) is None
    assert spans.attribute([["launch:enc", 0.0, 5.0]]) is None


@dataclasses.dataclass
class _Window:
    start: float = 0.0
    end: float = 3.0


def _run(engine, trace, rounds=4):
    return bench.Run(cfg={}, traffic={"engine": engine, "tenants": 2},
                     setup_s=1.0, window=_Window(), deployments=[],
                     launches=10, rounds=rounds, work_ops=0, trace=trace,
                     peaks=None)


def _trace():
    red = tracing.reduce_events(_host(), 0.6)
    red["spans"] = spans.attribute(_host())
    return red


@pytest.mark.parametrize("name", spans.METRICS)
def test_readers_read_their_mix_only(name):
    read = bench.load_reader(name)
    serve = name.endswith(".serve")
    assert read(_run(serve, _trace())) >= 0
    assert read(_run(not serve, _trace())) is None          # other mix
    assert read(_run(serve, None)) is None                  # untraced
    untagged = tracing.reduce_events(_host(), 0.6)
    assert read(_run(serve, untagged)) is None              # no spans
    assert read(_run(serve, _trace(), rounds=0)) is None
    # where the existing readers find nothing, so do these
    ladder = bench.load_reader(f"ladder_ms_per_round.{name.split('.')[1]}")
    for run in (_run(not serve, _trace()), _run(serve, None)):
        assert ladder(run) is None


def test_readers_divide_by_rounds_like_the_existing_readers():
    solo, serve = _run(False, _trace()), _run(True, _trace())
    enc = bench.load_reader("enc_ms_per_round.solo")
    assert enc(solo) == pytest.approx(396.5 / 4)
    assert bench.load_reader("dec_ms_per_round.serve")(serve) is not None
    assert bench.load_reader("matvec_ms_per_round.solo")(solo) == \
        pytest.approx(69.0 / 4)
    assert bench.load_reader("host_path_ms_per_round.serve")(serve) == \
        pytest.approx(8.0 / 2)                  # 4 tenant rounds, 2 tenants
    assert bench.load_reader("driver_ms_per_round.solo")(solo) == \
        pytest.approx(17.0 / 4)


def _recorded():
    """Two rounds of fig6_k3_1024.solo on a TPU v5e, with the program's
    spans (``perfbench/spans.py --fixture``)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "runtime_spans_fig6_k3_1024_solo.json")
    with open(path) as f:
        rec = json.load(f)
    host = sorted(rec["host"] + [s[:3] for s in rec["spans"]],
                  key=lambda h: h[1])
    return rec, host


def test_recorded_chip_trace_splits_the_ladders_by_op():
    rec, host = _recorded()
    red = spans.attribute(host)
    ms = {k: 1e3 * v / rec["rounds"] for k, v in red["busy_under"].items()}
    assert ms["launch:enc"] == pytest.approx(671.0, abs=0.1)
    assert ms["launch:dec"] == pytest.approx(652.2, abs=0.1)
    assert ms["launch:matvec"] == pytest.approx(67.0, abs=0.1)
    assert ms["launch:add"] == pytest.approx(1.9, abs=0.1)
    assert red["ladder_unlaunched"] == 0
    # the launches hold the ladders and their non-ladder programs, and
    # nothing else of note
    per = {}
    for name, s, e in tracing.executions(host):
        per[name] = per.get(name, 0.0) + (e - s) * 1e-9
    launched = sum(v for k, v in red["busy_under"].items()
                   if k.startswith("launch:"))
    programs = sum(per[p] for p in ("fixed_body", "powed_ct_body", "tree",
                                    "body"))
    assert launched == pytest.approx(programs, rel=0.01)
    run = _run(False, dict(tracing.reduce_events(host, rec["window_s"]),
                           spans=red), rounds=rec["rounds"])
    assert bench.load_reader("enc_ms_per_round.solo")(run) == \
        pytest.approx(ms["launch:enc"])
    assert bench.load_reader("ladder_ms_per_round.solo")(run) == \
        pytest.approx(1383.7, abs=0.1)


def test_recorded_chip_trace_names_the_idle_time():
    rec, host = _recorded()
    red = spans.attribute(host)
    assert red["idle_by_span"]["(none)"] <= 0.1 * red["idle_s"]
    top = [name for name, _ in spans.top_idle(red)]
    assert top[:2] == ["driver:quantize", "driver:dequantize"]
    per_round = {k: 1e3 * v / rec["rounds"] for k, v in
                 (("host", spans.host_path_s(red)),
                  ("driver", spans.driver_s(red)))}
    assert per_round["host"] == pytest.approx(0.66, abs=0.01)
    assert per_round["driver"] == pytest.approx(34.2, abs=0.1)


def test_recorded_spans_carry_their_round_and_stay_within_budget():
    rec, _ = _recorded()
    assert len(rec["spans"]) / rec["rounds"] <= 100
    launches = [s for s in rec["spans"] if s[0].startswith("launch:")]
    assert {s[3]["round"] for s in launches} == {1, 2}
    for name, _, _, attrs in launches:
        assert attrs["op"] == name.split(":")[1]
        assert attrs["fused"] == 1 and attrs["width"] in (3, 6)

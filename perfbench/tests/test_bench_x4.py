"""CPU rehearsal of the four-chip cell on four virtual devices.

A tiny configuration of the cell's deployment runs through ``bench.run``
in a process of its own (``_x4_worker.py``), with the cell's ``chips``
set to 4 in this test's own copy of ``BENCHMARK.json``.  As in the real
cell, enc's and the matvec's ladder batches divide by four and are spread
over the devices, and dec's does not and runs on one; the layout reader
reports, and an answer altered on one chip fails the check.  Nothing it reports is a
device measurement.
"""
import json
import os
import subprocess
import sys

import pytest

from perfbench import rehearse

SEED = 2**31 + 4242
CELL = "tiny_x4.solo"
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_x4_worker.py")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    root = rehearse.make_root(str(tmp_path_factory.mktemp("bench")),
                              dict(rehearse.TINY, name="tiny_x4", N=18),
                              traffics=("solo",))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    for w in bm["workloads"]:
        w["chips"] = 4
    with open(path, "w") as f:
        json.dump(bm, f)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, WORKER, root, CELL, str(SEED)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_on_four_devices(lines):
    line = lines["sound"]
    assert line["correct"] is True
    assert line["device"]["count"] == 4
    assert line["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_layout_reader_reports(lines):
    metrics = lines["sound"]["metrics"]
    # per round: enc of 36 values and a 3 x (6 x 6) matvec spread, dec of
    # 18 on one chip
    share = 100.0 * 18 / (36 + 18 + 108)
    assert metrics["one_chip_rows_share.x4"]["value"] == pytest.approx(share)
    assert metrics["one_chip_rows_share.x4"]["unit"] == "%"


def test_launches_reader_reports_on_four_devices(lines):
    """``launches_per_round.solo`` counts the runner's launches, however
    their batches lie over the devices."""
    metrics = lines["sound"]["metrics"]
    assert metrics["launches_per_round.solo"]["value"] > 0


def test_one_chip_answer_altered_fails_wrong_answers(lines):
    line = lines["faulted"]
    assert line["correct"] is False
    wrong = line["checks"]["wrong_answers"]
    assert wrong["value"] > wrong["limit"]

"""CPU rehearsal of each traffic mix's path, and discovery by file name.

Tiny keys on the CPU: these runs check control flow, counters and the
correctness check, never a device metric.
"""
import json
import os
import shutil

import pytest

from perfbench import bench, rehearse

DEVICE_METRICS = {m["name"] for m in bench.load_benchmark()["per_layer"]
                  if m["source"] == "device_trace"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.solo", "round_s"),
    ("tiny.tenants4", "tenant_rounds_per_s"),
])
def test_mix_runs_correct_untraced(root, cell, e2e):
    rc, line, err = rehearse.run_cell(root, cell, seed=2**31 + 12345)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(line["metrics"]) == {e2e, "setup_s"}
    assert line["metrics"][e2e]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert "0 backend compiles inside" in err


@pytest.mark.parametrize("cell,counter", [
    ("tiny.solo", "launches_per_round.solo"),
    ("tiny.tenants4", "launches_per_round.serve"),
])
def test_mix_traced_reports_no_device_metric_on_cpu(root, cell, counter):
    rc, line, err = rehearse.run_cell(root, cell, seed=3, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert counter in line["metrics"]
    assert not DEVICE_METRICS & set(line["metrics"])
    assert "busy_s" not in line["device"]


def test_same_seed_same_inputs():
    from perfbench import drive
    cfg, mix = rehearse.TINY, {"tenants": 2}
    a = drive.make_deployments(cfg, mix, 2**33 + 7)
    b = drive.make_deployments(cfg, mix, 2**33 + 7)
    c = drive.make_deployments(cfg, mix, 2**33 + 8)
    for x, y in zip(a, b):
        assert (x.A == y.A).all() and (x.y == y.y).all()
    assert not (a[0].A == c[0].A).all()
    assert not (a[0].A == a[1].A).all()      # tenants get their own data


def test_new_config_mix_and_metric_are_found_by_name(root, tmp_path):
    """A configuration, a traffic mix and a metric added as files and
    entries only, with no existing file edited, are picked up."""
    new = str(tmp_path / "grown")
    shutil.copytree(root, new)
    cfg = dict(rehearse.TINY, name="tiny2", K=2, N=8)
    with open(os.path.join(new, "perfbench/configs/tiny2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(new, "perfbench/traffic/tenants2.json"), "w") as f:
        json.dump({"tenants": 2, "engine": True, "admission": "concurrent",
                   "arrival": "closed"}, f)
    with open(os.path.join(new, "perfbench/metrics/rounds_seen.py"), "w") as f:
        f.write("def read(run):\n    return float(run.rounds)\n")
    with open(os.path.join(new, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny2",
                          "file": "perfbench/configs/tiny2.json"})
    bm["workloads"].append({"name": "tiny2.tenants2", "config": "tiny2",
                            "traffic": "tenants2", "chips": 1})
    bm["end_to_end"].append({"name": "rounds_seen", "unit": "rounds",
                             "workloads": ["tiny2.tenants2"]})
    with open(os.path.join(new, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, line, err = rehearse.run_cell(new, "tiny2.tenants2", seed=5)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["metrics"]["rounds_seen"]["value"] >= 2
    assert "tenant_rounds_per_s" in line["metrics"]
    assert bench.metrics_for(bm, "tiny.solo", False)[-1]["name"] != \
        "rounds_seen"


def test_discovery_reads_every_entry_of_the_benchmark():
    bm = bench.load_benchmark()
    for c in bm["configs"]:
        cfg = bench.load_config(bm, c["name"])
        assert cfg["name"] == c["name"]
        assert len(cfg["key_seeds"]) >= 1
        assert set(c["reduced"]) <= set(cfg)
    for w in bm["workloads"]:
        mix = bench.load_traffic(w["traffic"])
        cfg = bench.load_config(bm, w["config"])
        assert len(cfg["key_seeds"]) >= mix["tenants"]
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(bench.load_reader(m["name"]))


def _run_py(cwd, *args):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "fig6_k3_1024.solo", "--seed", str(2**31 + 5), "--seconds", "1",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_without_an_accelerator_no_result():
    r = _run_py(os.path.dirname(rehearse.HERE))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    repo = os.path.dirname(rehearse.HERE)
    shutil.copytree(rehearse.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), tmp_path)
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

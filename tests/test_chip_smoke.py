"""chip_smoke.py: its CPU rehearsal passes in-process, and without a chip
(or without the repo beside it) it fails without reporting ok."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(REPO)


def _lines(text: str) -> list[dict]:
    return [json.loads(s) for s in text.splitlines() if s.startswith("{")]


def test_rehearsal_passes_in_process(chip_smoke, capsys, tmp_path,
                                     monkeypatch):
    """Every phase runs and is bit-exact at the rehearsal key width, the
    compile cache goes where JAX_COMPILATION_CACHE_DIR says, and the run
    never claims ok."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import compile_cache

    cache = str(tmp_path / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.setitem(compile_cache._state, "enabled", None)
    prev = jax.config.jax_compilation_cache_dir
    cc.reset_cache()
    try:
        assert chip_smoke.main(["--rehearse"]) == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
    lines = _lines(capsys.readouterr().out)
    assert lines[0]["compile_cache"] == cache and os.listdir(cache)
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert set(phases) == {"sample", "protocol", "serving"}
    assert all(ln["ok"] for ln in phases.values())
    assert phases["protocol"]["chain_bit_exact_vs_plain"]
    assert phases["protocol"]["delta"] == 1e15
    assert phases["serving"]["fused_launches"] > 0
    assert lines[-1] == {"rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    assert not any(ln.get("ok") is True and "phase" not in ln
                   for ln in lines)


def _run(cwd, script, *args, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_four_chip_rehearsal_on_virtual_devices(tmp_path):
    """The sharded path is bit-exact against one device and the scalar
    reference; a batch the device count does not divide stays on one."""
    r = _run(REPO, SCRIPT, "--four-chips", "--rehearse",
             XLA_FLAGS="--xla_force_host_platform_device_count=4",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    phases = {ln["phase"]: ln for ln in _lines(r.stdout) if "phase" in ln}
    assert all(all(ln["bit_exact"].values()) for ln in phases.values())
    assert phases["four_chips_divisible"]["enc_devices"] == [0, 1, 2, 3]
    assert phases["four_chips_not_divisible"]["enc_devices"] == [0]
    assert _lines(r.stdout)[-1]["device"]["count"] == 4


def test_without_chip_fails_without_ok():
    r = _run(REPO, SCRIPT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "need tpu" in r.stderr


def test_alone_without_repo_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout

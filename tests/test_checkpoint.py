"""Checkpointing: atomic writes, resume, async, elastic mesh rescale."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.train import checkpoint as ckpt


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((2, 2), jnp.bfloat16),
                       "c": jnp.asarray(3, jnp.int32)},
            "lst": [jnp.zeros(5), jnp.ones(5)]}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 7, t, extra={"pipeline": {"seed": 0, "step": 9}})
    got, manifest = ckpt.restore(str(tmp_path), jax.eval_shape(lambda: t))
    assert manifest["step"] == 7
    assert manifest["extra"]["pipeline"]["step"] == 9
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_latest_step_and_multiple(tmp_path):
    t = _tree()
    for s in (1, 5, 3):
        ckpt.save(str(tmp_path), s, t)
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_atomicity_no_partial_dirs(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 1, t)
    entries = [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]
    assert not entries


def test_async_save(tmp_path):
    t = _tree()
    th = ckpt.save_async(str(tmp_path), 2, t)
    th.join(timeout=30)
    assert ckpt.latest_step(str(tmp_path)) == 2


def test_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore(str(tmp_path), {"a": jnp.zeros((3, 3))})


def test_elastic_restore_across_mesh_sizes(subproc, tmp_path):
    """Checkpoint on a 4-device mesh, restore onto a 2-device mesh."""
    subproc(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.train import checkpoint as ckpt
        from repro.train.fault import elastic_restore

        tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
        mesh4 = make_mesh((4,), ("data",))
        sh4 = NamedSharding(mesh4, P("data"))
        tree4 = {{"w": jax.device_put(tree["w"], sh4)}}
        ckpt.save(r"{tmp_path}", 3, tree4)

        # "failure": only 2 devices survive
        mesh2 = make_mesh((2,), ("data",))
        got, _ = elastic_restore(r"{tmp_path}", jax.eval_shape(lambda: tree),
                                 mesh2, {{"w": P("data")}})
        assert np.array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        assert got["w"].sharding.mesh.devices.size == 2
        print("elastic restore ok")
    """, devices=4)


def test_train_resume_continuity(subproc, tmp_path):
    """Driver-level: train 6 steps, kill, resume from 3 — same stream."""
    subproc(f"""
        import subprocess, sys, os
        env = dict(os.environ); env["PYTHONPATH"] = "src"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        base = [sys.executable, "-m", "repro.launch.train", "--arch",
                "xlstm_125m", "--reduced", "--batch", "2", "--seq", "16",
                "--ckpt-dir", r"{tmp_path}", "--log-every", "1"]
        r1 = subprocess.run(base + ["--steps", "3", "--ckpt-every", "3"],
                            capture_output=True, text=True, env=env)
        assert r1.returncode == 0, r1.stderr[-2000:]
        r2 = subprocess.run(base + ["--steps", "6", "--resume"],
                            capture_output=True, text=True, env=env)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "resumed from step 3" in r2.stdout
        print("resume ok")
    """, devices=1, timeout=900)

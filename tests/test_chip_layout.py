"""The layout of ladder batches over the local chips
(``core.paillier_batch._shard_batch``) and the runtime's per-round count
of it.

Four chips are four virtual CPU devices in a process of its own
(``tests/_layout_worker.py``, every batch size in turn): a batch the
device count divides is spread over every device, any other runs on one.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_layout_worker.py")
BATCHES = (9, 27, 90, 180)
OPS = ("enc", "dec_ct", "dec_int", "fixed_pair", "modexp", "pow_c_ct",
       "matvec_ct", "matvec_int", "matvec_neg")


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jc")))
    r = subprocess.run([sys.executable, WORKER, *map(str, BATCHES)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return {int(b): got for b, got in out["batches"].items()}


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("batch", BATCHES)
def test_laid_out_op_is_bit_exact(four_chips, batch, op):
    """Against the scalar ``core/paillier.py``, and with exactly one
    answer row per input row."""
    exact, rows = four_chips[batch]["case"][op]
    assert exact
    assert rows == batch


@pytest.mark.parametrize("batch", BATCHES)
def test_ladder_batch_spread_when_divisible(four_chips, batch):
    """Every ladder program's batch lies on all four devices when four
    divides its rows, else on device 0 alone."""
    ladders = four_chips[batch]["ladders"]
    assert {name for name, *_ in ladders} == {
        "crt_modexp_fixed", "crt_modexp", "crt_modexp_limbs_fixed",
        "crt_modexp_limbs", "crt_mv_limbs", "crt_mv"}
    for name, rows, devices in ladders:
        assert devices == ([0, 1, 2, 3] if rows % 4 == 0 else [0]), name


@pytest.mark.parametrize("batch", BATCHES)
def test_ladder_rows_counted(four_chips, batch):
    """Six ops of ``batch`` values and three matvecs of 3 x ``batch``
    exponent rows; all on one chip unless four divides the batch."""
    lay = four_chips[batch]["layout"]
    assert lay["rows"] == 6 * batch + 3 * 3 * batch
    assert lay["one_chip"] == (0 if batch % 4 == 0 else lay["rows"])


def test_one_device_same_arrays_no_device_put(monkeypatch):
    """On one device the layout hands every operand back untouched and
    the ops make no ``device_put``."""
    import random
    from repro.core import paillier as gold
    from repro.core import paillier_batch as pb
    from repro.core.cipher_tensor import CipherTensor
    if jax.local_device_count() != 1:
        pytest.skip("one-device check")
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: puts.append(a) or real_put(*a, **k))
    x, y = np.ones((9, 4), np.int32), np.zeros((2, 4), np.int32)
    a, b = pb._shard_batch(x, y, count=False)
    assert a is x and b is y
    key = gold.keygen(128, random.Random(3))
    bk = pb.make_batch_key(key)
    before = dict(pb.LAYOUT)
    ct = pb.enc_ct(bk, list(range(9)), random.Random(4))
    assert pb.dec_vec(bk, ct) == list(range(9))
    K = np.ones((3, 3, 3), dtype=object)
    pb.matvec_many(bk, K, [CipherTensor(bk, ct.limbs[3 * b:3 * b + 3])
                           for b in range(3)])
    assert puts == []
    moved = {k: n - before[k] for k, n in pb.LAYOUT.items()}
    assert moved == {"rows": 9 + 9 + 27, "one_chip": 9 + 9 + 27}


def test_runtime_counts_each_round(monkeypatch):
    """``stats["runtime"]["layout"]`` holds each protocol round's ladder
    rows, and nothing of the share phase; the launch spans carry the
    chips their batch ran on."""
    from repro.core import protocol
    from repro.core.quantization import QuantSpec
    from repro.data.synthetic import make_lasso
    from repro.obs import trace as trace_mod
    from repro.runtime import coalesce, runner
    if jax.local_device_count() != 1:
        pytest.skip("one-device count")
    seen = []

    class Span:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, dict(attrs)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append((self.name, self.attrs))

        def set_metadata(self, **attrs):
            self.attrs.update(attrs)

    monkeypatch.setattr(trace_mod, "span", Span)
    monkeypatch.setattr(coalesce.trace_mod, "span", Span)
    inst = make_lasso(3, 9, seed=5)
    cfg = protocol.ProtocolConfig(
        K=3, iters=3, spec=QuantSpec(delta=1e15, zmin=-16.0, zmax=16.0),
        cipher="gold", key_bits=128, gold_batch=True, seed=5)
    res = runner.run_on_runtime(inst.A, inst.y, cfg)
    per_round = res.stats["runtime"]["layout"]
    assert sorted(per_round) == [0, 1, 2]
    for t, n in per_round.items():
        # enc of 2 x 9 values, dec of 9, a 3 x (3 x 3) matvec
        assert n == {"rows": 18 + 9 + 27, "one_chip": 18 + 9 + 27}, t
    ladders = [a for name, a in seen if name.startswith("launch:")
               and name != "launch:add"]
    assert ladders and all(a["chips"] == 1 for a in ladders)
    assert not any("chips" in a for name, a in seen if name == "launch:add")

"""The main path's limb kernels compile for a TPU v5e chip at the widths of
the paper's 2048-bit keys.

Each case lowers and compiles for a described (not attached) ``v5e:2x2``
topology, so the chip's compiler refuses here what it would refuse on the
chip; nothing runs.  The topology is described only inside the fixtures
below: one process at a time may load the TPU library, so it must never
happen while a module is imported or tests are collected.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import paillier as gold
from repro.core import paillier_batch as pb
from repro.core import paillier_vec as pv
from repro.kernels import ops

HBM_BYTES = 16 * 2 ** 30      # one v5e chip
BATCH = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def key():
    return gold.keygen(2048, random.Random(0))


@pytest.fixture(scope="module")
def vk(key):
    return pb.make_batch_key(key).vk


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """An executable compiled for a described chip cannot be read back
    without the chip, so keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _limbs(rows, width, sharding):
    return jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def test_modexp_fixed_crt_half(key, vk, one_chip):
    """enc's r^n ladder in one CRT half (L8 = 256)."""
    pack = vk.pack_p2
    assert pack.L8 == 256
    e = key.n % key.phi_p2
    _compile(lambda b: ops.modexp_fixed(b, e, pack),
             _limbs(BATCH, pack.L16, one_chip))


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("batch",))
    return NamedSharding(mesh, PartitionSpec("batch"))


@pytest.mark.parametrize("chips", ["one_chip", "four_chips"])
def test_modexp_fixed_pair_stacked(key, vk, chips, request):
    """enc's r^n with both CRT halves stacked in one ladder, at the solo
    cell's 180 values (2 x 180 rows at L8 = 256), then recombined; on one
    chip, and batch-sharded over four as ``_shard_batch`` lays it out."""
    sharding = request.getfixturevalue(chips)
    e_p, e_q = key.n % key.phi_p2, key.n % key.phi_q2

    def enc_rn(bp, bq):
        xp, xq = ops.modexp_fixed_pair(bp, e_p, vk.pack_p2,
                                       bq, e_q, vk.pack_q2)
        return pv.crt_combine_batch(vk, xp, xq)

    _compile(enc_rn, _limbs(180, vk.pack_p2.L16, sharding),
             _limbs(180, vk.pack_q2.L16, sharding))


def test_modexp_per_element_crt_half(vk, one_chip):
    """The matvec ladder with per-element Gamma_2 exponents (L8 = 256)."""
    pack = vk.pack_p2
    _compile(lambda b, e: ops.modexp(b, e, pack),
             _limbs(BATCH, pack.L16, one_chip), _limbs(BATCH, 4, one_chip))


def test_mulmod_n2(vk, one_chip):
    """The ciphertext product (⊕) at n^2 width (L8 = 512)."""
    pack = vk.pack_n2
    assert pack.L8 == 512
    _compile(lambda a, b: ops.mulmod(a, b, pack),
             _limbs(BATCH, pack.L16, one_chip),
             _limbs(BATCH, pack.L16, one_chip))


def test_modexp_rows_n2(vk, one_chip):
    """The serving engine's multi-modulus ladder at n^2 width with a
    key-width exponent, as ``ops.modexp_rows`` launches it."""
    L8 = vk.pack_n2.L8
    rows = 8
    _compile(ops._MODEXP_ROWS8[ops.MODEXP_METHOD],
             _limbs(rows, L8, one_chip), _limbs(rows, 256, one_chip),
             _limbs(rows, L8, one_chip), _limbs(rows, L8 + 1, one_chip))


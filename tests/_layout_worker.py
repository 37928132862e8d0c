"""Runs the batched Paillier ops over every device of this process and
prints one JSON line of what ``tests/test_chip_layout.py`` checks, for
each batch size given.

Started by that test in a process of its own with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the device count
is fixed when JAX starts.

    python tests/_layout_worker.py <batch> [<batch> ...]
"""
import json
import os
import random
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402

from repro.core import bigint as bi  # noqa: E402
from repro.core import paillier as gold  # noqa: E402
from repro.core import paillier_batch as pb  # noqa: E402
from repro.core import paillier_vec as pv  # noqa: E402
from repro.core.cipher_tensor import CipherTensor  # noqa: E402

KEY_BITS = 128
BLOCKS, N = 3, 3              # matvec: 3 blocks of N ciphertexts

# the CRT ladder programs' cache names
LADDERS = ("crt_modexp_fixed", "crt_modexp", "crt_modexp_limbs_fixed",
           "crt_modexp_limbs", "crt_mv_limbs", "crt_mv")
calls: list = []              # (ladder program, rows, operand devices)
_cached_jit = pv._cached_jit


def recording_jit(vk, name, builder):
    """``pv._cached_jit`` that keeps, for every call of a ladder program,
    its batch's rows (its longest operand's) and that operand's devices."""
    fn = _cached_jit(vk, name, builder)
    if not (isinstance(name, tuple) and name[0] in LADDERS):
        return fn

    def call(*args):
        batch = max(args, key=lambda a: a.shape[0])
        calls.append((name[0], int(batch.shape[0]), _devices(batch)))
        return fn(*args)
    return call


def prod_pow(cs, ks, n2):
    acc = 1
    for c, k in zip(cs, ks):
        acc = acc * pow(c, int(k), n2) % n2
    return acc


def one_batch(key, bk, batch: int, rng: random.Random) -> dict:
    """Each op at ``batch`` rows: (bit-exact against the scalar path,
    rows in the answer)."""
    n, n2 = key.n, key.n2
    ms = [rng.randrange(1 << 60) for _ in range(batch)]
    seed = rng.randrange(1 << 30)
    ct = pb.enc_ct(bk, ms, random.Random(seed))
    r = random.Random(seed)
    ref = [gold.encrypt_crt(key, m, gold.rand_r(key, r)) for m in ms]
    rs = [rng.randrange(2, n) for _ in range(batch)]
    ks = [rng.randrange(1 << 40) for _ in range(batch)]
    signed = [k if i % 5 else -k for i, k in enumerate(ks)]
    out = {
        "enc": (ct.to_ints() == ref, int(ct.limbs.shape[0])),
        "dec_ct": (pb.dec_vec(bk, ct) == [m % n for m in ms], batch),
        "dec_int": (pb.dec_vec(bk, ref) == [m % n for m in ms], batch),
    }
    rn = pb.rn_pool_limbs(bk, rs)
    out["fixed_pair"] = (bi.to_ints(rn) == [pow(x, n, n2) for x in rs],
                         int(rn.shape[0]))
    out["modexp"] = (pb.modexp_crt_vec(bk, ref, signed)
                     == [pow(c, k, n2) for c, k in zip(ref, signed)], batch)
    pc = pb.pow_c_ct(bk, ct, ks)
    out["pow_c_ct"] = (pc.to_ints() == [pow(c, k, n2)
                                        for c, k in zip(ref, ks)],
                       int(pc.limbs.shape[0]))
    # matvec with ``batch`` outputs over the first BLOCKS * N ciphertexts
    M = batch // BLOCKS
    K = np.array([[[rng.randrange(1 << 20) for _ in range(N)]
                   for _ in range(M)] for _ in range(BLOCKS)], dtype=object)
    blocks = [ref[b * N:(b + 1) * N] for b in range(BLOCKS)]
    want = [[prod_pow(blocks[b], K[b, i], n2) for i in range(M)]
            for b in range(BLOCKS)]
    mv = pb.matvec_many(bk, K, [CipherTensor(bk, ct.limbs[b * N:(b + 1) * N])
                                for b in range(BLOCKS)])
    out["matvec_ct"] = ([c.to_ints() for c in mv] == want,
                        sum(int(c.limbs.shape[0]) for c in mv))
    mi = pb.matvec_many(bk, K, blocks)
    out["matvec_int"] = (mi == want, sum(len(x) for x in mi))
    Kn = K.copy()
    Kn[0, 0, 0] = -Kn[0, 0, 0]
    want_n = [[prod_pow(blocks[b], Kn[b, i], n2) for i in range(M)]
              for b in range(BLOCKS)]
    mn = pb.matvec_many(bk, Kn, blocks)
    out["matvec_neg"] = (mn == want_n, sum(len(x) for x in mn))
    return out


def _devices(x) -> list:
    return sorted(d.id for d in x.sharding.device_set)


def main(argv) -> int:
    pv._cached_jit = recording_jit
    key = gold.keygen(KEY_BITS, random.Random(7))
    bk = pb.make_batch_key(key)
    out = {}
    for batch in map(int, argv):
        calls.clear()
        before = dict(pb.LAYOUT)
        case = one_batch(key, bk, batch, random.Random(batch))
        out[batch] = {
            "case": case,
            "ladders": list(calls),
            "layout": {k: n - before[k] for k, n in pb.LAYOUT.items()},
        }
    print(json.dumps({"devices": jax.device_count(), "batches": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Limb-kernel microbenchmarks: reduction impls and ladder variants.

Races the batched kernel-boundary ops (``kernels/ops.py``) under each
``reduce_impl`` — Barrett (the oracle, ``kernels/common.py``) vs
Montgomery REDC (``kernels/montgomery.py``) — across key lengths:

  * ``mulmod``        — single product, always Barrett (the Montgomery
    domain enter/leave conversions don't amortize over one multiply, so
    there is no competing arm; timed as the baseline unit);
  * ``modexp``        — per-element-exponent win4 ladder, both impls;
  * ``modexp_fixed``  — host-known-exponent static-window ladder
    (enc's ``r^n`` / dec's ``c^lam`` schedule), both impls.

Every arm is verified bit-exact against Python-int ``pow`` on identical
operands; a mismatch raises ``SystemExit`` so CI fails loudly rather than
recording a wrong-but-fast number.  ``smoke=True`` (``--smoke``, the CI
step) runs the smallest key at a reduced batch with one repeat — timings
are then meaningless but the exactness gate still runs.

Run directly::

  PYTHONPATH=src python -m benchmarks.run --only kernels [--smoke]
"""
from __future__ import annotations

import random

import jax.numpy as jnp

from repro.core import bigint as bi
from repro.core import paillier as gold
from repro.core import paillier_batch as pb
from repro.kernels import ops as kops
try:
    from .common import emit, timeit
except ImportError:      # direct script run
    from common import emit, timeit

KEY_BITS = (128, 256)
BATCH = 128
EXP_BITS = 21            # Gamma_2-quantized exponent width


def _bench_key(rows: list, bits: int, batch: int, repeat: int) -> None:
    key = gold.keygen(bits, random.Random(7))
    pack = pb.make_batch_key(key).vk.pack_p2
    rng = random.Random(11)
    bases = [rng.randrange(1, pack.m_int) for _ in range(batch)]
    exps = [rng.randrange(1 << EXP_BITS) for _ in range(batch)]
    e_fix = key.n % pack.m_int
    b16 = jnp.asarray(bi.from_ints(bases, pack.L16))
    le = max(1, max(bi.n_limbs_for(e) for e in exps))
    e16 = jnp.asarray(bi.from_ints(exps, le))
    want = {
        "mulmod": [b * b % pack.m_int for b in bases],
        "modexp": [pow(b, e, pack.m_int) for b, e in zip(bases, exps)],
        "modexp_fixed": [pow(b, e_fix, pack.m_int) for b in bases],
    }

    def launch(op, impl):
        if op == "mulmod":
            return kops.mulmod(b16, b16, pack)
        if op == "modexp":
            return kops.modexp(b16, e16, pack, reduce_impl=impl)
        return kops.modexp_fixed(b16, e_fix, pack, reduce_impl=impl)

    walls: dict[tuple, float] = {}
    for op in ("mulmod", "modexp", "modexp_fixed"):
        arms = ("barrett",) if op == "mulmod" else ("barrett", "montgomery")
        for impl in arms:
            got = bi.to_ints(launch(op, impl))
            if got != want[op]:
                raise SystemExit(
                    f"kern_{op}_{impl}_{bits}b NOT bit-exact vs pow()")
            t = timeit(lambda: launch(op, impl).block_until_ready(),
                       repeat=repeat)
            walls[op, impl] = float(t)
            derived = "bit_exact=True"
            if impl == "montgomery":
                derived += (";speedup_vs_barrett="
                            f"{walls[op, 'barrett'] / float(t):.3f}")
            emit(rows, f"kern_{op}_{impl}_{bits}b", float(t) / batch,
                 derived=derived)


def run(rows: list, smoke: bool = False) -> None:
    sizes = KEY_BITS[:1] if smoke else KEY_BITS
    batch = 32 if smoke else BATCH
    repeat = 1 if smoke else 5
    for bits in sizes:
        _bench_key(rows, bits, batch, repeat)


if __name__ == "__main__":
    rows: list = []
    run(rows)
    print("\n".join(rows))

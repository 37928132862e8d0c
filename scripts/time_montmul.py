"""Time one Montgomery multiply (``montgomery.montmul2d``) on the default
device, off the benchmark, at one limb width over a sweep of batch sizes.

Each batch size compiles one program that runs ``--iters`` multiplies in a
chain (each takes the last one's result), then times it ``--reps`` times
after a warm-up call and prints the median per multiply; the chain's
result is checked against Python ints first.  The chain keeps
the device busy with nothing but multiplies, so the figure is the serial
cost of one multiply at that batch, with no host work or launch in it.
It imports ``repro`` from ``PYTHONPATH``, so the same script times two
trees: point ``PYTHONPATH`` at each tree's ``src``.

Usage::

  PYTHONPATH=src python scripts/time_montmul.py --limbs 256 \\
      --batches 8,46,90,180,360 [--iters 256] [--reps 3] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import random
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import montgomery as mg


def _limbs(values: list[int], n_limbs: int) -> jax.Array:
    return jnp.asarray([[(v >> (8 * i)) & 0xFF for i in range(n_limbs)]
                        for v in values], jnp.int32)


def _ints(arr) -> list[int]:
    return [sum(int(v) << (8 * i) for i, v in enumerate(row)) for row in arr]


def time_montmul(n_limbs: int, batch: int, iters: int, reps: int,
                 seed: int = 0) -> float:
    """Median seconds per multiply of a chain of ``iters`` multiplies."""
    rng = random.Random(seed)
    m = rng.getrandbits(8 * n_limbs) | 1 | (1 << (8 * n_limbs - 1))
    mp = mg.mont_constants(m, n_limbs)[0]
    xs = [rng.randrange(m) for _ in range(batch)]
    ys = [rng.randrange(m) for _ in range(batch)]
    a, b = _limbs(xs, n_limbs), _limbs(ys, n_limbs)
    m8 = _limbs([m], n_limbs)

    @jax.jit
    def chain(x, y, mod):
        return jax.lax.fori_loop(
            0, iters, lambda _, v: mg.montmul2d(v, y, mod, mp), x)

    r_inv = pow(1 << (8 * n_limbs), -1, m)
    want = [x * pow(y * r_inv, iters, m) % m for x, y in zip(xs, ys)]
    if _ints(jax.device_get(chain(a, b, m8))) != want:
        raise SystemExit(f"montmul2d chain wrong at L={n_limbs} B={batch}")
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chain(a, b, m8).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--limbs", type=int, default=256)
    ap.add_argument("--batches", default="8,46,90,180,360")
    ap.add_argument("--iters", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--json", default=None,
                    help="also write the readings to this file")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    rows = []
    for batch in map(int, args.batches.split(",")):
        s = time_montmul(args.limbs, batch, args.iters, args.reps)
        rows.append({"limbs": args.limbs, "batch": batch,
                     "us_per_montmul": s * 1e6})
        print(f"L={args.limbs} B={batch}: {s * 1e6:.2f} us per montmul",
              flush=True)
    out = {"device": {"platform": dev.platform,
                      "device_kind": dev.device_kind},
           "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Drives the four-chip cell's rehearsal through ``bench.run`` on every
device of this process, and prints one JSON line: the result line of a
sound traced run, and that of a run in which the answers of one chip's
share of each dec batch are altered.

Started by ``perfbench/tests/test_bench_x4.py`` in a process of its own
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

    python perfbench/tests/_x4_worker.py <root> <cell> <seed>
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import rehearse  # noqa: E402


def one_chip_share_altered() -> None:
    """The first quarter of each dec batch, chip 0's share of a batch
    spread over four chips, comes back one too large."""
    import jax
    from repro.core import paillier_batch as pb
    dec_vec = pb.dec_vec

    def altered(bk, cs, backend=None):
        out = dec_vec(bk, cs, backend=backend)
        share = -(-len(out) // jax.local_device_count())
        return [v + 1 for v in out[:share]] + out[share:]
    pb.dec_vec = altered


def main(argv) -> int:
    root, cell, seed = argv[0], argv[1], int(argv[2])
    rehearse.program_on_path()
    rc, sound, err = rehearse.run_cell(root, cell, seed=seed, trace=1)
    if rc != 0:
        print(err, file=sys.stderr)
        return rc
    one_chip_share_altered()
    rc, faulted, err = rehearse.run_cell(root, cell, seed=seed)
    if rc != 0:
        print(err, file=sys.stderr)
        return rc
    print(json.dumps({"sound": sound, "faulted": faulted}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

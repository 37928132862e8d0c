#!/usr/bin/env python3
"""Bring-up smoke of the private ADMM protocol on one accelerator chip.

Drives the protocol's main path once, through the entry points a user
calls, at the paper's deployment width: 2048-bit Paillier keys and the
Delta = 1e15 quantizer of ``configs/paper_admm.FIG6``.

  sample    encrypt a sample with the batched CRT path and with the scalar
            Python-int reference (``GoldBox(batch=False)``); ciphertexts
            and decryptions must be bit-identical
  protocol  ``run_on_runtime`` (the runner behind ``repro.launch.edge_sim``)
            with cipher ``gold`` on the batched CRT path, LASSO, K = 3, on
            ``paper_admm.scaled(FIG6, FACTOR)``; the decrypted chain must
            equal a ``cipher="plain"`` run of the same instance exactly
  serving   ``ProtocolEngine`` (behind ``repro.launch.serve_sim``) with four
            gold tenants, each with its own fresh key, for two rounds; the
            fused multi-modulus rows launches must run and every tenant's
            chain must equal its plain run exactly

Each phase prints one JSON line: wall seconds, the seconds the XLA
backend spent compiling inside it, sizes and verdicts.  The last line is
``{"ok": true, "device": {...}}``.  A mismatch or an error exits nonzero
without that line, and so does a run that finds no TPU.

  python chip_smoke.py                # one TPU chip
  python chip_smoke.py --four-chips   # batch sharding over four chips only
  python chip_smoke.py --rehearse     # the same phases, small key, on CPU

``--rehearse`` runs the phases at a small key and problem size on the CPU (add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for
``--four-chips``); it never prints the ``"ok": true`` line.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

KEY_BITS = 2048
FACTOR = 300            # paper_admm.scaled(FIG6, FACTOR): M=10, N=90, block 30
REHEARSE_KEY_BITS = 256
REHEARSE_FACTOR = 1000  # M=3, N=27, block 9: the CPU ladders scale with batch
ITERS = 3
TENANTS = 4
ROUNDS = 2
SAMPLE = 16
SEED = 0

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds the XLA backend spends compiling while entered, and the
    number of compiles (tracing and lowering are not counted)."""

    def __enter__(self):
        import jax
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def run_phase(name: str, fn, *args) -> dict:
    with CompileClock() as clock:
        t0 = time.perf_counter()
        info = fn(*args)
        wall = time.perf_counter() - t0
    line = {"phase": name, "wall_s": wall, "compile_s": clock.seconds,
            "compiles": clock.compiles, **info}
    emit(line)
    return line


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def sample_phase(key_bits: int) -> dict:
    """Batched vs scalar encryption and decryption of one sample."""
    from repro.core import paillier as gold
    from repro.core import protocol

    t0 = time.perf_counter()
    key = gold.keygen(key_bits, random.Random(SEED))
    keygen_s = time.perf_counter() - t0
    rng = random.Random(SEED + 1)
    ms = [rng.randrange(key.n) for _ in range(SAMPLE - 2)] + [-1, -(1 << 60)]
    batched = protocol.GoldBox(key, random.Random(SEED + 2), batch=True)
    scalar = protocol.GoldBox(key, random.Random(SEED + 2), batch=False)
    t0 = time.perf_counter()
    c_batch = batched.encrypt(ms)
    c_ints = c_batch.to_ints()
    first_s = time.perf_counter() - t0
    c_ref = scalar.encrypt(ms)
    d_batch = [int(v) for v in batched.decrypt(c_batch)]
    d_ref = [int(v) for v in scalar.decrypt(c_ref)]
    t0 = time.perf_counter()
    again = protocol.GoldBox(key, random.Random(SEED + 2)).encrypt(ms)
    again.to_ints()
    warm_s = time.perf_counter() - t0
    enc_exact = c_ints == c_ref
    dec_exact = d_batch == d_ref == [m % key.n for m in ms]
    return {"key_bits": key.n.bit_length(), "sample": len(ms),
            "keygen_s": keygen_s, "enc_first_s": first_s,
            "enc_warm_s": warm_s, "enc_bit_exact": enc_exact,
            "dec_bit_exact": dec_exact, "ok": enc_exact and dec_exact}


def _paper_case(key_bits: int, factor: int, iters: int, seed: int):
    from repro.configs import paper_admm
    from repro.core import protocol
    from repro.data.synthetic import make_lasso

    setup = paper_admm.scaled(paper_admm.FIG6, factor)
    inst = make_lasso(setup.M, setup.N, sparsity=0.1, noise=0.01, seed=seed)

    def cfg(cipher: str):
        return protocol.ProtocolConfig(
            K=setup.K, rho=setup.admm.rho, lam=setup.admm.lam, iters=iters,
            spec=setup.spec, cipher=cipher, key_bits=key_bits,
            gold_batch=True, seed=seed)
    return setup, inst, cfg


def _float_reference(inst, setup, iters: int):
    """Unquantized distributed ADMM on the host CPU (float64 LU has no
    TPU lowering)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import admm

    with jax.default_device(jax.devices("cpu")[0]):
        x, _ = admm.distributed_admm(
            jnp.asarray(inst.A), jnp.asarray(inst.y), setup.K,
            admm.ADMMConfig(rho=setup.admm.rho, lam=setup.admm.lam,
                            iters=iters))
        return np.asarray(x)


def protocol_phase(key_bits: int, factor: int) -> dict:
    import numpy as np
    from repro.runtime.runner import run_on_runtime

    setup, inst, cfg = _paper_case(key_bits, factor, ITERS, SEED)
    t0 = time.perf_counter()
    enc = run_on_runtime(inst.A, inst.y, cfg("gold"))
    gold_s = time.perf_counter() - t0
    plain = run_on_runtime(inst.A, inst.y, cfg("plain"))
    x_ref = _float_reference(inst, setup, ITERS)
    chain_exact = bool(np.array_equal(enc.history, plain.history))
    rt = enc.stats["runtime"]
    return {"factor": factor, "M": setup.M, "N": setup.N, "K": setup.K,
            "block": setup.N // setup.K, "iters": ITERS,
            "delta": setup.spec.delta, "key_bits": enc.stats["key_bits"],
            "gold_run_s": gold_s, "launches": rt["launches"],
            "coalesced_ops": rt["coalesced_ops"],
            "mse_vs_truth": float(np.mean((enc.x - inst.x_true) ** 2)),
            "max_abs_vs_float_admm": float(np.max(np.abs(enc.x - x_ref))),
            "chain_bit_exact_vs_plain": chain_exact, "ok": chain_exact}


def serving_phase(key_bits: int, factor: int) -> dict:
    import numpy as np
    from repro.runtime.runner import run_on_runtime
    from repro.serve.protocol_engine import ProtocolEngine

    eng = ProtocolEngine(seed=SEED, admission="concurrent")
    cases = {}
    for i in range(TENANTS):
        # a seed per tenant: its own fresh key and its own data
        setup, inst, cfg = _paper_case(key_bits, factor, ROUNDS,
                                       SEED + 1 + i)
        cases[f"t{i}"] = (inst, cfg)
        eng.admit(inst.A, inst.y, cfg("gold"), tid=f"t{i}")
    t0 = time.perf_counter()
    results = eng.run()
    serve_s = time.perf_counter() - t0
    serve = eng.stats()["serve"]
    exact = {tid: bool(np.array_equal(
        results[tid].history,
        run_on_runtime(inst.A, inst.y, cfg("plain")).history))
        for tid, (inst, cfg) in cases.items()}
    bits = sorted({r.stats["key_bits"] for r in results.values()})
    ok = all(exact.values()) and serve["fused_launches"] > 0
    return {"tenants": TENANTS, "rounds": ROUNDS, "key_bits": bits,
            "block": setup.N // setup.K, "engine_run_s": serve_s,
            "launches": serve["launches"],
            "rows_launches": serve["rows_launches"],
            "fused_launches": serve["fused_launches"],
            "fused_ops": serve["fused_ops"],
            "chain_bit_exact_vs_plain": exact, "ok": ok}


# ---------------------------------------------------------------------------
# four-chip phase: the batch sharding of paillier_batch._shard_batch
# ---------------------------------------------------------------------------

def _devices_of(x) -> list[int]:
    return sorted(d.id for d in x.sharding.device_set)


def _shard_run(bk, ms, Ks, rng_seed: int) -> dict:
    from repro.core import paillier_batch as pb
    from repro.core.cipher_tensor import CipherTensor

    cs = pb.enc_ct(bk, ms, random.Random(rng_seed))
    rows = Ks.shape[0]
    n = Ks.shape[2]
    mv = pb.matvec_many(bk, Ks, [CipherTensor(bk, cs.limbs[b * n:(b + 1) * n])
                                 for b in range(rows)])
    dec = pb.dec_vec(bk, cs)
    return {"enc": cs.to_ints(), "enc_devices": _devices_of(cs.limbs),
            "matvec": [c.to_ints() for c in mv],
            "matvec_devices": _devices_of(mv[0].limbs),
            "dec": dec}


def _scalar_reference(key, ms, Ks, rng_seed: int) -> dict:
    from repro.core import paillier as gold

    rng = random.Random(rng_seed)
    enc = [gold.encrypt_crt(key, m, gold.rand_r(key, rng)) for m in ms]
    rows, M, n = Ks.shape
    mv = []
    for b in range(rows):
        cs = enc[b * n:(b + 1) * n]
        mv.append([_prod_pow(cs, [int(k) for k in Ks[b, i]], key.n2)
                   for i in range(M)])
    return {"enc": enc, "matvec": mv,
            "dec": [gold.decrypt_crt(key, c) for c in enc]}


def _prod_pow(cs, ks, n2: int) -> int:
    acc = 1
    for c, k in zip(cs, ks):
        acc = acc * pow(c, k, n2) % n2
    return acc


def four_chip_phase(key_bits: int, rows: int, M: int, n: int) -> dict:
    """Batched enc, matvec and dec of ``rows * n`` values sharded over the
    local devices, against one device and the scalar reference."""
    import numpy as np
    from repro.core import paillier as gold
    from repro.core import paillier_batch as pb
    from repro.launch import mesh as mesh_mod

    key = gold.keygen(key_bits, random.Random(SEED))
    bk = pb.make_batch_key(key)
    rng = random.Random(SEED + 3)
    ms = [rng.randrange(1 << 100) for _ in range(rows * n)]
    Ks = np.array([[[rng.randrange(1 << 50) for _ in range(n)]
                    for _ in range(M)] for _ in range(rows)], dtype=object)
    t0 = time.perf_counter()
    sharded = _shard_run(bk, ms, Ks, SEED + 4)
    sharded_s = time.perf_counter() - t0
    with mock.patch.object(mesh_mod, "kernel_mesh", lambda: None):
        t0 = time.perf_counter()
        single = _shard_run(bk, ms, Ks, SEED + 4)
        single_s = time.perf_counter() - t0
    ref = _scalar_reference(key, ms, Ks, SEED + 4)
    verdict = {op: sharded[op] == single[op] == ref[op]
               for op in ("enc", "matvec", "dec")}
    return {"key_bits": key.n.bit_length(), "batch": rows * n,
            "matvec_exps": rows * M * n, "sharded_s": sharded_s,
            "single_s": single_s,
            "enc_devices": sharded["enc_devices"],
            "matvec_devices": sharded["matvec_devices"],
            "single_enc_devices": single["enc_devices"],
            "bit_exact": verdict, "ok": all(verdict.values())}


# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the batch-sharding path over four "
                         "devices, against one device and the scalar "
                         "reference")
    ap.add_argument("--rehearse", action="store_true",
                    help=f"run on the CPU at {REHEARSE_KEY_BITS}-bit keys; "
                         "never reports ok")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    # a smoke run is not part of the run history
    os.environ.setdefault("REPRO_LEDGER", "off")

    import jax
    from repro.kernels import compile_cache
    from repro.runtime import dispatch

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    want_platform = "cpu" if args.rehearse else "tpu"
    if device["platform"] != want_platform:
        print(f"chip_smoke: found {device['platform']} devices, need "
              f"{want_platform}", file=sys.stderr)
        return 1
    want_count = 4 if args.four_chips else 1
    if device["count"] != want_count:
        print(f"chip_smoke: found {device['count']} devices, need "
              f"{want_count}", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    key_bits, factor = ((REHEARSE_KEY_BITS, REHEARSE_FACTOR) if args.rehearse
                        else (KEY_BITS, FACTOR))
    emit({"device": device, "dispatch_device_kind": dispatch.device_kind(),
          "jax": jax.__version__, "compile_cache": cache,
          "key_bits": key_bits})

    if args.four_chips:
        lines = [run_phase("four_chips_divisible", four_chip_phase,
                           key_bits, 2, 2, 4),
                 run_phase("four_chips_not_divisible", four_chip_phase,
                           key_bits, 1, 3, 9)]
    else:
        lines = [run_phase("sample", sample_phase, key_bits),
                 run_phase("protocol", protocol_phase, key_bits, factor),
                 run_phase("serving", serving_phase, key_bits, factor)]
    failed = [line["phase"] for line in lines if not line["ok"]]
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        emit({"rehearsal": "passed", "device": device})
        return 0
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())

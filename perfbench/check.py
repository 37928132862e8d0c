"""The comparison that decides a run's ``correct``.

Two layers are judged against the plain reference (``reference.py``):

* limb ladders and host path: every answer of every homomorphic operation
  (enc, add, matvec, dec) of the checked rounds, and of the share phase,
  is read back with textbook Paillier under the key the configuration's
  key seed fixes, and judged by its own inputs.  ``wrong_answers`` counts
  the elements that disagree (limit 0: the arithmetic is exact);
* protocol: the program's iterate after its last round against float64
  distributed ADMM run for as many rounds on the same data.  ``x_gap``
  is the largest absolute difference over every coordinate of every
  deployment; its limit comes from the configuration file.
"""
from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from . import reference


def _ints(x) -> list[int]:
    if hasattr(x, "to_ints"):
        return [int(v) for v in x.to_ints()]
    return [int(v) for v in np.asarray(x, dtype=object).reshape(-1)]


def _answers(op: str, args: tuple, res):
    """(inputs as ints, answer as ints) of one recorded operation."""
    if op == "matvec":
        K = np.asarray(args[0], dtype=object)
        return ([[int(k) for k in row] for row in K], _ints(args[1])), \
            _ints(res)
    return tuple(_ints(a) for a in args), _ints(res)


def _ciphertexts(op: str, ins: tuple, out: list) -> list[int]:
    if op == "enc":
        return out
    if op == "add":
        return ins[0] + ins[1] + out
    if op == "matvec":
        return ins[1] + out
    return ins[0]          # dec: its inputs are the ciphertexts


def _decrypt_all(jobs: dict, workers: int) -> dict:
    """{deployment: (p, q), ciphertexts} -> {deployment: {c: m}}, the
    decryptions spread over ``workers`` processes that never touch JAX."""
    tasks = []
    for i, (pq, cs) in jobs.items():
        cs = sorted(set(cs))
        tasks += [(i, pq, cs[w::workers]) for w in range(workers)]
    tasks = [t for t in tasks if t[2]]
    if workers <= 1 or sum(len(t[2]) for t in tasks) < 64:
        parts = [reference.decrypt_many(pq, cs) for _, pq, cs in tasks]
    else:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
            parts = list(pool.map(reference.decrypt_many,
                                  [t[1] for t in tasks], [t[2] for t in tasks]))
    out = {i: {} for i in jobs}
    for (i, _, cs), ms in zip(tasks, parts):
        out[i].update(zip(cs, ms))
    return out


def keys_of(cfg: dict, deps) -> dict:
    """The reference's own keys, from the configuration's key seeds."""
    return {d.index: reference.Key(*reference.keygen(cfg["key_bits"],
                                                     d.key_seed))
            for d in deps}


def check(cfg: dict, deps, recorder, picked: dict, keys: dict) -> dict:
    """Compare the run with the reference; returns the numbers compared,
    each beside its limit, and the verdict."""
    limit = cfg["limits"]
    # the share phase (round -1) and the rounds drawn for checking
    chosen = [(i, t, op, *_answers(op, args, res))
              for i, t, op, args, res in recorder.ops
              if t == -1 or t in picked[i]]
    workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    mine = {i: [c for c in chosen if c[0] == i] for i in keys}
    plain = _decrypt_all(
        {i: ((keys[i].p, keys[i].q),
             [c for _, _, op, ins, out in mine[i]
              for c in _ciphertexts(op, ins, out)]) for i in keys}, workers)
    wrong = checked = 0
    for i, key in keys.items():
        def dec(c, key=key, known=plain[i]):
            m = known.get(c)
            return key.decrypt(c) if m is None else m
        for _, _, op, ins, out in mine[i]:
            wrong += reference.op_errors(key, op, ins, out, dec)
            checked += len(out)
    gap = 0.0
    for d in deps:
        last = len(d.round_ends)
        x_ref = reference.distributed_admm(d.A, d.y, cfg["K"], cfg["rho"],
                                           cfg["lam"], last)
        x = np.asarray(d.master.history[last - 1], np.float64)
        g = float(np.max(np.abs(x - x_ref)))
        gap = max(gap, g if np.isfinite(g) else float("inf"))
    numbers = {
        "wrong_answers": {"value": wrong, "limit": limit["wrong_answers"]},
        "x_gap": {"value": gap, "limit": limit["x_gap"]},
    }
    correct = checked > 0 and all(v["value"] <= v["limit"]
                                  for v in numbers.values())
    return {"correct": correct, "numbers": numbers, "answers_checked": checked,
            "rounds_checked": {i: picked[i] for i in picked}}

"""Adaptive dispatch + crypto-op coalescing: calibration cache round-trip,
cost-table routing, cross-representation bit-exactness, batched-launch
equivalence."""
import json
import os
import random

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import paillier as gold
from repro.core import paillier_vec as pv
from repro.core import protocol
from repro.core.quantization import QuantSpec
from repro.data.synthetic import make_lasso
from repro.runtime import dispatch
from repro.runtime.coalesce import CoalesceQueue, c_matvec_many
from repro.runtime.runner import run_on_runtime
from repro.runtime.scheduler import Scheduler

SPEC = QuantSpec(delta=1e6, zmin=-8.0, zmax=8.0)


def _table(gold_cheap=("enc", "dec"), bits=128, batch=16):
    """Synthetic calibration table: listed ops cheap on gold, rest on vec."""
    e = {}
    for op in dispatch.OPS:
        cheap = op in gold_cheap
        e[op] = (1e-6 if cheap else 1e-3, 1e-3 if cheap else 1e-6)
    return {"version": 1, "entries": {
        f"gold/{bits}/{batch}": {**{op: v[0] for op, v in e.items()},
                                 "convert": 1e-8},
        f"vec/{bits}/{batch}": {**{op: v[1] for op, v in e.items()},
                                "convert": 1e-8},
    }}


# ---------------------------------------------------------------------------
# calibration cache
# ---------------------------------------------------------------------------

def test_calibrate_writes_and_reuses_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "calib.json")
    calls = []
    real = dispatch._measure_backend

    def counting(backend, *a, **kw):
        calls.append(backend)
        return real(backend, *a, **kw)

    monkeypatch.setattr(dispatch, "_measure_backend", counting)
    t1 = dispatch.calibrate(key_bits=(128,), batch_sizes=(8,),
                            backends=("plain", "gold"), path=path)
    assert sorted(calls) == ["gold", "plain"]
    assert json.load(open(path)) == t1
    calls.clear()
    t2 = dispatch.calibrate(key_bits=(128,), batch_sizes=(8,),
                            backends=("plain", "gold"), path=path)
    assert calls == []          # fully served from disk
    assert t1 == t2
    # a new grid point measures only the missing entry
    dispatch.calibrate(key_bits=(128,), batch_sizes=(8, 16),
                       backends=("plain", "gold"), path=path)
    assert sorted(calls) == ["gold", "plain"]


@pytest.fixture
def fresh_compile_cache(monkeypatch):
    """A process that has configured no persistent cache yet; the jax
    config and the module state come back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setitem(compile_cache._state, "enabled", None)
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    yield compile_cache
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_compile_cache_enable_and_opt_out(tmp_path, monkeypatch,
                                          fresh_compile_cache):
    """``JAX_COMPILATION_CACHE_DIR`` is used as given, re-enabling is
    idempotent, a directory a host application already gave JAX is kept,
    and a directory that cannot be created leaves the run uncached."""
    import jax
    compile_cache = fresh_compile_cache
    d = str(tmp_path / "jx")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.enable() == d
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.enable() == d          # idempotent re-enable
    assert compile_cache.stats()["dir"] == d
    # a HOST-configured dir (set by someone else while we think we
    # configured nothing) is respected, not overwritten
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    host = str(tmp_path / "host")
    jax.config.update("jax_compilation_cache_dir", host)
    compile_cache._state["enabled"] = None
    assert compile_cache.enable() == host
    assert jax.config.jax_compilation_cache_dir == host
    # an uncreatable dir: no reconfiguration, the run goes uncached
    (tmp_path / "file").write_text("")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "file" / "sub"))
    compile_cache._state["enabled"] = None
    assert compile_cache.enable() is None
    assert jax.config.jax_compilation_cache_dir == host


def test_compile_cache_default_is_fixed_in_checkout(fresh_compile_cache):
    """Without the variable the cache sits at one fixed, git-ignored path
    inside the checkout — no home directory, temp name, pid or time."""
    import jax
    compile_cache = fresh_compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_warmup_enables_compile_cache(tmp_path, monkeypatch,
                                      fresh_compile_cache):
    """paillier_batch.warmup switches the persistent cache on, so every
    warmed entry point (dispatch.calibrate's warm_key hook, the benches)
    persists its compiles."""
    import jax
    from repro.core import paillier_batch as pb
    d = str(tmp_path / "jx2")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    key = gold.keygen(128, random.Random(3))
    w = pb.warmup(pb.make_batch_key(key), (8,))
    assert w["calls"] == 3
    assert jax.config.jax_compilation_cache_dir == d


def test_entry_points_enable_compile_cache(tmp_path, monkeypatch,
                                           fresh_compile_cache):
    """edge_sim and serve_sim turn the cache on before any protocol work."""
    import jax
    from repro.launch import edge_sim, serve_sim
    for i, mod in enumerate((edge_sim, serve_sim)):
        d = str(tmp_path / f"entry{i}")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        fresh_compile_cache._state["enabled"] = None
        args = (["--edges", "2", "--iters", "1"] if mod is edge_sim
                else ["--tenants", "1", "--cipher", "plain", "--iters", "1"])
        mod.main(args)
        assert jax.config.jax_compilation_cache_dir == d


def test_lookup_nearest_entry():
    t = _table(batch=16)
    assert dispatch.lookup(t, "gold", 128, 999) \
        == t["entries"]["gold/128/16"]
    # nearest key bits tolerated (keygen may deliver n of bits-1)
    assert dispatch.lookup(t, "vec", 127, 16) \
        == t["entries"]["vec/128/16"]
    with pytest.raises(KeyError, match="no calibration"):
        dispatch.lookup(t, "plain", 0, 16)


def test_calibration_keyed_by_device_kind(tmp_path):
    path = str(tmp_path / "calib.json")
    dev = dispatch.device_kind()
    t = dispatch.calibrate(key_bits=(64,), batch_sizes=(8,),
                           backends=("plain",), path=path)
    # entries are written under this device's kind ...
    assert list(t["entries"]) == [f"{dev}/plain/0/8"]
    assert t["version"] == dispatch.TABLE_VERSION
    # ... and lookup never crosses device kinds (4-part keys), while
    # legacy 3-part keys stay device-wildcards for hand-built tables
    other = "tpu" if dev != "tpu" else "gpu"
    t2 = {"version": dispatch.TABLE_VERSION, "entries": {
        f"{other}/gold/128/8": {"enc": 1.0},
        f"{dev}/gold/128/8": {"enc": 2.0},
        "vec/128/8": {"enc": 3.0},
    }}
    assert dispatch.lookup(t2, "gold", 128, 8) == {"enc": 2.0}
    assert dispatch.lookup(t2, "gold", 128, 8, device=other) == {"enc": 1.0}
    assert dispatch.lookup(t2, "vec", 128, 8) == {"enc": 3.0}
    with pytest.raises(KeyError, match="no calibration"):
        dispatch.lookup({"entries": {f"{other}/gold/128/8": {}}},
                        "gold", 128, 8)


FAKE_ENTRY = {"enc": 1.0, "add": 1.0, "matvec": 1.0, "dec": 1.0,
              "convert": 0.0}


def test_calibrate_recovers_from_corrupted_or_partial_cache(tmp_path,
                                                            monkeypatch):
    """A corrupted/partial cache file must fall back to calibrating, not
    crash the load (regression for the TABLE_VERSION 3 format change)."""
    monkeypatch.setattr(dispatch, "_measure_backend",
                        lambda *a, **kw: dict(FAKE_ENTRY))
    path = tmp_path / "calib.json"
    bad_files = (
        b"{truncated",                                   # invalid JSON
        b"[1, 2, 3]",                                    # wrong top type
        b'"a string"',
        json.dumps({"version": dispatch.TABLE_VERSION,
                    "entries": "nope"}).encode(),        # entries not a dict
        json.dumps({"version": dispatch.TABLE_VERSION,
                    "entries": {"cpu/plain/0/8": 7}}).encode(),  # bad entry
        json.dumps({"version": 1, "entries": {}}).encode(),      # stale v1
    )
    for bad in bad_files:
        path.write_bytes(bad)
        t = dispatch.calibrate(key_bits=(64,), batch_sizes=(8,),
                               backends=("plain",), path=str(path))
        assert t["version"] == dispatch.TABLE_VERSION, bad
        assert dispatch.lookup(t, "plain", 0, 8) == FAKE_ENTRY, bad
        # the file was rewritten valid and reloads cleanly
        assert json.load(open(path))["entries"] == t["entries"], bad


def test_legacy_3part_cache_entries_still_resolve_as_wildcards(tmp_path):
    """Hand-built/migrated v3 files may carry device-less 3-part keys;
    after the device-keyed format they must keep matching any device."""
    path = tmp_path / "calib.json"
    legacy = {"version": dispatch.TABLE_VERSION,
              "entries": {"gold/128/8": dict(FAKE_ENTRY)}}
    path.write_text(json.dumps(legacy))
    t = dispatch.calibrate(backends=(), path=str(path))   # pure load
    assert dispatch.lookup(t, "gold", 128, 8) == FAKE_ENTRY
    assert dispatch.lookup(t, "gold", 128, 8, device="tpu") == FAKE_ENTRY


def test_calibrate_warm_key_invokes_warmup_hook(tmp_path, monkeypatch):
    """warm_key pre-compiles the batched path even on a full cache hit."""
    calls = []
    monkeypatch.setattr(dispatch.pb, "warmup",
                        lambda bk, shapes, **kw: calls.append(
                            (bk.key, tuple(shapes))))
    monkeypatch.setattr(dispatch, "_measure_backend",
                        lambda *a, **kw: dict(FAKE_ENTRY))
    key = gold.keygen(96, random.Random(0))
    path = str(tmp_path / "calib.json")
    dispatch.calibrate(key_bits=(96,), batch_sizes=(8,), backends=("plain",),
                       path=path, warm_key=key)
    assert calls == [(key, (8,))]            # shapes default to batch_sizes
    dispatch.calibrate(key_bits=(96,), batch_sizes=(8,), backends=("plain",),
                       path=path, warm_key=key, warm_shapes=(4, (1, 2, 3)))
    assert calls[1] == (key, (4, (1, 2, 3)))  # cache hit still warms


def test_cost_model():
    cm = dispatch.CostModel()
    assert cm.edge_step_cost(8) > 0
    cm2 = dispatch.CostModel.from_table(_table(), "vec", 128, 16)
    assert cm2.unit["enc"] == 1e-3 and cm2.unit["modexp"] == 1e-6


# ---------------------------------------------------------------------------
# adaptive box
# ---------------------------------------------------------------------------

def test_adaptive_box_routes_by_table_and_stays_exact():
    key = gold.keygen(128, random.Random(0))
    box = dispatch.AdaptiveBox(key, random.Random(1),
                               _table(gold_cheap=("enc", "dec")))
    m = np.arange(6, dtype=np.int64)
    c = box.encrypt(m)
    assert c.rep == "gold"
    s = box.add(c, box.encrypt(np.ones(6, dtype=np.int64)))
    assert s.rep == "vec"                       # add is cheap on vec
    K = np.eye(6, dtype=np.int64) * 2
    t = box.matvec(K, s)
    assert t.rep == "vec"
    out = box.decrypt(t)                        # dec converts back to gold
    assert list(np.asarray(out, dtype=np.int64)) \
        == [2 * (x + 1) for x in range(6)]
    picks = dict(box.choices)
    assert picks[("enc", "gold")] == 2
    assert picks[("add", "vec")] == 1 and picks[("matvec", "vec")] == 1
    assert picks[("dec", "gold")] == 1


def test_auto_protocol_bit_exact_vs_plain():
    inst = make_lasso(24, 48, sparsity=0.1, noise=0.01, seed=1)
    plain = protocol.run_protocol(inst.A, inst.y, protocol.ProtocolConfig(
        K=3, lam=0.05, iters=4, spec=SPEC, cipher="plain", seed=0))
    auto = run_on_runtime(inst.A, inst.y, protocol.ProtocolConfig(
        K=3, lam=0.05, iters=4, spec=SPEC, cipher="auto", key_bits=128,
        seed=0), table=_table(gold_cheap=("enc", "dec")))
    assert np.array_equal(plain.history, auto.history)
    assert sum(auto.stats["runtime"]["dispatch"].values()) > 0


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------

def _drain(sched):
    sched.run()


def test_coalesce_plain_equivalent_to_direct():
    box = protocol.PlainBox(SPEC, 8, counter=protocol.OpCounter())
    sched = Scheduler()
    cq = CoalesceQueue(sched, box, counter=box.counter)
    ms = [np.arange(8, dtype=np.int64) + i for i in range(5)]
    got = {}
    for i, m in enumerate(ms):
        cq.submit("enc", (m,), lambda c, i=i: got.setdefault(i, c))
    _drain(sched)
    assert cq.launches == 1 and cq.coalesced_ops == 5
    for i, m in enumerate(ms):
        assert np.array_equal(got[i], box.encrypt(m))
    # counter totals equal the per-op sum (5 batched + 5 direct); no
    # phase was ever set, so the bumps land in the unphased bucket
    # instead of leaking into "init"
    assert box.counter.counts[protocol.PHASE_UNSET]["enc"] == 80


def test_coalesce_gold_add_and_dec_groups():
    key = gold.keygen(128, random.Random(0))
    box = protocol.GoldBox(key, random.Random(1),
                           counter=protocol.OpCounter())
    sched = Scheduler()
    cq = CoalesceQueue(sched, box, counter=box.counter)
    c1 = box.encrypt(np.array([1, 2, 3]))
    c2 = box.encrypt(np.array([10, 20, 30]))
    out = {}
    cq.submit("add", (c1, c2), lambda r: out.setdefault("s", r))
    cq.submit("add", (c2, c2), lambda r: out.setdefault("s2", r))
    _drain(sched)
    cq.submit("dec", (out["s"],), lambda r: out.setdefault("d", r))
    cq.submit("dec", (out["s2"],), lambda r: out.setdefault("d2", r))
    _drain(sched)
    assert list(out["d"]) == [11, 22, 33]
    assert list(out["d2"]) == [20, 40, 60]


def test_coalesce_hold_merges_cross_tick_singletons():
    """hold_ticks > 0: a lone op waits for same-shaped company arriving a
    few ticks later and both run as ONE launch; without holding each
    flushes in its own tick."""
    m = np.arange(8, dtype=np.int64)

    def run(hold):
        box = protocol.PlainBox(SPEC, 8, counter=protocol.OpCounter())
        sched = Scheduler()
        cq = CoalesceQueue(sched, box, counter=box.counter, tick_s=1e-4,
                           hold_ticks=hold)
        got = {}
        cq.submit("enc", (m,), lambda c: got.setdefault(0, c))
        sched.at(3e-4, lambda: cq.submit("enc", (m + 1,),
                                         lambda c: got.setdefault(1, c)))
        sched.run()
        assert np.array_equal(got[0], box.encrypt(m))
        assert np.array_equal(got[1], box.encrypt(m + 1))
        return cq

    held = run(hold=10)
    assert (held.launches, held.coalesced_ops, held.held_flushes) == (1, 2, 1)
    flat = run(hold=0)
    assert (flat.launches, flat.coalesced_ops, flat.held_flushes) == (2, 0, 0)


def test_coalesce_hold_horizon_bounds_the_wait():
    """An op that never gets company still flushes — at the hold horizon,
    not never — and a later lone op opens a fresh hold window."""
    box = protocol.PlainBox(SPEC, 4, counter=protocol.OpCounter())
    sched = Scheduler()
    cq = CoalesceQueue(sched, box, counter=box.counter, tick_s=1e-4,
                       hold_ticks=5)
    got = []
    cq.submit("enc", (np.arange(4, dtype=np.int64),), got.append)
    sched.run()
    assert len(got) == 1 and sched.now <= 7e-4   # flushed at the horizon
    assert cq.launches == 1 and cq.coalesced_ops == 0
    # second lonely op: its own window, its own horizon
    cq.submit("enc", (np.arange(4, dtype=np.int64),), got.append)
    sched.run()
    assert len(got) == 2 and cq.held_flushes == 2


def test_c_matvec_many_matches_per_edge_matvec():
    key = gold.keygen(128, random.Random(0))
    vk = pv.make_vec_key(key)
    rng = random.Random(2)
    B, M, N = 3, 4, 4
    Ks = np.array([[[rng.randrange(50) for _ in range(N)]
                    for _ in range(M)] for _ in range(B)], dtype=np.int64)
    ms = np.array([[rng.randrange(100) for _ in range(N)]
                   for _ in range(B)], dtype=np.int64)
    cs = []
    for b in range(B):
        pool = gold.make_r_pool(key, N, rng)
        rn = jnp.asarray(pv.bi.from_ints(pool, vk.pack_n2.L16))
        cs.append(pv.encrypt_batch(vk, jnp.asarray(ms[b]), rn))
    fused = c_matvec_many(vk, jnp.asarray(Ks), jnp.stack(cs))
    for b in range(B):
        ref = pv.c_matvec(vk, jnp.asarray(Ks[b]), cs[b])
        assert np.array_equal(np.asarray(fused[b]), np.asarray(ref)), b

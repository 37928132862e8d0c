"""Counted work of the ladders and the table of peaks."""
import pytest

from perfbench import bench, drive, reference, rehearse, work


@pytest.fixture(scope="module")
def keys():
    return {bits: reference.Key(*reference.keygen(bits, 6001))
            for bits in (1024, 2048)}


@pytest.mark.parametrize("bits,sq", [(1024, 4 * 256 ** 2),
                                     (2048, 4 * 512 ** 2)])
def test_squaring_priced_at_the_n2_width(keys, bits, sq):
    assert work.n2_bytes(keys[bits].n) == bits // 4
    assert work.squaring_ops(keys[bits].n) == sq


def test_known_shapes(keys):
    k1, k2 = keys[1024], keys[2048]
    # one encryption: r^n, e = bits of n
    assert work.op_ops("enc", ([5],), k1.n, k1.lam) == 4 * 256 ** 2 * 1024
    assert work.op_ops("enc", ([5],), k2.n, k2.lam) == 4 * 512 ** 2 * 2048
    # one decryption: c^lam
    assert work.op_ops("dec", ([7],), k1.n, k1.lam) == \
        4 * 256 ** 2 * k1.lam.bit_length()
    assert work.op_ops("dec", ([7],), k2.n, k2.lam) == \
        4 * 512 ** 2 * k2.lam.bit_length()
    assert 1020 <= k1.lam.bit_length() <= 1023
    # a 2x2 matvec: one exponentiation per entry, e = bits of the entry
    K = [[1, 2], [255, 2 ** 48]]
    assert work.op_ops("matvec", (K, [0, 0]), k1.n, k1.lam) == \
        4 * 256 ** 2 * (1 + 2 + 8 + 49)
    assert work.op_ops("add", ([1], [2]), k1.n, k1.lam) == 0


def test_paper_round_at_2048_bits(keys):
    """fig6_k3_2048: 180 encryptions, 90 decryptions and three 30x30
    matvecs with ~49-bit entries: about 7.2e11 int8 operations."""
    k = keys[2048]
    ops = (work.op_ops("enc", ([0] * 180,), k.n, k.lam)
           + work.op_ops("dec", ([0] * 90,), k.n, k.lam)
           + 3 * work.op_ops("matvec", ([[2 ** 48] * 30] * 30, []),
                             k.n, k.lam))
    assert 7.0e11 < ops < 7.5e11


def _per_round_work(monkeypatch, cfg, env=None, method=None):
    from repro.kernels import ops
    for name, value in (env or {}).items():
        monkeypatch.setenv(name, value)
    if method is not None:
        monkeypatch.setattr(ops, "MODEXP_METHOD", method)
    deps, win, rec, _ = drive.run_traffic(cfg, {"tenants": 1,
                                                "engine": False,
                                                "arrival": "closed"},
                                          seed=4, seconds=0.2)
    keys = {0: reference.Key(*reference.keygen(cfg["key_bits"],
                                               cfg["key_seeds"][0]))}
    return bench.window_work(rec, deps, win, keys) / win.rounds


def test_count_ignores_how_the_ladder_is_built(monkeypatch):
    """Reduction, window method, CRT and the batched path all move the
    time, never the count."""
    rehearse.program_on_path()
    cfg = rehearse.TINY
    base = _per_round_work(monkeypatch, cfg)
    variants = [
        _per_round_work(monkeypatch, cfg, env={"REPRO_REDUCE_IMPL": "barrett"}),
        _per_round_work(monkeypatch, cfg, method="binary"),
        _per_round_work(monkeypatch, dict(cfg, crt=False)),
        _per_round_work(monkeypatch, dict(cfg, gold_batch=False)),
    ]
    assert all(v == base for v in variants)
    k = reference.Key(*reference.keygen(cfg["key_bits"], cfg["key_seeds"][0]))
    nk = cfg["N"] // cfg["K"]
    sq = work.squaring_ops(k.n)
    # 2 K nk encryptions and K nk decryptions; the rest is the matvecs
    assert base > sq * cfg["K"] * nk * (2 * k.n.bit_length()
                                        + k.lam.bit_length())


def test_unknown_device_kind_raises():
    assert work.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_peaks_name_their_source():
    import json
    with open(work.PEAKS_FILE) as f:
        for kind, row in json.load(f).items():
            assert row["source"] and row["int8_ops_per_s"] > 0

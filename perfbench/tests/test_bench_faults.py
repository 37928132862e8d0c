"""The check that decides ``correct`` fails its control and each fault.

The control is the program run at the next precision below the one the
configuration states: the quantizer's Delta = 1e15 (about float64's 52
fraction bits over the clipping span) cut to 2^24 (float32's 24).  The
faults are planted under a tiny CPU run of each traffic mix: a step that
leaves the state unchanged, half of a batch left out, and an answer
altered where it is produced.  The cells run on one chip, so there is no
exchange between chips to leave out.
"""
import numpy as np
import pytest

from perfbench import rehearse

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def control_root(tmp_path_factory):
    cfg = dict(rehearse.TINY, delta=float(2 ** 24))
    return rehearse.make_root(str(tmp_path_factory.mktemp("control")), cfg)


CELLS = ("tiny.solo", "tiny.tenants4")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    rc, line, err = rehearse.run_cell(root, cell, seed=SEED)
    assert rc == 0 and line["correct"] is True, err


@pytest.mark.parametrize("cell", CELLS)
def test_control_lower_precision_fails(control_root, cell):
    rc, line, err = rehearse.run_cell(control_root, cell, seed=SEED)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["checks"]["x_gap"]["value"] > line["checks"]["x_gap"]["limit"]


def _state_unchanged(mp):
    from repro.workloads import base
    mp.setattr(base.Workload, "global_update", lambda self, st, x: None)


def _half_batch(mp):
    from repro.core import paillier_batch as pb
    enc_ct, enc_rows = pb.enc_ct, pb.enc_rows

    def half_ct(bk, ms, rng, backend=None):
        ms = list(np.asarray(ms, dtype=object).reshape(-1))
        h = (len(ms) + 1) // 2
        return enc_ct(bk, ms[:h] + ms[:len(ms) - h], rng, backend=backend)

    def half_rows(items):
        out = []
        for key, ms, rs in items:
            h = (len(ms) + 1) // 2
            out.append((key, ms[:h] + ms[:len(ms) - h], rs))
        return enc_rows(out)
    mp.setattr(pb, "enc_ct", half_ct)
    mp.setattr(pb, "enc_rows", half_rows)


def _answer_altered(mp):
    from repro.core import paillier_batch as pb
    dec_vec, dec_rows = pb.dec_vec, pb.dec_rows

    def bumped(vals):
        return [vals[0] + 1] + list(vals[1:])
    mp.setattr(pb, "dec_vec", lambda *a, **k: bumped(dec_vec(*a, **k)))
    mp.setattr(pb, "dec_rows",
               lambda items: [bumped(v) for v in dec_rows(items)])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,number", [
    (_state_unchanged, "x_gap"),
    (_half_batch, "wrong_answers"),
    (_answer_altered, "wrong_answers"),
])
def test_fault_is_caught(root, monkeypatch, cell, fault, number):
    fault(monkeypatch)
    rc, line, err = rehearse.run_cell(root, cell, seed=SEED)
    assert rc == 0, err
    assert line["correct"] is False, err
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]

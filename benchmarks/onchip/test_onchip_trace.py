"""The trace reduction on small traces: busy union, idle share,
all-to-all time and the part of it no other operation overlaps, and the
idle gaps named by the host span they fall in."""
import gzip
import json
from pathlib import Path

import pytest

import tracereduce as tr

HERE = Path(__file__).resolve().parent


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]
    assert tr.length(tr.union([(0, 10), (2, 3)])) == 10


def test_subtract_counts_only_uncovered_time():
    a = tr.union([(0, 10), (20, 30)])
    b = tr.union([(5, 25)])
    assert tr.subtract(a, b) == 5 + 5
    assert tr.subtract(a, []) == 20
    assert tr.subtract([], b) == 0


def hand_trace():
    # window [0, 100) us; device 0: compute 0-30, a2a 25-45 (5 us under
    # compute), compute 60-70; device 1: a2a 10-20 alone, compute 50-90
    # the while loop on device 1 encloses its body: busy, not a leaf
    us = 1000
    return {
        "devices": {
            0: tr.mark_leaves([
                [0, 30 * us, "fusion.1"], [25 * us, 45 * us, "all-to-all.3"],
                [60 * us, 70 * us, "fusion.2"]]),
            1: tr.mark_leaves([
                [10 * us, 20 * us, "all-to-all.3"],
                [50 * us, 90 * us, "while.4"],
                [50 * us, 90 * us, "convolution.7"]]),
        },
        "host": [[0, 50 * us, "step"], [50 * us, 55 * us, "metrics"],
                 [55 * us, 100 * us, "step"]],
    }


def test_reduce_on_a_hand_made_trace():
    us = 1000
    r = tr.reduce(hand_trace(), (0, 100 * us))
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"][0] == pytest.approx(55e-6)      # 0-45, 60-70
    assert r["busy_s"][1] == pytest.approx(50e-6)
    assert r["a2a_s"][0] == pytest.approx(20e-6)
    assert r["a2a_exposed_s"][0] == pytest.approx(15e-6)
    assert r["a2a_s"][1] == pytest.approx(10e-6)
    assert r["a2a_exposed_s"][1] == pytest.approx(10e-6)
    ops = dict(r["device_ops"])
    assert ops["convolution.7"] == pytest.approx(20e-6)  # per device mean
    assert ops["all-to-all.3"] == pytest.approx(15e-6)
    assert "while.4" not in ops
    # device 0 idles 45-60 (inside step then metrics) and 70-100 (step)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["step", pytest.approx(30e-6)]
    assert gaps[1][1] == pytest.approx(15e-6)
    assert gaps[1][0] == "step"                         # 10 us of 15


def test_window_is_spanned_by_the_host_spans():
    assert tr.window_of(hand_trace()) == (0, 100_000)
    with pytest.raises(ValueError):
        tr.window_of({"devices": {}, "host": []})


def test_reduce_on_a_recorded_chip_trace():
    """Two steps of the one-chip moe-gpt2 cell, recorded on a TPU v5e
    (device operations of TPU:0 and the benchmark's host spans)."""
    path = HERE / "testdata" / "trace_gpt2_1chip.json.gz"
    rec = json.loads(gzip.decompress(path.read_bytes()))
    lo, hi = rec.pop("window")
    r = tr.reduce(rec, (lo, hi))
    ops = [(int(s), int(e)) for s, e, _, _ in rec["devices"]["0"]]
    merged = tr.union(tr.clip(ops, lo, hi))
    assert r["busy_s"][0] == pytest.approx(tr.length(merged) / 1e9)
    assert 0 < r["busy_s"][0] < r["window_s"]
    assert sum(t for _, t in r["device_ops"]) <= r["window_s"] * 1.0001
    assert r["a2a_s"][0] == 0.0                     # one chip: no exchange
    names = {n for n, _ in r["idle_gaps"]}
    assert names <= set(tr.HOST_SPANS) | {"no span"}
    # between the two steps the chip waits on the host's metrics pull
    assert r["idle_gaps"][0][0] == "metrics"
    assert not any(n.startswith("while") for n, _ in r["device_ops"])


def test_op_labels_name_the_instruction_not_its_operands():
    text = ("%fusion.12 = bf16[4,16,768]{2,1,0:T(8,128)(2,1)} "
            "fusion(bf16[4,16,768] %all-to-all.3), kind=kLoop")
    assert tr.op_label(text) == "fusion.12 bf16[4,16,768]"
    assert not tr.is_collective(tr.op_label(text))
    assert tr.is_collective(tr.op_label(
        "%all-to-all.3 = bf16[4,16,768]{2,1,0} all-to-all(%fusion.2)"))

"""The trace reduction, on hand-made intervals and on short traces
recorded on TPU v5e chips (``data/<cell>.xplane.pb.gz``, with the compiled
program's HLO text and the benchmark's host spans of that window beside
each)."""
import gzip
import json
import os

import pytest

import hlo
import xplane
from xplane import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _op(s, e, name="fusion.1", kind="other"):
    return Op(s, e, name, kind)


def test_union_and_uncovered_length():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                              (5, 8)]
    assert xplane._minus([(0, 10)], [(2, 3), (5, 20)]) == 10 - 1 - 5
    assert xplane._minus([(0, 10), (12, 14)], []) == 12


def test_reduce_splits_busy_conv_and_exposed_collectives():
    # window 0..100 ns on two devices; device 0: a conv 10..40, an
    # all-reduce 30..60 that the conv hides for 10 ns; device 1 idle but
    # for one 20 ns op
    ops = {0: [_op(10, 40, "fusion.3", "conv"),
               _op(30, 60, "all-reduce.1", "collective")],
           1: [_op(50, 70, "fusion.3", "conv")]}
    spans = [(0, 100, "window"), (60, 95, "data_wait"), (0, 10, "dispatch")]
    r = xplane.reduce(Trace(ops, {}, spans))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((50 + 20) / 2 * 1e-9)
    assert r["conv_s"] == pytest.approx(50e-9)
    assert r["collective_s"] == pytest.approx(30e-9)
    assert r["collective_exposed_s"] == pytest.approx(20 / 2 * 1e-9)
    # device 0's gaps: 60..100 (host in data_wait), 0..10 (dispatch)
    assert r["idle_gaps"][0] == ["data_wait", pytest.approx(40e-9)]
    assert r["idle_gaps"][1] == ["dispatch", pytest.approx(10e-9)]
    assert r["device_ops"][0][0] == "fusion.3 (conv)"


def test_ops_outside_the_window_do_not_count():
    ops = {0: [_op(0, 50), _op(90, 200)]}
    r = xplane.reduce(Trace(ops, {}, [(40, 100, "window")]))
    assert r["busy_s"] == pytest.approx(20e-9)


RECORDED = sorted(f[:-len(".xplane.pb.gz")] for f in os.listdir(DATA)
                  if f.endswith(".xplane.pb.gz"))


def _recorded(cell):
    base = os.path.join(DATA, cell)
    with gzip.open(base + ".hlo.txt.gz", "rt") as f:
        kinds = hlo.kinds(f.read())
    with open(base + ".spans.json") as f:
        spans = json.load(f)
    with gzip.open(base + ".xplane.pb.gz") as f:
        return xplane.load(data=f.read(), kinds=kinds, host_spans=spans)


@pytest.mark.parametrize("cell", RECORDED)
def test_recorded_trace_reduces(cell):
    t = _recorded(cell)
    chips = 4 if "4chip" in cell else 1
    assert sorted(t.ops) == list(range(chips))
    r = xplane.reduce(t)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert all(g[0] != "none" for g in r["idle_gaps"])
    if cell.startswith("vgg-a"):
        # most of VGG-A's device time is in its convolutions
        assert r["conv_s"] > 0.5 * r["busy_s"] * chips
    else:
        assert r["conv_s"] == 0 < r["matmul_s"]
    assert (r["collective_ops"] > 0) == (chips > 1)

"""The reduction from a trace to busy time, top operations and labelled
idle gaps, checked by hand."""
import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand_trace():
    # window [100, 1100); chip 0 runs a at [50, 150), b at [150, 300),
    # a at [600, 700), c at [1050, 1200); chip 1 runs a at [100, 1100)
    return {"names": ["a", "b", "c"],
            "devices": [[[0, 1, 0, 2], [50, 150, 600, 1050],
                         [100, 150, 100, 150]],
                        [[0], [100], [1000]]],
            "host": [["bench.window", 100, 1000], ["bench.step", 250, 400],
                     ["bench.decode", 280, 30], ["bench.wait", 700, 350],
                     ["other", 0, 2000]]}


def test_busy_is_the_union_clipped_to_the_window_mean_over_chips():
    red = trace_reduce.reduce(_hand_trace(), 2)
    # chip 0: [100, 300) + [600, 700) + [1050, 1100) = 350; chip 1: 1000
    assert red["busy_s"] == pytest.approx((350 + 1000) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(1000 / 1e9)


def test_top_ops_are_clipped_time_mean_per_chip():
    red = trace_reduce.reduce(_hand_trace(), 2)
    ops = dict(red["device_ops"])
    assert ops["a"] == pytest.approx((50 + 100 + 1000) / 2 / 1e9)
    assert ops["b"] == pytest.approx(150 / 2 / 1e9)
    assert ops["c"] == pytest.approx(50 / 2 / 1e9)
    assert [k for k, _ in red["device_ops"]] == ["a", "b", "c"]


def test_gaps_of_the_first_chip_labelled_by_innermost_bench_span():
    red = trace_reduce.reduce(_hand_trace(), 2)
    # chip 0 idles over [300, 600) (midpoint 450: bench.step) and
    # [700, 1050) (midpoint 875: bench.wait)
    assert red["idle_gaps"] == [["bench.wait", pytest.approx(350e-9)],
                                ["bench.step", pytest.approx(300e-9)]]


def test_no_window_span_is_an_error():
    tr = _hand_trace()
    tr["host"] = tr["host"][1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce(tr, 2)


def test_an_operation_inside_another_counts_once():
    tr = {"names": ["loop", "inner"],
          "devices": [[[0, 1, 1], [0, 10, 50], [100, 20, 30]]],
          "host": [["bench.window", 0, 200]]}
    red = trace_reduce.reduce(tr, 1)
    assert red["busy_s"] == pytest.approx(100e-9)
    # the loop has children, so only they are listed
    assert red["device_ops"] == [["inner", pytest.approx(50e-9)]]


def test_recorded_chip_trace():
    """A short window recorded on one v5e (two decode rounds of the chat
    cell); busy, gaps and labels worked out independently of the reducer."""
    path = os.path.join(DATA, "chip_trace.json")
    with open(path) as f:
        rec = json.load(f)
    red = trace_reduce.reduce(rec["trace"], 1)
    w0, w1 = rec["window"]
    _, starts, durs = rec["trace"]["devices"][0]
    ivs = sorted((max(s, w0), min(s + d, w1)) for s, d in zip(starts, durs)
                 if min(s + d, w1) > max(s, w0))
    busy, end = 0, w0
    for a, b in ivs:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert red["idle_gaps"][0][0] == rec["longest_gap_label"]

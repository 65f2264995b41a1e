"""The benchmark's yardstick on hand-made inputs: percentiles, the
throughput window, idle share, histogram deltas, the trace reduction and the
FLOP count.  CPU only, no JAX."""

import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import flops, reduce  # noqa: E402
from perfbench.peaks import peaks_for  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("values,q,expected", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([7.0], 95, 7.0),
    ([10, 0], 25, 2.5),
])
def test_percentile_interpolates_between_order_statistics(values, q,
                                                          expected):
    assert reduce.percentile(values, q) == pytest.approx(expected)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        reduce.percentile([], 50)


def test_iqr_spread_uses_statistics_quantiles():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert reduce.iqr_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


@pytest.mark.parametrize("fences,opens,seconds,expected", [
    # fences every 2 steps, 1 s a step: opens at step 4, first fence at
    # least 5 s later is step 10 -> 6 steps over 6 s
    ([(2, 2.0), (4, 4.0), (6, 6.0), (8, 8.0), (10, 10.0), (12, 12.0)],
     4, 5.0, (6, 6.0, 10)),
    # a fence exactly on the limit closes the window
    ([(4, 0.0), (6, 2.0), (8, 4.0)], 4, 4.0, (4, 4.0, 8)),
    # the window never closes
    ([(2, 0.0), (4, 1.0), (6, 2.0)], 4, 5.0, None),
    # the opening fence was never logged
    ([(2, 0.0), (6, 2.0)], 4, 1.0, None),
])
def test_throughput_window_is_whole_steps_between_fences(fences, opens,
                                                         seconds, expected):
    assert reduce.throughput_window(fences, opens, seconds) == expected


@pytest.mark.parametrize("intervals,length", [
    ([(0, 1), (2, 3)], 2.0),
    ([(0, 2), (1, 3)], 3.0),
    ([(0, 5), (1, 2), (3, 4)], 5.0),
    ([], 0.0),
])
def test_union_length(intervals, length):
    assert reduce.union_length(intervals) == pytest.approx(length)


def test_idle_share():
    assert reduce.idle_share(7.5, 10.0) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        reduce.idle_share(1.0, 0.0)


def test_histogram_delta_and_percentile():
    base = 2.0 ** 0.25
    before = {"count": 2, "sum": 2.0, "zeros": 0, "buckets": {0: 2}}
    after = {"count": 6, "sum": 2.0 + 4 * 0.01, "zeros": 0,
             "buckets": {0: 2, "-27": 4}}     # keys may be strings (JSON)
    delta = reduce.histogram_delta(before, after)
    assert delta["count"] == 4
    assert delta["sum"] == pytest.approx(0.04)
    assert delta["buckets"] == {-27: 4}
    assert reduce.histogram_percentile(delta, 50) == pytest.approx(
        base ** -27.5)
    assert reduce.histogram_percentile(
        reduce.histogram_delta(after, after), 50) is None


def test_self_times_take_children_out_of_an_enclosing_op():
    events = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
              ("copy.2", 5.0, 9.0), ("fusion.3", 11.0, 12.0)]
    got = {name: self_s for name, _, _, self_s in reduce.self_times(events)}
    assert got == pytest.approx({"while": 3.0, "fusion.1": 3.0,
                                 "copy.2": 4.0, "fusion.3": 1.0})


def test_reduce_trace_on_a_hand_made_trace():
    trace = {
        "device": {
            "/device:TPU:0": [("fusion.1", 0.0, 1.0),
                              ("all-reduce.1", 1.0, 1.5),
                              ("fusion.2", 2.0, 3.0)],
            "/device:TPU:1": [("fusion.1", 0.0, 1.0),
                              ("all-reduce.1", 1.0, 2.0),
                              ("fusion.2", 2.0, 3.0)],
        },
        "host": [("bench/data", 1.4, 1.9)],
    }
    got = reduce.reduce_trace(trace)
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx((2.5 + 3.0) / 2)
    assert got["window_s"] == pytest.approx(3.0)
    assert got["collective_exposed_s"] == pytest.approx(0.75)
    assert dict(got["device_ops"])["fusion.1"] == pytest.approx(1.0)
    assert got["idle_gaps"] == [
        ["bench/data_after_all-reduce.1", pytest.approx(0.5)]]
    assert reduce.reduce_trace({"device": {}, "host": []}) is None


def test_reduce_trace_on_the_recorded_trace():
    """A slice of a trace recorded on the v5e (PERF.md says from which
    run): the reduction reproduces the numbers worked out by hand from the
    events in the file."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    trace = {"device": {k: [tuple(e) for e in v]
                        for k, v in recorded["device"].items()},
             "host": [tuple(e) for e in recorded["host"]]}
    got = reduce.reduce_trace(trace)
    expected = recorded["expected"]
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert got["device_ops"][0][0] == expected["top_op"]
    assert 0.0 <= reduce.idle_share(got["busy_s"], got["window_s"]) < 1.0


@pytest.mark.parametrize("name,params,gflop_per_token", [
    ("gpt2-medium", 354_823_168, 2.422708224),
    ("gpt2-large", 774_030_080, 5.1989376),
])
def test_flop_function_against_hand_counts(name, params, gflop_per_token):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    assert flops.param_count(config) == params == config["parameters"]
    d, layers, vocab = config["n_embd"], config["n_layer"], 50257
    # by hand: 12 d^2 matmul weights a block, the head once; 6 FLOPs a
    # weight a token; 12 L d S for attention at S = 1024
    by_hand = 6 * (layers * 12 * d * d + vocab * d) + 12 * layers * d * 1024
    assert flops.train_flops_per_token(config, 1024) == by_hand
    assert by_hand / 1e9 == pytest.approx(gflop_per_token)
    # 24,000 tokens/s of gpt2-medium on one v5e is 29.5% of 197 TFLOP/s
    if name == "gpt2-medium":
        assert flops.mfu_pct(config, 1024, 24000.0, 1, 197e12) == \
            pytest.approx(100 * 24000 * by_hand / 197e12)
        assert flops.kv_bytes_per_position(config) == 98_304


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")

"""The benchmark's own arithmetic: the schedule, the percentiles, the
interval sums of the trace reduction, the Prometheus reader."""

import math

import numpy as np
import pytest

from benchmark import harness, prom, trace_reduce, traffic


def test_schedule_is_a_function_of_the_seed():
    a = traffic.open_schedule(5.2, 40, seed=2147483653)
    b = traffic.open_schedule(5.2, 40, seed=2147483653)
    c = traffic.open_schedule(5.2, 40, seed=7)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == len(c) == 208
    # every seed gets the same succession of gaps from another phase
    ga, gc = np.diff(a, prepend=0), np.diff(c, prepend=0)
    shift = (7 - 2147483653) % 208
    assert np.allclose(np.roll(ga, -shift), gc)
    fixed = traffic.open_schedule(5.2, 40, seed=7, phase_from_seed=False)
    assert np.array_equal(fixed, traffic.open_schedule(
        5.2, 40, seed=99, phase_from_seed=False))
    assert 0 < a[0] and a[-1] < 40 and np.all(np.diff(a) > 0)


def test_gaps_are_exponential_quantiles():
    due = traffic.open_schedule(10.0, 100, seed=1)
    gaps = np.diff(due, prepend=0)
    assert abs(gaps.mean() - 0.1) < 1e-3
    assert abs(np.median(gaps) - 0.1 * math.log(2)) < 2e-3


def test_pairs_and_sample_come_from_the_seed():
    o = traffic.pair_order(200, 64, seed=3)
    assert sorted(o[:64]) == list(range(64))
    assert np.array_equal(o, traffic.pair_order(200, 64, seed=3))
    ids = traffic.sample_ids(200, 6, seed=3)
    assert len(ids) == 6 and ids[-1] == 199 and len(set(ids)) == 6
    assert ids == traffic.sample_ids(200, 6, seed=3)


def test_latency_is_timed_from_the_due_instant():
    # a send that takes 50 ms, and a generator that cannot start the second
    # request before the first returns would still owe the wait
    import time

    def send(i):
        time.sleep(0.05)
        if i == 2:
            raise RuntimeError("shed")
        return i

    res = traffic.run_open_loop([0.0, 0.0, 0.01, 0.02], send,
                                max_in_flight=1, drain_s=5)
    assert res.ok.tolist() == [True, True, False, True]
    lat = res.done - res.due
    assert lat[1] >= 0.095          # waited for request 0, still counted
    assert lat[3] >= 0.17
    assert len(res.latency_s) == 3


def test_percentiles_count_failures_as_misses():
    lat = [10.0, 20.0, 30.0, 40.0]
    assert harness.percentile(lat, 50, attempted=4) == 20.0
    assert harness.percentile(lat, 50, attempted=8) == 40.0
    assert harness.percentile(lat, 95, attempted=8) == math.inf
    assert harness.percentile(list(range(1, 101)), 95, 100) == 95
    with pytest.raises(ValueError):
        harness.percentile(lat, 50, attempted=3)


def test_union_gaps_and_self_time():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert trace_reduce.union_seconds(iv) == pytest.approx(30e-9)
    g = trace_reduce.gaps([(0, 10, "a"), (5, 20, "b"), (30, 40, "c")],
                          (0, 50))
    assert g == [(20, 30, "b"), (40, 50, "c")]
    events = [(0, 100, "while", ""), (10, 40, "conv", ""),
              (50, 90, "conv", ""), (60, 70, "inner", "")]
    own = trace_reduce.self_times(events)
    assert own["while"] == pytest.approx(30e-9)
    assert own["conv"] == pytest.approx(60e-9)
    assert own["inner"] == pytest.approx(10e-9)


def test_prometheus_text():
    text = ("# HELP x\nserve_queue_wait_seconds_sum 1.5\n"
            "serve_queue_wait_seconds_count 3\n"
            'serve_dispatches_total{batch="2"} 4\n'
            'serve_dispatches_total{batch="8"} 1\n')
    s = prom.parse(text)
    assert prom.total(s, "serve_dispatches_total") == 5
    assert prom.total(s, "serve_dispatches_total", 'batch="8"') == 1
    d = prom.delta({"serve_queue_wait_seconds_sum": 0.5}, s)
    assert d["serve_queue_wait_seconds_sum"] == 1.0
    from benchmark import layer_metrics

    assert layer_metrics.histogram_mean_ms(
        {"counters": s}, "serve_queue_wait_seconds") == pytest.approx(500.0)
    assert layer_metrics.histogram_mean_ms({"counters": {}}, "nope") is None

"""``host_spans``: the device's idle time by the program's own spans.  On a
plane built by hand, then on a small serving trace recorded on the v5e (my
chip run, PR 25, call 8: the accuracy configuration's model at 64x128, 2
iterations, batch size 1 behind the serving engine, three requests in 40 ms
as three dispatches of one program, Python tracer off, host tracer level 1;
``.counters.json`` is what the same engine's ``/metrics`` grew by from just
before the capture was started to just after it was stopped)."""

import json
import os
import shutil

import pytest

from benchmark import harness, host_spans, layer_metrics, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVING = os.path.join(DATA, "tiny_serving_64x128_2iters.xplane.pb")
DEVICE_ONLY = os.path.join(DATA, "tiny_accuracy_64x128_2iters.xplane.pb")
CELL = "accuracy.serve.kitti-steady"


# ----------------------------------------------------------- a plane by hand
class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = ()


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Event(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, *planes):
        self.planes = list(planes)


def _by_hand(worker_events, other_events=()):
    """Two device operations, 100-200 and 700-800, in a capture whose
    events run from 0 to 1000."""
    return _Profile(
        _Plane("/device:TPU:0", [_Line("XLA Ops", [("%a", 100, 200),
                                                    ("%b", 700, 800)]),
                                 _Line("XLA Modules", [("jit_f", 90, 300)])]),
        _Plane("/host:CPU", [_Line("python", list(worker_events)),
                             _Line("python", list(other_events)),
                             _Line("edges", [("mark", 0, 1),
                                             ("mark", 999, 1000)])]))


def test_a_gap_is_split_between_two_spans_and_a_remainder():
    att = host_spans.attribute(_by_hand(
        [("serve.execute", 50, 210), ("serve.fetch", 210, 400),
         ("serve.wait_work", 450, 650), ("serve.execute", 660, 820)],
        # another thread's spans take nothing: it is not the worker's line
        [("serve.decode", 300, 600)]))
    by = att["idle_by_span"]
    # the stretch the worker's spans cover is 50-820; the capture's edges,
    # 0-50 and 820-1000, are no span's and are left out
    assert att["stretch_s"] == pytest.approx(770e-9)
    assert att["idle_s"] == pytest.approx(570e-9)
    # the gap 200-700: execute 10, fetch 190, nothing 50, wait 200, nothing
    # 10, execute 40; the first gap, from 50: execute 50; the last, to 820:
    # execute 20
    assert by["serve.fetch"] == pytest.approx(190e-9)
    assert by["serve.wait_work"] == pytest.approx(200e-9)
    assert by["serve.execute"] == pytest.approx((10 + 40 + 50 + 20) * 1e-9)
    assert by[host_spans.UNATTRIBUTED] == pytest.approx((50 + 10) * 1e-9)
    assert sum(by.values()) == pytest.approx(att["idle_s"])
    assert att["executes"] == 2
    # ends of the spans 210 and 820 against the operations' 200 and 800
    assert att["skew_ms"] == {"median": pytest.approx(15e-6),
                              "max": pytest.approx(20e-6)}


def test_a_wait_the_capture_did_not_record_is_still_the_wait():
    """A capture holds a span only if it opened inside it: between one
    dispatch's ``serve.respond`` and the next one's ``serve.assemble`` the
    worker waits for work, event or no event."""
    cycle = [("serve.assemble", 0, 90), ("serve.execute", 90, 210),
             ("serve.respond", 210, 230)]
    later = [(n, a + 600, b + 600) for n, a, b in cycle]
    lost = host_spans.attribute(_by_hand(cycle + later))
    held = host_spans.attribute(_by_hand(
        cycle + [("serve.wait_work", 231, 599)] + later))
    assert lost["idle_by_span"] == pytest.approx(held["idle_by_span"])
    # the gap 200-700: execute 10, respond 20, the wait 370, assemble 90,
    # execute 10
    assert lost["idle_by_span"]["serve.wait_work"] == pytest.approx(370e-9)
    assert host_spans.UNATTRIBUTED not in lost["idle_by_span"]


def test_the_innermost_span_takes_the_instant():
    stretches = host_spans.innermost(
        [(0, 100, "outer"), (10, 30, "inner"), (30, 40, "next"),
         (150, 160, "later")])
    assert stretches == [(0, 10, "outer"), (10, 30, "inner"),
                         (30, 40, "next"), (40, 100, "outer"),
                         (150, 160, "later")]
    att = host_spans.attribute(_by_hand(
        [("infer.execute", 90, 210), ("infer.unpad", 210, 700),
         ("infer.fetch", 300, 500)]))
    assert att["idle_by_span"]["infer.fetch"] == pytest.approx(200e-9)
    assert att["idle_by_span"]["infer.unpad"] == pytest.approx(290e-9)


def test_no_program_span_or_no_device_reads_none():
    assert host_spans.attribute(_by_hand([("Eigen", 0, 500)])) is None
    # spans, but no line holds an execute: no line is the worker's
    assert host_spans.attribute(
        _by_hand([("serve.decode", 0, 500)])) is None
    host_only = _Profile(_Plane("/host:CPU", [
        _Line("tf_XLAEigen/1", [("op", 100, 200)]),
        _Line("python", [("serve.execute", 50, 300)])]))
    assert host_spans.attribute(host_only) is None
    stand_in = host_spans.attribute(host_only, host_stand_in=True)
    assert stand_in["idle_by_span"] == {
        "serve.execute": pytest.approx(150e-9)}


# ------------------------------------------------------- the recorded traces
@pytest.fixture()
def observed(tmp_path, monkeypatch):
    """What a traced run of the steady cell hands its readers, made of the
    recorded trace: the file under the cell's work directory, its reduction,
    and the counters of the same capture."""
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path))
    where = tmp_path / CELL / "profiles" / "ondemand-0"
    where.mkdir(parents=True)

    def make(trace=SERVING):
        shutil.copy(trace, where / "t.xplane.pb")
        with open(SERVING.replace(".xplane.pb", ".counters.json")) as f:
            counters = json.load(f)
        return {"cell": {"name": CELL}, "counters": counters,
                "trace": trace_reduce.reduce_file(
                    trace, {"gru_iter": "%gru_iter"}, {})}

    return make


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(harness.BENCH_DIR, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("suffix", [".lat", ".thr"])
def test_the_three_shares_are_the_stretchs_idle_share(observed, suffix):
    """Over the stretch the worker's spans cover, not the reduction's
    window: the window's edges, which no span can own, make the whole
    window's ``device_idle_pct`` the larger of the two."""
    obs = observed()
    shares = [_reader(name + suffix)(obs)
              for name in ("idle_in_engine_pct", "idle_in_queue_hold_pct",
                           "idle_unattributed_pct")]
    assert all(s is not None and s >= 0 for s in shares)
    att = host_spans.for_run(obs)
    assert sum(shares) == pytest.approx(
        100.0 * att["idle_s"] / att["stretch_s"], abs=1e-6)
    assert att["stretch_s"] < obs["trace"]["window_s"]
    assert att["idle_s"] < obs["trace"]["window_s"] - obs["trace"]["busy_s"]


def test_each_reader_against_the_recorded_trace(observed):
    obs = observed()
    att = host_spans.for_run(obs)
    read = {name: _reader(name + ".lat")(obs)
            for name in ("engine_pre_ms", "engine_post_ms",
                         "idle_in_engine_pct", "idle_in_queue_hold_pct",
                         "idle_unattributed_pct")}
    assert read == pytest.approx(RECORDED_READINGS, rel=1e-9)
    # every dispatch of the capture is in the counters and in the trace
    assert obs["counters"]["serve_batches_total"] == att["executes"] == 3
    # the host learns that the device has finished 1.8 ms after it has
    assert att["skew_ms"]["median"] == pytest.approx(1.845152, rel=1e-6)
    assert att["idle_by_span"]["serve.wait_work"] == pytest.approx(
        0.042744894, rel=1e-6)


def test_readers_read_none_without_program_spans(observed):
    """A program older than the spans (the parent of PR 25): the trace holds
    device operations and no ``serve.*`` event, ``/metrics`` no such
    histogram; the metric is left out, never 0."""
    obs = observed(DEVICE_ONLY)
    obs["counters"] = {"serve_batches_total": 5.0}
    assert layer_metrics.device_idle_pct(obs) is not None
    for name in ("engine_pre_ms", "engine_post_ms", "idle_in_engine_pct",
                 "idle_in_queue_hold_pct", "idle_unattributed_pct"):
        for suffix in (".lat", ".thr"):
            assert _reader(name + suffix)(obs) is None, name + suffix
    # an untraced run, and a run whose work directory holds no trace
    obs["trace"] = None
    assert _reader("idle_in_engine_pct.lat")(obs) is None
    assert host_spans.trace_file("no.such.cell") is None


# my chip run, PR 25, call 8: 1.08 ms of assemble + upload and 0.62 ms of
# fetch + account + respond a dispatch; idle 92.75 % of the 57.0 ms that the
# worker's spans cover (92.79 % of the 57.3 ms window)
RECORDED_READINGS = {"engine_pre_ms": 1.0825099999999999,
                     "engine_post_ms": 0.6166966666666667,
                     "idle_in_engine_pct": 7.448791763276118,
                     "idle_in_queue_hold_pct": 74.97105902982736,
                     "idle_unattributed_pct": 10.33499614743041}

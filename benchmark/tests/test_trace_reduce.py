"""The reduction on a small trace recorded on the v5e (my chip run, PR 24:
the accuracy configuration at 64x128, 2 iterations, batch 1, two calls,
host and python tracers off)."""

import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_accuracy_64x128_2iters.xplane.pb")
SCOPES = {"gru_iter": "%gru_iter"}
KERNELS = {"corr_lookup": ["%gru_iter", "tpu_custom_call"],
           "gru_fused": ["%gru", "tpu_custom_call"],
           "absent": ["%no_such_kernel"]}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE, SCOPES, KERNELS)


def test_busy_is_a_union_inside_the_window(reduced):
    assert reduced["device_planes"] == ["/device:TPU:0"]
    assert not reduced["stand_in_host_plane"]
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # two calls of ~0.77 ms of device each, a fetch and a dispatch between
    assert reduced["busy_s"] == pytest.approx(1.543e-3, rel=1e-3)
    assert reduced["window_s"] == pytest.approx(4.078e-3, rel=1e-3)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.55 < idle < 0.70


def test_the_loop_is_the_scopes(reduced):
    # the refinement loop (its while and all it holds), not only the
    # kernels that bear the scope's name
    loop = reduced["scopes"]["gru_iter"]
    lookup = reduced["kernels"]["corr_lookup"]["seconds"]
    assert lookup < loop < reduced["busy_s"]
    assert loop / reduced["busy_s"] == pytest.approx(0.418, abs=0.01)


def test_kernels_by_name(reduced):
    k = reduced["kernels"]
    assert "absent" not in k                # nothing to read: left out
    assert k["corr_lookup"]["launches"] == 4        # 2 calls x 2 iterations
    assert k["gru_fused"]["launches"] == 16         # + 3 levels x 2 x 2
    assert k["corr_lookup"]["seconds"] == pytest.approx(1.537e-4, rel=1e-2)


def test_breakdown_is_short(reduced):
    b = reduced["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(len(name) <= 96 + 4 for name, _ in b["device_ops"])
    assert b["device_ops"][0][0].startswith("%gru08")
    # self times add up to the busy time: nothing counted twice
    own = trace_reduce.self_times(
        [e for p in [__import__("jax").profiler.ProfileData.from_file(TRACE)]
         for pl in trace_reduce.device_planes(p) for ln in pl.lines
         if ln.name in trace_reduce.OPS_LINES
         for e in trace_reduce._events(ln)])
    assert sum(own.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_a_clocked_window_takes_the_traces_place():
    r = trace_reduce.reduce_file(TRACE, SCOPES, {}, window_s=0.5)
    assert r["window_s"] == 0.5


def test_a_kernels_work_is_counted_from_its_own_events(reduced):
    # two calls x two iterations of one 64x128 pair at 1/4 resolution; at
    # this size a launch writes all four levels' taps (at KITTI's size the
    # kernel makes a launch a level), and the count comes out the same
    from benchmark import flops

    k = reduced["kernels"]["corr_lookup"]
    assert k["out_elements"] == 4 * 16 * 32 * 36
    model = {"n_downsample": 2, "corr_levels": 4, "corr_radius": 4}
    assert k["out_elements"] / flops.lookup_taps(model, 64, 128) == 4
    assert trace_reduce.result_elements(
        "%gru_iter.7 = bf16[6144,156,36]{2,1,0:T(8,128)(2,1)} custom-call("
    ) == 6144 * 156 * 36
    assert trace_reduce.result_elements("XlaModule:#hlo=jit_f#") == 0


def test_a_trace_with_no_device_plane_is_refused():
    class Event:
        name, start_ns, duration_ns, stats = "Eigen", 0.0, 5.0, ()

    class Line:
        name, events = "XLAEigen/1", [Event()]

    class Plane:
        name, lines = "/host:CPU", [Line()]

    class Profile:
        planes = [Plane()]

    from benchmark import harness

    with pytest.raises(harness.BenchError):
        trace_reduce.reduce_profile(Profile(), SCOPES, KERNELS)
    r = trace_reduce.reduce_profile(Profile(), SCOPES, KERNELS,
                                    host_stand_in=True)
    assert r["stand_in_host_plane"] and r["busy_s"] == pytest.approx(5e-9)

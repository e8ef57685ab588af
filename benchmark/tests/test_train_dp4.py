"""The four-chip training cell ``sceneflow.train.b8-dp4``: its committed
file against the one-chip cell's, its entry in ``BENCHMARK.json``, the
all-reduce share's reader, and the reduction of a trace with four device
planes (a synthetic profile: the per-plane mean of busy and kernel
seconds)."""

import json
import os

import pytest

from benchmark import harness, trace_reduce

B4, DP4 = "sceneflow.train.b4", "sceneflow.train.b8-dp4"
TRAIN_METRICS = {"step_mfu_pct.train", "device_idle_pct.train",
                 "data_wait_ms.train", "corr_lookup_bwd_roofline.train",
                 "allreduce_share_pct.train"}


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = v
    return out


def test_the_file_is_the_one_chip_cells_but_for_batch_devices_and_patterns():
    b4 = _flat(harness.load_json("workloads", B4 + ".json"))
    dp4 = _flat(harness.load_json("workloads", DP4 + ".json"))
    differ = {k for k in set(b4) | set(dp4) if b4.get(k) != dp4.get(k)}
    assert differ == {"name", "why", "traffic.batch_size",
                      "traffic.data_parallel",
                      "trace.kernels.corr_lookup_bwd",
                      "trace.kernels.allreduce",
                      "compare.limits.grad_gap_units_fnet"}
    # fnet's largest sound reading at batch 8 is 1.29 (1.24 at batch 4)
    assert (b4["compare.limits.grad_gap_units_fnet"],
            dp4["compare.limits.grad_gap_units_fnet"]) == (1.9, 2.2)
    assert (dp4["traffic.batch_size"], dp4["traffic.data_parallel"]) == (8, 4)
    # the backward's first cotangent: rows = 2 pairs a chip x 80
    assert dp4["trace.kernels.corr_lookup_bwd"] == [
        "tpu_custom_call", " = (bf16[160,180,180]"]
    assert "trace.kernels.allreduce" not in b4


def test_benchmark_json_gives_it_four_chips_and_the_train_metrics():
    cell, one_chip = harness.load_cell(DP4), harness.load_cell(B4)
    assert cell["chips"] == 4 and one_chip["chips"] == 1
    assert cell["config"] == one_chip["config"]
    assert {m["name"] for m in cell["per_layer"]} == TRAIN_METRICS
    assert {m["name"] for m in one_chip["per_layer"]} == TRAIN_METRICS - {
        "allreduce_share_pct.train"}
    assert ({m["name"] for m in cell["end_to_end"]}
            == {"pairs_per_s", "setup_s"})
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    assert sum(c["chips"] == 4 for c in cells) == 1 <= len(cells) // 4


def _allreduce_share(observed):
    """The reader as the harness loads it: its number, or None where the
    metric is left out of the line."""
    name = "allreduce_share_pct.train"
    out = harness.read_per_layer(
        {"per_layer": [{"name": name, "unit": "%"}]}, observed)
    return out[name]["value"] if out else None


@pytest.mark.parametrize("observed,want", [
    ({"trace": {"busy_s": 2.0, "kernels": {
        "allreduce": {"seconds": 0.05, "launches": 12.0,
                      "out_elements": 0.0}}}}, 2.5),
    ({"trace": {"busy_s": 2.0, "kernels": {}}}, None),   # one chip: none
    ({"trace": None}, None), ({}, None)])
def test_the_allreduce_share_reads_the_kernel_or_nothing(observed, want):
    got = _allreduce_share(observed)
    assert got == (pytest.approx(want) if want is not None else None)


class _Event:
    stats = ()

    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (name, start_ns,
                                                      duration_ns)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


ALLREDUCE = ("%all-reduce.{i} = f32[1024]{{0}} all-reduce(f32[1024]{{0}} "
             "%fusion.{i}), replica_groups={{{{0,1,2,3}}}}, to_apply=%add")
USER = ("%fusion.9 = f32[1024]{0} fusion(f32[1024]{0} %all-reduce.1), "
        "kind=kLoop")


def test_four_device_planes_reduce_to_their_mean():
    """Plane i is busy (i + 1) x 100 ns with a fusion, then runs one
    all-reduce of (i + 1) x 10 ns, then an operation that only READS the
    all-reduce's result (its name holds the operand's): the reduction gives
    the mean over the planes, and the pattern with its leading blank takes
    the collective and not its reader."""
    class Profile:
        planes = [_Plane(f"/device:TPU:{i}", [_Line("XLA Ops", [
            _Event("%fusion.1 = f32[8]{0} fusion()", 0.0, (i + 1) * 100.0),
            _Event(ALLREDUCE.format(i=1), 1000.0, (i + 1) * 10.0),
            _Event(USER, 2000.0, 5.0)]),
            _Line("XLA Modules", [_Event("jit_step", 0.0, 3000.0)])])
            for i in range(4)] + [_Plane("/host:CPU", [])]

    kernels = harness.load_json("workloads", DP4 + ".json")["trace"]["kernels"]
    r = trace_reduce.reduce_profile(Profile(), {}, kernels, window_s=4e-6)
    assert r["device_planes"] == [f"/device:TPU:{i}" for i in range(4)]
    assert r["busy_s"] == pytest.approx((250.0 + 25.0 + 5.0) * 1e-9)
    k = r["kernels"]["allreduce"]
    assert k["seconds"] == pytest.approx(25.0e-9) and k["launches"] == 1
    assert "corr_lookup_bwd" not in r["kernels"]
    share = _allreduce_share({"trace": r})
    assert share == pytest.approx(100.0 * 25.0 / 280.0)


def test_the_replay_spread_over_four_devices_is_the_replay_in_turn():
    """Two steps on two batches of four 64x96 samples, three iterations,
    with the bf16 unit's hook: ``spread_over`` four (virtual) devices
    against the samples taken in turn.  Only the order of a sum of four
    float32 terms differs."""
    import jax
    import numpy as np

    from benchmark import control, reference_train, scenes_tree, weights

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (XLA_FLAGS=--xla_force_host_"
                    "platform_device_count=4)")
    cell = harness.load_cell(DP4)
    model, hw = cell["config"]["model"], (64, 96)
    recipe = dict(cell["config"]["train"], train_iters=3)
    rng = np.random.default_rng(5)
    batches = []
    for _ in range(2):
        triples = [scenes_tree.make_pair(rng, hw) for _ in range(4)]
        batches.append({"image1": np.stack([t[0] for t in triples]),
                        "image2": np.stack([t[1] for t in triples]),
                        "flow": -0.3 * np.stack([t[2] for t in triples]),
                        "valid": np.ones((4,) + hw, np.float32)})
    start = weights.make_weights(model, 7)
    lower = reference_train.straight_through(control.LOWER["bf16"])
    in_turn = reference_train.replay_pair(model, recipe, start, batches,
                                          lower)
    spread = reference_train.replay_pair(model, recipe, start, batches,
                                         lower, jax.devices()[:4])
    for (a, mu_a, _, steps_a), (b, mu_b, _, steps_b) in zip(in_turn, spread):
        for sa, sb in zip(steps_a, steps_b):
            assert sb["loss"] == pytest.approx(sa["loss"], rel=1e-5)
            assert sb["grad_norm"] == pytest.approx(sa["grad_norm"],
                                                    rel=1e-4)
        norm = np.sqrt(sum(float(np.sum(np.square(v)))
                           for v in mu_a.values()))
        gap = np.sqrt(sum(float(np.sum(np.square(
            np.asarray(mu_a[k]) - np.asarray(mu_b[k])))) for k in mu_a))
        assert gap <= 1e-4 * norm
        assert set(a) == set(b)
    with pytest.raises(ValueError, match="calls of 4"):
        reference_train.batch_grad(lambda *a: None, start, {
            k: v[:3] for k, v in batches[0].items()}, at_once=4)

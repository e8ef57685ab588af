"""Adaptive GRU early exit: the convergence-gated while-loop path.

The contracts pinned here (ISSUE round 12):

* parity pin — with ``exit_threshold_px <= 0`` the model runs today's
  fixed-depth scan program bitwise-unchanged (and keeps the 2-tuple
  return); with a threshold > 0 but ``min_iters == max_iters`` the
  while-loop path reproduces the scan output bitwise (the companion of
  test_costs' ``unroll_gru`` parity pin);
* loop semantics — the gate exits at the first iteration >= min_iters
  whose worst-batch-member mean |Δdisparity| drops below the threshold,
  and a batch pairing a converged-early image with a hard image rides to
  the hard image's solo depth (max-over-batch) with per-image results
  inside the engine's ladder tolerance;
* the serving tiers — per-tier executables, no cross-tier batching, the
  quality tier bitwise-equal to solo inference (the PR-6 contract), and
  the iters-used/saved telemetry.
"""

import dataclasses
import io
import json
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

TINY = dict(hidden_dims=(32, 32, 32), fnet_dim=64, corr_backend="reg")
ITERS = 4
HW = (48, 64)


@pytest.fixture(scope="module")
def tiny_model():
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(**TINY)
    model = RAFTStereo(cfg)
    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    return cfg, variables


def _pair(seed=3, textured=True):
    if not textured:   # low-texture: no correlation signal, updates stall
        left = np.full(HW + (3,), 127, np.uint8)
        return left, left.copy()
    rng = np.random.default_rng(seed)
    left = rng.integers(0, 255, HW + (3,), dtype=np.uint8)
    return left, np.roll(left, -3, axis=1)


def _as_batch(*imgs):
    return jnp.asarray(np.stack(imgs).astype(np.float32))


def _ee_model(cfg, **knobs):
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    return RAFTStereo(dataclasses.replace(cfg, **knobs))


def _delta_curve(model, variables, i1, i2, iters):
    """mean |Δdisparity| per iteration per image, reconstructed from
    fixed-depth scan runs — exactly the quantity the while-loop predicate
    reduces (disp_0 is the zero init)."""
    disps = [np.zeros_like(np.asarray(
        model.apply(variables, i1, i2, iters=1, test_mode=True)[0]))]
    for k in range(1, iters + 1):
        d, _ = model.apply(variables, i1, i2, iters=k, test_mode=True)
        disps.append(np.asarray(d))
    return [np.abs(disps[k] - disps[k - 1]).mean(axis=(1, 2))
            for k in range(1, iters + 1)]   # [k-1] -> per-image means


def _predicted_exit(deltas, threshold, min_iters, limit):
    """First iteration count the while-loop predicate admits an exit at:
    the loop checks the LAST transition's worst-member delta."""
    for k in range(min_iters, limit + 1):
        if max(deltas[k - 1]) < threshold:
            return k
    return limit


# ------------------------------------------------------------ config knobs
def test_config_validation():
    from raft_stereo_tpu.config import RaftStereoConfig

    with pytest.raises(ValueError, match="exit_min_iters"):
        RaftStereoConfig(exit_min_iters=0)
    with pytest.raises(ValueError, match="exit_max_iters"):
        RaftStereoConfig(exit_min_iters=4, exit_max_iters=2)
    with pytest.raises(ValueError, match="rows_gru"):
        RaftStereoConfig(exit_threshold_px=0.1, rows_shards=2,
                         rows_gru=True)


def test_parse_tier_presets_and_inline_specs():
    from raft_stereo_tpu.config import REQUEST_TIERS, parse_tier

    assert parse_tier("quality").exit_threshold_px <= 0
    assert parse_tier("interactive") is REQUEST_TIERS["interactive"]
    t = parse_tier("fast:0.5:3")
    assert (t.name, t.exit_threshold_px, t.min_iters) == ("fast", 0.5, 3)
    assert parse_tier("fast:0.5").min_iters == 1
    for bad in ("nope", "fast:abc", ":0.5", "a:1:2:3"):
        with pytest.raises(ValueError):
            parse_tier(bad)


def test_tier_apply_swaps_knobs_only(tiny_model):
    from raft_stereo_tpu.config import parse_tier

    cfg, _ = tiny_model
    t_cfg = parse_tier("interactive").apply(cfg)
    assert t_cfg.exit_threshold_px == 0.05 and t_cfg.exit_min_iters == 2
    assert dataclasses.replace(t_cfg, exit_threshold_px=0.0,
                               exit_min_iters=1) == cfg


# ------------------------------------------------------- model-level parity
def test_threshold_disabled_is_todays_scan_program(tiny_model):
    """exit_threshold_px <= 0 keeps the 2-tuple return and the exact scan
    output — the threshold-disabled parity pin."""
    cfg, variables = tiny_model
    base = _ee_model(cfg)
    off = _ee_model(cfg, exit_threshold_px=0.0, exit_min_iters=3)
    i1, i2 = map(_as_batch, _pair())
    out_base = base.apply(variables, i1, i2, iters=ITERS, test_mode=True)
    out_off = off.apply(variables, i1, i2, iters=ITERS, test_mode=True)
    assert len(out_base) == len(out_off) == 2
    np.testing.assert_array_equal(np.asarray(out_base[1]),
                                  np.asarray(out_off[1]))


def test_min_eq_max_reproduces_scan_bitwise(tiny_model):
    """Satellite pin (alongside test_costs' unroll_gru parity): the
    while-loop path at a pinned trip count is bitwise-equal to the
    fixed-iters scan."""
    cfg, variables = tiny_model
    base = _ee_model(cfg)
    ee = _ee_model(cfg, exit_threshold_px=0.01, exit_min_iters=ITERS,
                   exit_max_iters=ITERS)
    i1, i2 = map(_as_batch, _pair())
    d_scan, f_scan = base.apply(variables, i1, i2, iters=ITERS,
                                test_mode=True)
    d_ee, f_ee, used = ee.apply(variables, i1, i2, iters=ITERS,
                                test_mode=True)
    assert int(used) == ITERS
    np.testing.assert_array_equal(np.asarray(d_scan), np.asarray(d_ee))
    np.testing.assert_array_equal(np.asarray(f_scan), np.asarray(f_ee))


def test_exit_at_floor_matches_shallow_scan_bitwise(tiny_model):
    """A threshold above every update exits at the min_iters floor and the
    result equals the scan truncated there — intermediate disparities are
    valid outputs (the paper's framing), not a different computation."""
    cfg, variables = tiny_model
    ee = _ee_model(cfg, exit_threshold_px=1e9, exit_min_iters=2)
    i1, i2 = map(_as_batch, _pair())
    d_ee, f_ee, used = ee.apply(variables, i1, i2, iters=ITERS,
                                test_mode=True)
    assert int(used) == 2
    d2, f2 = _ee_model(cfg).apply(variables, i1, i2, iters=2,
                                  test_mode=True)
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(f_ee))


def test_exit_max_iters_caps_below_caller_iters(tiny_model):
    cfg, variables = tiny_model
    ee = _ee_model(cfg, exit_threshold_px=1e-9, exit_min_iters=1,
                   exit_max_iters=3)
    i1, i2 = map(_as_batch, _pair())
    *_, used = ee.apply(variables, i1, i2, iters=ITERS, test_mode=True)
    assert int(used) <= 3


# --------------------------------------------------- convergence semantics
def test_batch_rides_to_worst_member_depth(tiny_model):
    """Satellite: the max-over-batch rule.  A threshold separating the
    easy (low-texture) and hard (textured) images' measured delta curves
    must (a) exit each solo run at its predicted iteration, (b) run the
    mixed batch to the DEEPER member's solo depth, and (c) keep each batch
    member's result within the engine's batch-N ladder tolerance of the
    fixed scan truncated at the batch's depth.

    The program as it ships: a batch of two runs cnet's 64-channel stage
    two images a lane row, a solo pair (one image) runs it plain.  So
    (c)'s fixed scan runs the member in a batch of two of itself, at its
    own place: both sides run the same stage, and (c) holds that the
    pairing carries nothing from one member into the other.  And (d) each
    member stays within 1e-2 of its solo run."""
    _batch_rides_to_worst_member_depth(tiny_model, packed=True)


def test_batch_rides_to_worst_member_depth_plain_stage(tiny_model,
                                                       monkeypatch):
    """The same rule with the trunk's stage plain in every program, where
    (c) holds against the solo run itself."""
    from raft_stereo_tpu.models import extractor

    monkeypatch.setattr(extractor, "LANE_BATCH_IMAGES", 0)
    _batch_rides_to_worst_member_depth(tiny_model, packed=False)


def _batch_rides_to_worst_member_depth(tiny_model, packed):
    cfg, variables = tiny_model
    base = _ee_model(cfg)
    easy_l, easy_r = _pair(textured=False)
    hard_l, hard_r = _pair(seed=3)

    i1 = _as_batch(easy_l, hard_l)
    i2 = _as_batch(easy_r, hard_r)
    deltas = _delta_curve(base, variables, i1, i2, ITERS)  # per-image
    min_iters = 2
    # The gate sits between the two images' deltas at the FIRST checked
    # transition, so one member exits at the floor and the other rides on.
    # (The seed asserted max(easy[1:]) < min(hard[1:]) — an ordering of
    # whole curves that seeded random weights do not owe anyone: under
    # the installed XLA both curves RISE, 2.41→2.89 vs 2.80→3.33, and
    # overlap.  The property under test is the batch rule, not that.)
    first = deltas[min_iters - 1]
    assert first[0] != first[1], deltas
    threshold = float(first[0] + first[1]) / 2.0

    ee = _ee_model(cfg, exit_threshold_px=threshold,
                   exit_min_iters=min_iters)
    k_easy = _predicted_exit([[d[0]] for d in deltas], threshold,
                             min_iters, ITERS)
    k_hard = _predicted_exit([[d[1]] for d in deltas], threshold,
                             min_iters, ITERS)
    assert k_easy != k_hard, (k_easy, k_hard, deltas)
    k_worst = max(k_easy, k_hard)

    *_, used_easy = ee.apply(variables, _as_batch(easy_l),
                             _as_batch(easy_r), iters=ITERS,
                             test_mode=True)
    *_, used_hard = ee.apply(variables, _as_batch(hard_l),
                             _as_batch(hard_r), iters=ITERS,
                             test_mode=True)
    assert int(used_easy) == k_easy
    assert int(used_hard) == k_hard

    _, flows, used_batch = ee.apply(variables, i1, i2, iters=ITERS,
                                    test_mode=True)
    assert int(used_batch) == k_worst, \
        "the batch must ride to the worst member's solo depth"
    # Per-image parity at the batch's depth (the ladder tolerance the
    # engine documents for batch-N reassociation).
    flows = np.asarray(flows)
    for i, (l, r) in enumerate(((easy_l, easy_r), (hard_l, hard_r))):
        solo = np.asarray(base.apply(variables, _as_batch(l), _as_batch(r),
                                     iters=k_worst, test_mode=True)[1])[0]
        want = solo
        if packed:
            want = np.asarray(base.apply(
                variables, _as_batch(l, l), _as_batch(r, r), iters=k_worst,
                test_mode=True)[1])[i]
            # (d) The packed and the plain stage differ by float32
            # accumulation order, which the untrained loop amplifies: the
            # members read 7.1e-3 (easy) and 2.5e-3 (hard) px from their
            # solo runs, where half an ulp of random sign on each of
            # cnet's trunk weights moves the plain solo run 3.0e-3 to
            # 1.9e-2 (median 9.2e-3) and 1.7e-3 to 4.3e-3 (median 3.3e-3)
            # px (twelve draws; jaxlib 0.9.0).
            np.testing.assert_allclose(flows[i], solo, atol=1e-2, rtol=1e-4)
        # rtol covers the untrained fixture's large flow magnitudes —
        # reassociation drift scales with |flow| (the engine's 5e-4
        # ladder tolerance is stated for benchmark-regime disparities).
        # atol: a batch-2 and a batch-1 program reassociate differently
        # and the untrained GRU amplifies that ~5x per iteration; at the
        # 4-iteration cap jaxlib 0.9.0 measures 7.5e-4 on |flow| ~ 15 px.
        np.testing.assert_allclose(flows[i], want, atol=2e-3, rtol=1e-4)


def test_runner_tracks_iters_used_and_batch_rule(tiny_model):
    from raft_stereo_tpu.eval.runner import InferenceRunner

    cfg, variables = tiny_model
    easy = _pair(textured=False)
    hard = _pair(seed=3)
    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             exit_threshold_px=1e9, exit_min_iters=2)
    flow, _ = runner(*easy)
    assert runner.last_iters_used == 2
    runner(*hard)
    assert runner.iters_used_mean() == 2.0
    runner.reset_iters_used()
    assert runner.iters_used_mean() is None
    flows, _ = runner.run_batch([easy[0], hard[0]], [easy[1], hard[1]])
    assert flows.shape == (2,) + HW and runner.last_iters_used == 2

    fixed = InferenceRunner(cfg, variables, iters=ITERS)
    fixed(*easy)
    assert fixed.last_iters_used is None and fixed.iters_used_mean() is None


# ------------------------------------------------------------ serving tiers
def test_engine_tiers_parity_and_telemetry(tiny_model):
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    left, right = _pair(seed=5)
    solo_full = InferenceRunner(cfg, variables, iters=ITERS)
    solo_floor = InferenceRunner(cfg, variables, iters=2)
    with StereoService(cfg, variables, ServeConfig(
            max_batch=2, iters=ITERS, cost_telemetry=True,
            tiers=("interactive:1e9:2", "quality"))) as svc:
        assert svc.default_tier == "quality"
        # quality == the fixed-depth program == bitwise solo parity (the
        # PR-6 contract survives tiers)
        r_q = svc.infer(left, right, tier="quality", timeout=120)
        assert r_q.tier == "quality" and r_q.iters_used == ITERS
        assert np.array_equal(r_q.flow, solo_full(left, right)[0])
        # default requests run the default tier
        assert svc.infer(left, right, timeout=120).tier == "quality"
        # interactive exits at its floor == the 2-iter fixed program
        r_i = svc.infer(left, right, tier="interactive", timeout=120)
        assert r_i.tier == "interactive" and r_i.iters_used == 2
        assert np.array_equal(r_i.flow, solo_floor(left, right)[0])
        # telemetry: per-tier trip-count histogram + saved counter
        hist, saved = svc.metrics.iters_used_stats("interactive")
        assert hist.count == 1 and saved.value == ITERS - 2
        q_hist, q_saved = svc.metrics.iters_used_stats("quality")
        assert q_hist.count == 2 and q_saved.value == 0
        text = svc.metrics.render_text()
        assert 'infer_gru_iters_used_count{tier="interactive"} 1' in text
        assert 'serve_gru_iters_saved_total{tier="interactive"} 2' in text
        # cost registry: the interactive family is a distinct executable,
        # quality shares the base (no tier suffix — one program)
        keys = {rec.key for rec in svc.costs.records()}
        assert "serving.forward(64x64,b1,tier=interactive)" in keys
        assert "serving.forward(64x64,b1)" in keys
        with pytest.raises(ValueError, match="unknown tier"):
            svc.infer(left, right, tier="nope", timeout=10)


def test_engine_never_batches_across_tiers(tiny_model):
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    left, right = _pair(seed=6)
    with StereoService(cfg, variables, ServeConfig(
            max_batch=8, iters=2, cost_telemetry=False,
            tiers=("interactive:1e9:1", "quality"))) as svc:
        svc.prewarm(HW)    # both executable families, all ladder rungs
        d0 = svc.metrics.batches.value
        svc.queue.pause()
        futs = [svc.submit(left, right, tier=t)
                for t in ("interactive", "quality",
                          "interactive", "quality")]
        svc.queue.resume()
        results = [f.result(timeout=120) for f in futs]
        # 4 requests, 2 per tier: tiers never share a dispatch, so the
        # scheduler issues exactly one batch-2 dispatch PER TIER
        assert svc.metrics.batches.value - d0 == 2
        assert [r.batch_size for r in results] == [2, 2, 2, 2]
        assert {r.tier for r in results} == {"interactive", "quality"}
        assert all(r.iters_used == (1 if r.tier == "interactive" else 2)
                   for r in results)


def test_engine_prewarm_covers_tier_families(tiny_model):
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    with StereoService(cfg, variables, ServeConfig(
            max_batch=2, batch_sizes=(1, 2), iters=2, cost_telemetry=True,
            tiers=("interactive:1e9:1", "balanced:1e8:1",
                   "quality"))) as svc:
        svc.prewarm(HW)
        keys = {rec.key for rec in svc.costs.records()}
        for n in (1, 2):
            assert f"serving.forward(64x64,b{n})" in keys          # base
            assert f"serving.forward(64x64,b{n},tier=interactive)" in keys
            assert f"serving.forward(64x64,b{n},tier=balanced)" in keys
        # quality shares the base family — no quality-suffixed compiles
        assert not any("tier=quality" in k for k in keys)


def test_serve_config_tier_validation():
    from raft_stereo_tpu.serving import ServeConfig

    with pytest.raises(ValueError, match="duplicate"):
        ServeConfig(tiers=("interactive", "interactive:0.5:2"))
    with pytest.raises(ValueError, match="default_tier"):
        ServeConfig(tiers=("quality",), default_tier="interactive")
    with pytest.raises(ValueError, match="unknown tier"):
        ServeConfig(tiers=("not-a-preset",))


def test_http_tier_selection_and_iters_header(tiny_model):
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    left, right = _pair(seed=7)
    svc = StereoService(cfg, variables, ServeConfig(
        max_batch=2, iters=ITERS, tiers=("interactive:1e9:2", "quality")))
    server = StereoHTTPServer(svc, port=0).start()
    try:
        buf = io.BytesIO()
        np.savez(buf, left=left, right=right)

        def post(url):
            req = urllib.request.Request(url, data=buf.getvalue(),
                                         method="POST")
            req.add_header("Content-Type", "application/x-npz")
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    return resp.status, dict(resp.headers), resp.read()
            except urllib.error.HTTPError as e:
                return e.code, dict(e.headers), e.read()

        status, headers, _ = post(
            server.url + "/v1/disparity?tier=interactive")
        assert status == 200
        assert headers["X-Tier"] == "interactive"
        assert headers["X-Iters-Used"] == "2"
        status, headers, _ = post(server.url + "/v1/disparity")
        assert status == 200 and headers["X-Tier"] == "quality"
        assert headers["X-Iters-Used"] == str(ITERS)
        status, _, body = post(server.url + "/v1/disparity?tier=bogus")
        assert status == 400 and b"unknown tier" in body
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
        assert 'infer_gru_iters_used_count{tier="interactive"} 1' in text
    finally:
        server.shutdown()
        svc.close()

"""Int8 quantized inference tier tests (tier-1, CPU): the round-15
turbo path.

Headline pins (the ISSUE acceptance properties):

* ``quant="off"`` is BITWISE the pre-quant program — no int8 ops trace
  into either the fixed-depth scan or the early-exit while program, and
  the quality tier's outputs equal the raw config's outputs exactly.
* Calibration is deterministic: same pairs -> byte-identical scale
  record; the scale file round-trips and version/mode-checks.
* Quantized and base executables can never collide in the persistent
  disk cache (distinct content keys) or the compile-cost registry
  (distinct key labels with the ``quant=int8`` tail).
* The int8 correlation pyramid's fused-kernel path (interpret mode)
  matches the XLA dequant fallback — the backend-independence contract
  of the kernel family.
* The per-session context cache reuses/invalidates correctly and its
  reuse program is numerically identical to the plain warm program.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from raft_stereo_tpu.config import (REQUEST_TIERS, RaftStereoConfig,
                                    parse_tier)
from raft_stereo_tpu.quant import (calibrate, corr_scales,
                                   dequantize_variables, load_scales,
                                   quantize_array, quantize_variables,
                                   quantized_param_bytes, save_scales,
                                   tree_is_quantized)

TINY = dict(hidden_dims=(32, 32, 32), fnet_dim=64, corr_backend="reg")


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(**TINY)
    model = RAFTStereo(cfg)
    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    return cfg, variables


def _pair(hw=(32, 48), seed=3):
    rng = np.random.default_rng(seed)
    left = rng.integers(0, 255, hw + (3,), dtype=np.uint8)
    return left, np.roll(left, -3, axis=1)


# ------------------------------------------------------------- core quant
def test_quantize_array_per_channel_roundtrip():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(3, 3, 8, 16)).astype(np.float32) * \
        np.linspace(0.1, 10.0, 16, dtype=np.float32)  # per-channel ranges
    q, s = quantize_array(w)
    assert q.dtype == np.int8 and s.shape == (1, 1, 1, 16)
    # per-channel scales: each channel's error bounded by ITS half-step,
    # the whole point over a per-tensor scale (Wu et al. 2020 §4)
    err = np.abs(q.astype(np.float32) * s - w)
    assert np.all(err <= 0.5 * s + 1e-7)
    # all-zero channels reproduce exactly (scale 1, q 0)
    w[..., 3] = 0.0
    q, s = quantize_array(w)
    assert np.all(q[..., 3] == 0) and s[0, 0, 0, 3] == 1.0


def test_quantize_variables_scope_and_dequant(tiny_model):
    _, variables = tiny_model
    qvars = quantize_variables(variables)
    assert tree_is_quantized(qvars)
    # encoder kernels packed; the update block stays full precision
    p = qvars["params"]
    assert "q8" in p["fnet"]["trunk"]["conv1"]["kernel"]
    assert "q8" in p["cnet"]["trunk"]["conv1"]["kernel"]
    assert "q8" in p["context_zqr_conv0"]["kernel"]
    flat_ub = p["update_block"]
    assert not tree_is_quantized({"params": flat_ub})
    # biases/norms untouched
    assert np.asarray(
        p["fnet"]["trunk"]["conv1"]["bias"]).dtype == np.float32
    # structural inverse + bounded error
    dq = dequantize_variables(qvars)
    orig = np.asarray(variables["params"]["fnet"]["trunk"]["conv1"]
                      ["kernel"])
    back = np.asarray(dq["params"]["fnet"]["trunk"]["conv1"]["kernel"])
    assert back.shape == orig.shape
    assert np.max(np.abs(back - orig)) <= np.max(np.abs(orig)) / 127 + 1e-6
    acct = quantized_param_bytes(qvars)
    assert acct["int8"] > 0 and acct["scales"] > 0


def test_quant_config_validation():
    with pytest.raises(ValueError, match="quant="):
        RaftStereoConfig(**TINY, quant="fp8")
    with pytest.raises(ValueError, match="rows_shards"):
        RaftStereoConfig(**TINY, quant="int8", rows_shards=2)
    with pytest.raises(ValueError, match="quant_corr_scales"):
        RaftStereoConfig(**TINY, quant="int8", quant_corr_scales=(1.0,))
    cfg = RaftStereoConfig(**TINY, quant="int8",
                           quant_corr_scales=(.1, .2, .3, .4))
    assert cfg.from_json(cfg.to_json()) == cfg


def test_turbo_tier_preset_and_ladder():
    from raft_stereo_tpu.serving.resilience import cost_ladder

    turbo = REQUEST_TIERS["turbo"]
    # Turbo v2 (r22): the preset rides the int8 COMPUTE path; the r15
    # weights-only mode stays reachable through inline specs.
    assert turbo.quant == "int8_mxu" and turbo.exit_threshold_px > 0
    inline = parse_tier("fast8:0.1:2:int8")
    assert inline.quant == "int8" and inline.min_iters == 2
    inline_mxu = parse_tier("fast8m:0.1:2:int8_mxu")
    assert inline_mxu.quant == "int8_mxu" and inline_mxu.min_iters == 2
    with pytest.raises(ValueError, match="quant"):
        parse_tier("bad:0.1:2:fp8")
    tiers = [parse_tier(t) for t in
             ("interactive", "balanced", "quality", "turbo")]
    ladder = cost_ladder(tiers)
    assert ladder[0] == "turbo" and ladder[-1] == "quality"


# ------------------------------------------------------- quant-off bitwise
def _jaxpr_has_int8(fn, *avals):
    import jax

    jaxpr = jax.make_jaxpr(fn)(*avals)
    return "i8[" in str(jaxpr)


def test_quant_off_traces_no_int8_scan_and_early_exit(tiny_model):
    """The bitwise-off pin at the jaxpr level: with quant='off' neither
    the fixed-depth scan program nor the early-exit while program
    contains a single int8 op — the traced computation IS the pre-quant
    one.  With quant='int8' both carry int8 (the positive control)."""
    import jax.numpy as jnp

    from raft_stereo_tpu.eval.runner import make_forward
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg, variables = tiny_model
    img = jnp.zeros((1, 32, 64, 3), jnp.uint8)
    for exit_px in (0.0, 0.05):
        base = dataclasses.replace(cfg, exit_threshold_px=exit_px)
        fwd = make_forward(RAFTStereo(base), 2, donate_images=False)
        assert not _jaxpr_has_int8(fwd, variables, img, img)
        qcfg = dataclasses.replace(base, quant="int8")
        qfwd = make_forward(RAFTStereo(qcfg), 2, donate_images=False)
        qvars = quantize_variables(variables)
        assert _jaxpr_has_int8(qfwd, qvars, img, img)


def test_quality_tier_apply_is_identity_program(tiny_model):
    """REQUEST_TIERS['quality'].apply (quant='off') on the base config
    yields the base config exactly — the engine's shared-executable
    normalization depends on this equality."""
    cfg, _ = tiny_model
    assert REQUEST_TIERS["quality"].apply(cfg) == dataclasses.replace(
        cfg, exit_threshold_px=0.0, exit_min_iters=1, exit_max_iters=None)


# ------------------------------------------------------------- calibration
def test_calibration_deterministic_and_roundtrip(tiny_model, tmp_path):
    cfg, variables = tiny_model
    left, right = _pair()
    pairs = [(left, right), _pair(seed=7)]
    rec_a = calibrate(cfg, variables, pairs, percentile=99.5)
    rec_b = calibrate(cfg, variables, pairs, percentile=99.5)
    assert json.dumps(rec_a, sort_keys=True) == \
        json.dumps(rec_b, sort_keys=True)
    assert len(rec_a["corr_levels"]) == cfg.corr_levels
    assert rec_a["n_pairs"] == 2 and rec_a["activations"]
    # different data -> different scales (the record measures the input)
    rec_c = calibrate(cfg, variables, [_pair(seed=99)], percentile=99.5)
    assert rec_c["corr_levels"] != rec_a["corr_levels"]
    # file round trip + guards
    path = os.path.join(tmp_path, "scales.json")
    save_scales(path, rec_a)
    loaded = load_scales(path)
    assert loaded["corr_levels"] == rec_a["corr_levels"]
    scales = corr_scales(loaded)
    assert len(scales) == cfg.corr_levels and all(s > 0 for s in scales)
    bad = dict(rec_a, version=999)
    save_scales(path, bad)
    with pytest.raises(ValueError, match="version"):
        load_scales(path)


# ----------------------------------------------------------- int8 kernels
def test_int8_pyramid_fused_matches_xla_fallback():
    """Interpret-mode kernel parity: the fused int8 lookup (in-register
    dequant, scale applied after) equals the XLA fallback (dequant then
    sample) up to float associativity — same int8 grid either way."""
    import jax.numpy as jnp

    import raft_stereo_tpu.kernels.corr_lookup as cl
    from raft_stereo_tpu.models.corr import make_corr_fn

    rng = np.random.default_rng(1)
    b, h, w, d = 1, 8, 128, 32
    f1 = jnp.asarray(rng.normal(size=(b, h, w, d)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(b, h, w, d)).astype(np.float32))
    coords = jnp.asarray(
        rng.uniform(0, w, size=(b, h, w)).astype(np.float32))
    base = RaftStereoConfig(**TINY)
    old = cl._interpret_override
    try:
        for backend in ("reg_fused", "alt"):
            qcfg = dataclasses.replace(base, corr_backend=backend,
                                       quant="int8")
            cl._interpret_override = False     # XLA fallback path
            ref = make_corr_fn(qcfg, f1, f2)(coords)
            cl._interpret_override = True      # fused interpret kernels
            fused = make_corr_fn(qcfg, f1, f2)(coords)
            np.testing.assert_allclose(np.asarray(fused),
                                       np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
    finally:
        cl._interpret_override = old


def test_int8_pyramid_calibrated_scales_clip():
    """Calibrated (percentile-clipped) scales saturate outliers at
    127*scale instead of blowing up the grid — the clip semantics the
    PTQ literature prescribes."""
    import jax.numpy as jnp

    from raft_stereo_tpu.models.corr import quantize_pyramid

    cfg = RaftStereoConfig(**TINY, quant="int8",
                           quant_corr_scales=(0.01,) * 4)
    vol = jnp.asarray(np.array([[[[0.5, -3.0, 0.002]]]], np.float32))
    qs, scales = quantize_pyramid([vol] * 4, cfg)
    q0 = np.asarray(qs[0])
    assert q0[0, 0, 0, 0] == 50          # 0.5 / 0.01
    assert q0[0, 0, 0, 1] == -127        # clipped
    assert float(scales[0]) == pytest.approx(0.01)


# --------------------------------------------------- runner / engine tier
def test_runner_int8_close_to_fp32(tiny_model):
    from raft_stereo_tpu.eval.runner import InferenceRunner

    cfg, variables = tiny_model
    left, right = _pair()
    r_fp = InferenceRunner(cfg, variables, iters=2)
    r_q = InferenceRunner(cfg, variables, iters=2, quant="int8")
    assert tree_is_quantized(r_q.variables)
    f_fp, _ = r_fp(left, right)
    f_q, _ = r_q(left, right)
    assert np.isfinite(f_q).all() and f_q.shape == f_fp.shape
    # loose: random-init nets amplify perturbations; the trained-weights
    # accuracy gate lives in tools/quant_drift.py
    denom = max(np.abs(f_fp).mean(), 1.0)
    assert np.abs(f_q - f_fp).mean() / denom < 0.5


def test_persist_keys_never_collide(tiny_model):
    """The acceptance pin: quantized and base executables get distinct
    persistent-cache AND compile-cost keys at every (bucket, batch) —
    exactly like the r14 warm/state family split."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    svc = StereoService(cfg, variables, ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=2,
        tiers=("turbo", "interactive", "quality"),
        default_tier="quality"))
    try:
        keys = {}
        cost_keys = {}
        for tier in (None, "turbo", "interactive"):
            ct = svc._cache_tier(tier)
            keys[tier] = svc._disk_key((32, 64), 1, 0, ct)
            cost_keys[tier] = svc._cost_key((32, 64), 1, tier)
        assert len(set(keys.values())) == 3, keys
        assert "quant=int8" in cost_keys["turbo"]
        assert "quant" not in cost_keys[None]
        assert "quant" not in cost_keys["interactive"]
        # family split keys stay distinct too (regression: r14 pin)
        k_base = svc._disk_key((32, 64), 1, 0, "turbo", family=None)
        k_state = svc._disk_key((32, 64), 1, 0, "turbo", family="state")
        assert k_base != k_state
    finally:
        svc.close()


def test_engine_turbo_tier_end_to_end(tiny_model):
    """One engine, quality + turbo: turbo runs the int8 program (close
    but not equal to quality), quality stays bitwise the solo fp32
    runner, and the two tiers compile distinct cost records."""
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    left, right = _pair()
    solo = InferenceRunner(cfg, variables, iters=2,
                           donate_images=False)
    solo_flow, _ = solo(left, right)
    svc = StereoService(cfg, variables, ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=2, cost_telemetry=True,
        tiers=("turbo", "quality"), default_tier="quality"))
    try:
        r_q = svc.infer(left, right, tier="quality", timeout=300)
        r_t = svc.infer(left, right, tier="turbo", timeout=300)
        assert np.array_equal(r_q.flow, solo_flow), \
            "quality tier must stay bitwise the solo fp32 program"
        assert r_t.tier == "turbo"
        assert not np.array_equal(r_t.flow, r_q.flow)
        denom = max(np.abs(r_q.flow).mean(), 1.0)
        assert np.abs(r_t.flow - r_q.flow).mean() / denom < 0.5
        recs = {r.key for r in svc.costs.records()}
        assert any("quant=int8" in k for k in recs), recs
        assert any("quant" not in k for k in recs), recs
    finally:
        svc.close()


# ------------------------------------------------------ session ctx cache
def test_ctx_cache_config_validation(tiny_model):
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    with pytest.raises(ValueError, match="sessions"):
        ServeConfig(session_ctx_cache=True)
    cfg, variables = tiny_model
    shared = dataclasses.replace(cfg, shared_backbone=True,
                                 n_downsample=3, n_gru_layers=2)
    with pytest.raises(ValueError, match="shared_backbone"):
        StereoService(shared, variables, ServeConfig(
            sessions=True, session_ctx_cache=True))


def test_ctx_reuse_program_matches_plain_warm(tiny_model):
    """The warm_ctx program fed the bundle a cold state_ctx frame saved
    produces EXACTLY the plain warm program's output: skipping the
    context encoder is a pure compute-reuse, not an approximation."""
    import jax.numpy as jnp

    from raft_stereo_tpu.eval.runner import make_forward
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg, variables = tiny_model
    model = RAFTStereo(cfg)
    left, right = _pair()
    p1 = jnp.asarray(np.pad(left, ((0, 0), (0, 16), (0, 0)),
                            mode="edge")[None])
    p2 = jnp.asarray(np.pad(right, ((0, 0), (0, 16), (0, 0)),
                            mode="edge")[None])
    fwd_save = make_forward(model, 2, return_state=True, ctx="save",
                            donate_images=False)
    flow_up0, flow_low0, ctx = fwd_save(variables, p1, p2)
    # the ctx-saving cold program's flow equals the base program's
    fwd_base = make_forward(model, 2, donate_images=False)
    np.testing.assert_array_equal(np.asarray(flow_up0),
                                  np.asarray(fwd_base(variables, p1, p2)))
    fwd_warm = make_forward(model, 2, warm_start=True,
                            donate_images=False)
    fwd_reuse = make_forward(model, 2, warm_start=True, ctx="reuse",
                             donate_images=False)
    out_warm = fwd_warm(variables, p1, p2, flow_low0)
    out_reuse = fwd_reuse(variables, p1, p2, flow_low0, ctx)
    np.testing.assert_array_equal(np.asarray(out_reuse[0]),
                                  np.asarray(out_warm[0]))


def test_engine_session_ctx_cache_hits_and_invalidation(tiny_model):
    """Static-camera stream: frame 0 cold (bundle saved), later frames
    reuse it (X-Ctx-Cached semantics, counter, per-session stats); a
    frame past the static-scene gate drops the bundle; a scene cut
    recomputes it."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    left, right = _pair()
    bright = np.clip(left.astype(np.int32) + 30, 0, 255).astype(np.uint8)
    dark = (left * 0.2).astype(np.uint8)
    svc = StereoService(cfg, variables, ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=2,
        sessions=True, session_ttl_s=600.0,
        session_ctx_cache=True, ctx_cache_threshold=3.0,
        scene_cut_threshold=40.0))
    try:
        r0 = svc.infer_session("s", left, right, timeout=300)
        assert not r0.warm and not r0.ctx_cached and r0.ctx is not None
        r1 = svc.infer_session("s", left, right, timeout=300)
        assert r1.warm and r1.ctx_cached
        r2 = svc.infer_session("s", left, right, timeout=300)
        assert r2.warm and r2.ctx_cached
        assert svc.metrics.ctx_cache_hits.value == 2
        # moderate delta: warm WITHOUT ctx (> gate, < scene cut) and the
        # bundle is invalidated — the next small-delta frame cannot hit
        r3 = svc.infer_session("s", bright, right, timeout=300)
        assert r3.warm and not r3.ctx_cached and not r3.scene_cut
        r4 = svc.infer_session("s", bright, right, timeout=300)
        assert r4.warm and not r4.ctx_cached, \
            "stale bundle must not be reused after an over-gate frame"
        # hard scene cut: cold start, bundle recomputed -> next frame hits
        r5 = svc.infer_session("s", dark, right, timeout=300)
        assert r5.scene_cut and not r5.warm
        r6 = svc.infer_session("s", dark, right, timeout=300)
        assert r6.warm and r6.ctx_cached
        stats = svc.close_session("s")
        assert stats["ctx_cache_hits"] == 3
        assert svc.metrics.ctx_cache_hits.value == 3
    finally:
        svc.close()


def test_ctx_cache_http_header(tiny_model):
    """X-Ctx-Cached rides the stream response exactly when the frame
    reused the bundle."""
    import io
    import urllib.request

    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    left, right = _pair()
    svc = StereoService(cfg, variables, ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=2,
        sessions=True, session_ttl_s=600.0,
        session_ctx_cache=True, ctx_cache_threshold=3.0))
    server = StereoHTTPServer(svc, port=0).start()
    try:
        def post(sid):
            buf = io.BytesIO()
            np.savez(buf, left=left, right=right)
            req = urllib.request.Request(
                f"{server.url}/v1/stream/{sid}", data=buf.getvalue(),
                method="POST",
                headers={"Content-Type": "application/x-npz"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                return dict(resp.headers)
        h0 = post("cam")
        h1 = post("cam")
        assert "X-Ctx-Cached" not in h0 and h0["X-Warm"] == "0"
        assert h1.get("X-Ctx-Cached") == "1" and h1["X-Warm"] == "1"
    finally:
        server.shutdown()
        svc.close()


# ------------------------------------------------ quantized compute (r22)
def test_ascale_pack_is_quantized_leaf():
    """Pack detection accepts both key sets: {q8, qscale} (r15) and
    {q8, qscale, ascale} (r22 calibrated activation scales) — and
    rejects partial dicts, so a corrupt tree can never half-route."""
    from raft_stereo_tpu.quant import is_quantized_leaf

    q8 = np.zeros((3, 3, 4, 8), np.int8)
    qs = np.ones((1, 1, 1, 8), np.float32)
    assert is_quantized_leaf({"q8": q8, "qscale": qs})
    assert is_quantized_leaf({"q8": q8, "qscale": qs,
                              "ascale": np.float32(0.1)})
    assert not is_quantized_leaf({"q8": q8})
    assert not is_quantized_leaf({"q8": q8, "qscale": qs, "extra": 1})
    assert not is_quantized_leaf(np.zeros((3, 3, 4, 8), np.float32))


def test_quantconv_pack_matches_fp(tiny_model):
    """QuantConv routing is data-driven: the same module applied with
    the fp tree and with a {q8, qscale} pack tree agree within the
    int8 quantization budget, and the pack apply is finite."""
    import jax.numpy as jnp

    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg, variables = tiny_model
    model = RAFTStereo(cfg)
    im = jnp.asarray(_pair()[0][None].astype(np.float32))
    qvars = quantize_variables(variables)
    f_fp = np.asarray(model.apply(variables, im, im, iters=2,
                                  test_mode=True)[1])
    f_q = np.asarray(model.apply(qvars, im, im, iters=2,
                                 test_mode=True)[1])
    assert np.isfinite(f_q).all() and f_q.shape == f_fp.shape
    # loose on random init — the trained-weights gate is quant_drift's
    denom = max(np.abs(f_fp).mean(), 1.0)
    assert np.abs(f_q - f_fp).mean() / denom < 0.5


def test_int8_mxu_jaxpr_pin(tiny_model):
    """The r22 acceptance pin: quant='int8_mxu' traces >= 1 int8 x int8
    -> int32 conv with NO fp32 dequant feeding any matmul (quantized
    compute, not dequantize-then-fp32), in both the fixed-depth scan
    and the early-exit while program.  quant='off' keeps its zero-
    int8-matmul twin of the existing bitwise pin."""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.eval.runner import make_forward
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.quant import int8_matmul_report

    cfg, variables = tiny_model
    img = jnp.zeros((1, 32, 64, 3), jnp.uint8)
    qvars = quantize_variables(variables)
    for exit_px in (0.0, 0.05):
        base = dataclasses.replace(cfg, exit_threshold_px=exit_px)
        mxu = dataclasses.replace(base, quant="int8_mxu")
        fwd = make_forward(RAFTStereo(mxu), 2, donate_images=False)
        rep = int8_matmul_report(jax.make_jaxpr(fwd)(qvars, img, img))
        assert rep["int8_convs"] + rep["int8_dots"] >= 1, rep
        assert rep["dequant_fed_matmuls"] == 0, rep
        off = make_forward(RAFTStereo(base), 2, donate_images=False)
        rep_off = int8_matmul_report(
            jax.make_jaxpr(off)(variables, img, img))
        assert rep_off["int8_convs"] + rep_off["int8_dots"] == 0, rep_off


def test_conv_input_scales_mapping(tiny_model):
    """conv_input_scales maps the calibration record's sown ``qin``
    sites back to PARAM-TREE paths (the act_scales contract of
    quantize_variables), and the mapped scales ride the packs as
    ``ascale`` — absent exactly where calibration has no coverage."""
    from raft_stereo_tpu.quant import conv_input_scales

    cfg, variables = tiny_model
    rec = calibrate(cfg, variables, [_pair(), _pair(seed=7)])
    scales = conv_input_scales(rec)
    assert scales and all(s > 0 for s in scales.values())
    params = variables["params"]
    for path in scales:
        node = params
        for part in path.split("/"):
            assert part in node, f"unresolvable scale path {path!r}"
            node = node[part]
        assert "kernel" in node, path
    assert "fnet/trunk/conv1" in scales
    # context_zqr convs sit outside the calibration capture surface:
    # they take the dynamic in-graph fallback, never a stale ascale
    assert not any(p.startswith("context_zqr") for p in scales)
    qvars = quantize_variables(variables, act_scales=scales)
    p = qvars["params"]
    covered = p["fnet"]["trunk"]["conv1"]["kernel"]
    uncovered = p["context_zqr_conv0"]["kernel"]
    assert "ascale" in covered and float(covered["ascale"]) == \
        pytest.approx(scales["fnet/trunk/conv1"])
    assert "q8" in uncovered and "ascale" not in uncovered
    # pre-r22 records (no activations section) degrade to {}
    assert conv_input_scales({"activations": {}}) == {}


def test_fp8_corr_capability_gate():
    """fp8 q-entries are capability-gated: unavailable on plain CPU
    (corr_q_dtype transparently falls back to int8 so
    ``quant_corr_fp8=True`` is safe everywhere), available under the
    interpret override, and check_q_dtype rejects an fp8 pyramid
    whenever the gate says no."""
    import jax.numpy as jnp

    import raft_stereo_tpu.kernels.corr_lookup as cl
    from raft_stereo_tpu.models.corr import corr_q_dtype

    if cl.FP8_CORR_DTYPE is None:
        pytest.skip("this jax build has no float8_e4m3fn dtype")
    cfg = RaftStereoConfig(**TINY, quant="int8", quant_corr_fp8=True)
    old = cl._interpret_override
    try:
        cl._interpret_override = False
        assert not cl.fp8_corr_available()
        assert jnp.dtype(corr_q_dtype(cfg)) == jnp.dtype(jnp.int8)
        fp8_lvl = jnp.zeros((1, 4, 8, 8), cl.FP8_CORR_DTYPE)
        with pytest.raises(ValueError, match="fp8"):
            cl.check_q_dtype([fp8_lvl], None)
        cl._interpret_override = True
        assert cl.fp8_corr_available()
        assert jnp.dtype(corr_q_dtype(cfg)) == \
            jnp.dtype(cl.FP8_CORR_DTYPE)
        assert cl.check_q_dtype([fp8_lvl], None) == \
            jnp.dtype(cl.FP8_CORR_DTYPE)
    finally:
        cl._interpret_override = old
    # mixed-dtype pyramids are rejected regardless of capability
    with pytest.raises(ValueError, match="levels"):
        cl.check_q_dtype([jnp.zeros((1, 4, 8, 8), jnp.int8),
                          jnp.zeros((1, 4, 8, 4), jnp.float32)], jnp.int8)


def test_fp8_pyramid_lookup_parity_interpret():
    """Kernel-level fp8 parity in interpret mode: the q entry sampling
    an fp8 grid equals the fp fused kernel sampling the SAME grid
    upcast to fp32 — the kernel body is dtype-generic, the in-register
    upcast is the only difference."""
    import jax.numpy as jnp

    import raft_stereo_tpu.kernels.corr_lookup as cl
    from raft_stereo_tpu.quant.core import FP8_QMAX, quantize_fp8

    if cl.FP8_CORR_DTYPE is None:
        pytest.skip("this jax build has no float8_e4m3fn dtype")
    rng = np.random.default_rng(2)
    b, h, w1, radius = 1, 4, 32, 3
    # the kernel's layout: level i is (B, H, W2_i, W1)
    pyramid_f32 = [
        jnp.asarray(rng.normal(size=(b, h, w2, w1)).astype(np.float32))
        for w2 in (32, 16, 8)]
    coords = jnp.asarray(
        rng.uniform(0, w1, size=(b, h, w1)).astype(np.float32))
    old = cl._interpret_override
    try:
        cl._interpret_override = True
        pyramid_q = []
        for lvl in pyramid_f32:
            scale = float(np.abs(np.asarray(lvl)).max()) / FP8_QMAX
            pyramid_q.append(quantize_fp8(lvl, scale, cl.FP8_CORR_DTYPE))
        got = cl.lookup_pyramid_fused_q(pyramid_q, coords, radius,
                                        out_dtype=jnp.float32)
        ref = cl.lookup_pyramid_fused(
            [q.astype(jnp.float32) for q in pyramid_q], coords, radius)
        assert jnp.isfinite(got).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        cl._interpret_override = old

#!/usr/bin/env python
"""CI smoke: the quantized turbo tier end to end — calibrate, gate, serve.

The round-15 acceptance check, hermetic on CPU, grown in round 22 to
cover the quantized-compute-v2 path (the turbo tier now runs
``quant="int8_mxu"`` — int8 x int8 -> int32 extractor convs with fp32
rescale after accumulation, quant/matmul.py):

1. brief-train the tiny architecture (drift must be measured in a
   functioning network — the same reason every tool in the drift family
   trains first);
2. run the calibration pass (quant/calibrate.py) on in-distribution
   pairs and write the checkpoint-adjacent scale file; assert the pass
   is DETERMINISTIC (same pairs -> identical scales);
3. measure BOTH quantized modes' EPE drift vs fp32 on a warped-stereo
   scene — weights-only ``int8`` and compute-path ``int8_mxu`` (with
   the calibrated activation scales) — and assert the drift gate passes
   for each (|dEPE| within the CI budget — the briefly-trained CI net
   is noisier than a converged checkpoint, so the CI budget is looser
   than quant_drift's 0.05 px product gate);
4. assert the int8_mxu program actually takes the MXU path: its jaxpr
   traces >= 1 int8 x int8 -> int32 conv and ZERO matmuls fed by an
   int8 -> fp32 dequant (quant.int8_matmul_report — quantized compute,
   not dequant-then-fp32);
5. start the serving engine with the turbo tier configured (calibrated
   scales via ServeConfig.quant_scales_path) behind the real HTTP front
   door and serve one request at ``?tier=turbo`` (now int8_mxu):
   assert X-Tier: turbo, a sane disparity payload matching the solo
   int8_mxu runner's math, per-tier metrics in ``/metrics``
   (``infer_gru_iters_used{tier="turbo"}``), and the turbo executable's
   distinct mode-carrying compile-cost record in ``/debug/compiles``;
6. assert ``quant="off"`` bitwise parity: the engine's quality tier
   answer equals the solo fp32 runner's.

Writes QUANT_ci.json (set QUANT_CI_OUT; CI uploads it).  Exit 0 on
success, non-zero with a diagnostic on any failed assertion.

Run from the repo root:  JAX_PLATFORMS=cpu python scripts/quant_smoke.py
"""

from __future__ import annotations

import io
import json
import os
import sys
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

OUT = os.environ.get("QUANT_CI_OUT", os.path.join(_REPO, "QUANT_ci.json"))
STEPS = int(os.environ.get("QUANT_SMOKE_STEPS", "120"))
ITERS_CAP = 6
# CI drift budget: a 120-step 32x48 network is NOT the trained
# checkpoint the 0.05 px product gate (tools/quant_drift.py) applies
# to; the smoke asserts the tier is sane, not product-accurate.
CI_GATE_PX = 0.5


def main() -> int:
    from _hermetic import force_cpu

    force_cpu(1)
    import jax
    import numpy as np

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from early_exit_report import model_config, trained_variables
    from golden_data import disparity_field, textured_image, warp_right
    from quant_drift import calibration_pairs

    from raft_stereo_tpu import quant
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    hw = (32, 48)
    cfg = model_config()
    t0 = time.perf_counter()
    variables = trained_variables(cfg, STEPS, hw, 4)
    print(f"brief-trained {STEPS} steps in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    # --- calibration + determinism -------------------------------------
    pairs = calibration_pairs(hw, 3)
    rec_a = quant.calibrate(cfg, variables, pairs)
    rec_b = quant.calibrate(cfg, variables, pairs)
    blob_a = json.dumps(rec_a, sort_keys=True)
    assert blob_a == json.dumps(rec_b, sort_keys=True), \
        "calibration must be deterministic: same pairs -> same scales"
    scales_path = os.path.join("/tmp", "quant_smoke_scales.json")
    quant.save_scales(scales_path, rec_a)
    corr_scales = quant.corr_scales(rec_a)
    print(f"calibrated {len(rec_a['activations'])} activation sites, "
          f"corr scales {[round(s, 5) for s in corr_scales]}", flush=True)

    # --- drift gate on a held-out warped scene --------------------------
    rng = np.random.default_rng(5)
    left = textured_image(rng, *hw)
    disp = disparity_field(rng, *hw)
    right = warp_right(left, disp)
    left8 = left.astype(np.uint8)
    right8 = right.astype(np.uint8)
    import dataclasses
    runner_fp = InferenceRunner(cfg, variables, iters=ITERS_CAP)
    runner_q = InferenceRunner(
        dataclasses.replace(cfg, quant="int8",
                            quant_corr_scales=corr_scales),
        variables, iters=ITERS_CAP)
    # int8_mxu twin: the turbo tier's actual mode since round 22 — packs
    # pass THROUGH to the traced program, calibrated activation scales
    # ride in them (quantize_variables act_scales), exactly what the
    # engine builds from the same scale file.
    act_scales = quant.conv_input_scales(rec_a)
    mxu_vars = quant.quantize_variables(variables, act_scales=act_scales)
    runner_mxu = InferenceRunner(
        dataclasses.replace(cfg, quant="int8_mxu",
                            quant_corr_scales=corr_scales),
        mxu_vars, iters=ITERS_CAP)
    d_fp = runner_fp.disparity(left8, right8)
    d_q = runner_q.disparity(left8, right8)
    d_mxu = runner_mxu.disparity(left8, right8)
    epe_fp = float(np.mean(np.abs(d_fp - disp)))
    epe_q = float(np.mean(np.abs(d_q - disp)))
    epe_mxu = float(np.mean(np.abs(d_mxu - disp)))
    depe = epe_q - epe_fp
    depe_mxu = epe_mxu - epe_fp
    print(f"drift gate: epe fp32 {epe_fp:.3f} px, int8 {epe_q:.3f} px "
          f"(dEPE {depe:+.4f}), int8_mxu {epe_mxu:.3f} px "
          f"(dEPE {depe_mxu:+.4f}) — budget {CI_GATE_PX}", flush=True)
    assert abs(depe) <= CI_GATE_PX, \
        f"int8 CI drift gate failed: |dEPE| {abs(depe):.4f} > {CI_GATE_PX}"
    assert abs(depe_mxu) <= CI_GATE_PX, \
        f"int8_mxu CI drift gate failed: |dEPE| {abs(depe_mxu):.4f} > " \
        f"{CI_GATE_PX}"

    # --- jaxpr pin: the MXU path is actually taken ----------------------
    import jax.numpy as jnp
    im = jnp.zeros((1,) + hw + (3,), jnp.float32)
    report = quant.int8_matmul_report(jax.make_jaxpr(
        lambda v, a, b: runner_mxu.model.apply(v, a, b, iters=2,
                                               test_mode=True))(
        runner_mxu.variables, im, im))
    print(f"int8_mxu jaxpr: {report}", flush=True)
    assert report["int8_convs"] + report["int8_dots"] >= 1, \
        f"int8_mxu program traced no int8 matmuls: {report}"
    assert report["dequant_fed_matmuls"] == 0, \
        f"int8_mxu program dequantizes before a matmul: {report}"

    # --- serve one request at ?tier=turbo over HTTP ---------------------
    serve_cfg = ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=ITERS_CAP,
        tiers=("turbo", "quality"), default_tier="quality",
        quant_scales_path=scales_path, cost_telemetry=True)
    with StereoService(cfg, variables, serve_cfg) as svc:
        server = StereoHTTPServer(svc, port=0).start()
        url = server.url
        try:
            buf = io.BytesIO()
            np.savez(buf, left=left8, right=right8)
            req = urllib.request.Request(
                url + "/v1/disparity?tier=turbo", data=buf.getvalue(),
                method="POST",
                headers={"Content-Type": "application/x-npz"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                assert resp.status == 200
                assert resp.headers["X-Tier"] == "turbo", \
                    dict(resp.headers)
                iters_used = int(resp.headers["X-Iters-Used"])
                disp_turbo = np.load(io.BytesIO(resp.read()))
            assert disp_turbo.shape == hw and np.isfinite(
                disp_turbo).all()
            # The turbo answer through the engine IS the int8_mxu
            # runner's math (same make_forward program family, same
            # packs + calibrated activation scales from the scale file).
            assert float(np.mean(np.abs(disp_turbo - d_mxu))) < 1e-3

            # quality tier stays bitwise the fp32 solo path.
            req = urllib.request.Request(
                url + "/v1/disparity?tier=quality", data=buf.getvalue(),
                method="POST",
                headers={"Content-Type": "application/x-npz"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                disp_quality = np.load(io.BytesIO(resp.read()))
            assert np.array_equal(disp_quality, d_fp), \
                "quality tier must stay bitwise the fp32 solo program"

            # Per-tier metrics + the distinct turbo compile record.
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=60) as resp:
                metrics = resp.read().decode()
            for needle in ('infer_gru_iters_used_count{tier="turbo"} 1',
                           'serve_gru_iters_saved_total{tier="turbo"}'):
                assert needle in metrics, f"{needle!r} missing:\n" + \
                    "\n".join(ln for ln in metrics.splitlines()
                              if "turbo" in ln)
            with urllib.request.urlopen(url + "/debug/compiles",
                                        timeout=60) as resp:
                compiles = json.loads(resp.read())
            keys = [c["key"] for c in compiles["executables"]]
            turbo_keys = [k for k in keys if "quant=int8_mxu" in k]
            assert turbo_keys, \
                f"no quant=int8_mxu compile record in {keys}"
            assert any("quant" not in k for k in keys), keys
        finally:
            server.shutdown()

    rec = bench_record({
        "metric": "quant_ci_smoke",
        "value": round(depe_mxu, 4),
        "unit": f"int8_mxu dEPE px vs fp32 (cap {ITERS_CAP}, "
                f"{hw[0]}x{hw[1]}, {STEPS} steps, CPU; product gate in "
                f"tools/quant_drift.py)",
        "train_steps": STEPS,
        "epe_fp32": round(epe_fp, 4),
        "epe_int8": round(epe_q, 4),
        "epe_int8_mxu": round(epe_mxu, 4),
        "depe_int8": round(depe, 4),
        "ci_gate_px": CI_GATE_PX,
        "int8_mxu_jaxpr": report,
        "activation_scale_sites": len(act_scales),
        "turbo_iters_used": iters_used,
        "turbo_compile_keys": turbo_keys,
        "corr_scales": [round(s, 6) for s in corr_scales],
        "param_bytes": quant.quantized_param_bytes(mxu_vars),
    })
    print(json.dumps(rec))
    write_record(OUT, rec, indent=1)
    print(f"quant smoke OK -> {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark: realtime-config RAFT-Stereo inference FPS at KITTI resolution.

Replicates the reference's FPS protocol (reference: evaluate_stereo.py:77-82,
105-107): test-mode forward, inputs padded to /32 (375x1242 -> 384x1248),
warmup discarded, FPS = 1 / mean(per-image runtime).  Model is the realtime
configuration (reference: README.md:84 — shared backbone, n_downsample 3,
2 GRU layers, slow-fast, 7 iters, mixed precision).

Timing method: K forwards are chained on-device in a ``lax.fori_loop``
(inputs perturbed per-iteration so nothing folds away), a scalar is fetched,
and two K values are differenced to take the host's dispatch out:
    per_image = (t(K_hi) - t(K_lo)) / (K_hi - K_lo)

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``vs_baseline`` divides by 26 FPS — the reference paper's realtime-model
RTX-6000 claim (arXiv 2109.07547; external, see BASELINE.md — the repo
publishes no measured number, so the denominator inherits the paper's
uncertainty).  Run-to-run spread on the v5e: not measured yet (ROADMAP S1);
compare trends, not single runs.  North star
(BASELINE.json): vs_baseline >= 4.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_FPS = 26.0  # reference realtime model on RTX 6000 (paper claim)
KITTI_PADDED = (384, 1248)  # 375x1242 padded to /32 (evaluate_stereo.py:73)
BENCH_ITERS = 7             # realtime model --valid_iters
K_LO, K_HI = 3, 23
REPEATS = 3
BASELINE_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE.json")
# Warn only past clear noise: until the v5e's run-to-run spread is measured
# (module docstring) a tighter regression line would fire on healthy runs.
REGRESSION_FACTOR = 1.25


def _seconds_per_forward(model, variables, img1, img2, iters):
    from raft_stereo_tpu.profiling import (chained_seconds_per_call,
                                           make_forward_chain)

    # scalar float() fetch inside the chain = full sync
    # (see profiling.make_forward_chain)
    make_chain = make_forward_chain(
        lambda v, a, b: model.apply(v, a, b, iters=iters, test_mode=True)[1],
        variables, img1, img2)
    return chained_seconds_per_call(make_chain, k_lo=K_LO, k_hi=K_HI,
                                    repeats=REPEATS)


def phase_split(t_iters_s: float, t_one_iter_s: float, iters: int) -> dict:
    """First-class encoder-vs-GRU attribution (the ad-hoc round-3
    measurement, INFERENCE_PROFILE_r03.json): differencing the chained
    ``iters``-iteration and 1-iteration forwards isolates the per-GRU-iter
    cost; everything else (encoders, corr pyramid, final upsample,
    dispatch) is the fixed remainder."""
    per_iter = (t_iters_s - t_one_iter_s) / (iters - 1)
    fixed = t_one_iter_s - per_iter
    return {
        "metric": "realtime_phase_split",
        f"t_iters{iters}_ms": round(t_iters_s * 1e3, 3),
        "t_iters1_ms": round(t_one_iter_s * 1e3, 3),
        "per_gru_iter_ms": round(per_iter * 1e3, 4),
        "encoder_and_fixed_ms": round(fixed * 1e3, 4),
        f"gru_share_at_{iters}_iters": round(
            per_iter * iters / t_iters_s, 3),
    }


def check_regression(split: dict, fps: float) -> list:
    """Compare this run against BASELINE.json's published numbers; return
    warn lines (printed as JSON) when a phase regressed past the noise
    band.  Attribution first: the per-GRU-iter number is the one the fused
    update-block kernel moves."""
    warnings = []
    try:
        with open(BASELINE_JSON) as f:
            published = json.load(f).get("published", {})
    except (OSError, ValueError):
        return warnings
    ref = published.get("realtime_phase_split")
    if ref:
        for key in ("per_gru_iter_ms", "encoder_and_fixed_ms"):
            if key in ref and split[key] > REGRESSION_FACTOR * ref[key]:
                warnings.append({
                    "warning": f"{key} regressed vs BASELINE.json",
                    "value_ms": split[key],
                    "baseline_ms": ref[key],
                    "baseline_source": ref.get("source", "BASELINE.json"),
                })
    north_star = published.get("north_star_vs_baseline")
    if north_star and fps / BASELINE_FPS < north_star / REGRESSION_FACTOR:
        warnings.append({
            "warning": "fps fell below the north-star band",
            "vs_baseline": round(fps / BASELINE_FPS, 3),
            "north_star": north_star,
        })
    return warnings


def tier_latency_split(cfg, variables, img1, img2, fixed_s: float) -> list:
    """Per-tier chained latency at the bench's fixed input vs the
    fixed-depth program (config.REQUEST_TIERS — the serving engine's
    per-request early-exit presets).  Random bench inputs on seeded init
    weights rarely converge, so ``iters_used`` is reported next to every
    time: the latency win is a function of the OBSERVED trip count
    (EARLY_EXIT_r12.json carries the trained-weights curve); a tier may
    tie the baseline here but must never exceed it beyond the noise band
    (warn line)."""
    from raft_stereo_tpu.config import REQUEST_TIERS
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    rows = []
    for tier in REQUEST_TIERS.values():
        t_cfg = tier.apply(cfg)
        t_model = RAFTStereo(t_cfg)
        adaptive = t_cfg.exit_threshold_px > 0
        t_vars = variables
        if t_cfg.quant != "off":
            # The chained bench applies the model directly (not through
            # make_forward's int8-tree program), so feed the int8
            # ROUND-TRIPPED weights: the math matches the serving turbo
            # tier exactly; the HBM-residency half of the win is what
            # bench_serve.py's tier sweep measures through the engine.
            from raft_stereo_tpu.quant import (dequantize_variables,
                                               quantize_variables)
            t_vars = dequantize_variables(quantize_variables(variables))
        secs = _seconds_per_forward(t_model, t_vars, img1, img2,
                                    BENCH_ITERS)
        if adaptive:   # one un-chained apply fetches the trip count
            out = t_model.apply(t_vars, img1, img2, iters=BENCH_ITERS,
                                test_mode=True)
            iters_used = int(out[2])
        else:
            iters_used = BENCH_ITERS
        row = {
            "tier": tier.name,
            "exit_threshold_px": tier.exit_threshold_px,
            "min_iters": tier.min_iters,
            "quant": tier.quant,
            "per_image_ms": round(secs * 1e3, 3),
            "vs_fixed": round(secs / fixed_s, 3),
            "iters_used": iters_used,
            "iters_cap": BENCH_ITERS,
        }
        if secs > REGRESSION_FACTOR * fixed_s:
            row["warning"] = (f"tier {tier.name} is {secs / fixed_s:.2f}x "
                              f"the fixed-depth program — early-exit "
                              f"overhead regression")
        rows.append(row)
    return rows


def main():
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.telemetry.costs import aot_cost_summary
    from raft_stereo_tpu.telemetry.events import bench_record

    cfg = RaftStereoConfig.realtime()
    model = RAFTStereo(cfg)

    h, w = KITTI_PADDED
    rng = np.random.default_rng(0)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)

    variables = jax.jit(
        lambda r: model.init(r, img1[:, :64, :96], img2[:, :64, :96],
                             iters=1, test_mode=True)
    )(jax.random.PRNGKey(0))

    per_image = _seconds_per_forward(model, variables, img1, img2,
                                     BENCH_ITERS)
    t_one = _seconds_per_forward(model, variables, img1, img2, 1)
    fps = 1.0 / per_image
    # Cost denominator (telemetry/costs.py): the bench forward's compiled
    # flops/bytes ride the record, so every BENCH_*.json carries the
    # model-required work next to the measured time — measured seconds x
    # this flops number over the device peak IS the bench's MFU.
    cost = aot_cost_summary(
        jax.jit(lambda v, a, b: model.apply(v, a, b, iters=BENCH_ITERS,
                                            test_mode=True)[1]),
        variables, img1, img2)
    # Shared versioned header (telemetry/events.py): schema_version + the
    # run's device topology/timestamp ride the primary record.
    print(json.dumps(bench_record({
        "metric": "realtime_model_inference_fps_kitti_res",
        "value": round(fps, 2),
        "unit": "frames/s",
        "vs_baseline": round(fps / BASELINE_FPS, 3),
    }, cost=cost)))
    split = phase_split(per_image, t_one, BENCH_ITERS)
    split["fused_gru"] = cfg.fused_gru
    print(json.dumps(split))
    # Per-tier chained latency (adaptive early exit, config.REQUEST_TIERS)
    # against the fixed-depth program just measured.
    print(json.dumps({
        "metric": "realtime_tier_latency",
        "fixed_per_image_ms": round(per_image * 1e3, 3),
        "tiers": tier_latency_split(cfg, variables, img1, img2, per_image),
    }))
    for warning in check_regression(split, fps):
        print(json.dumps(warning))


if __name__ == "__main__":
    main()

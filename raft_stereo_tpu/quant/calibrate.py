"""Post-training calibration: per-layer activation ranges for the int8
inference tier, collected by running the model on in-distribution pairs.

tools/bf16_drift.py established this repo's rule for precision
claims: measure the drift in-distribution on the trained checkpoint, not
on paper.  Calibration is the collection half of that rule for int8 —
run the REAL forward (same padding semantics as ``eval/runner``, same
compute dtypes) over a handful of representative pairs and record, per
site, the percentile-clipped |activation| range that becomes the int8
scale:

* **Correlation pyramid levels** (``corr_levels`` entries) — the scales
  the int8 pyramid path uses (models/corr.py); computed from the exact
  fp32 volume math the ``reg``/``reg_fused`` backends run.
* **Feature maps** (``fmap1`` + the W-pooled ``fmap2`` pyramid) — the
  scales the no-volume ``alt`` kernel path uses.
* **Encoder layer outputs** — every fnet/cnet intermediate's range
  (Flax ``capture_intermediates``), recorded for the drift report and
  any future activation-quantized matmul path.

Percentile clipping (default 99.9) follows the PTQ literature (Wu et
al. 2020 §5): a handful of outlier correlation peaks would otherwise
blow the scale up and crush the resolution of the 99.9% of values that
carry the signal.

The result is a CHECKPOINT-ADJACENT JSON file (``save_scales`` /
``load_scales``): parameters on disk stay fp32, and the scale file rides
next to the checkpoint the way the config JSON already does.  Same
pairs in => byte-identical scale file out (pinned by
tests/test_quant.py — the calibration determinism contract).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

SCALES_VERSION = 1
DEFAULT_PERCENTILE = 99.9


def _percentile_absmax(values: List[np.ndarray], percentile: float) -> float:
    flat = np.concatenate([np.abs(np.asarray(v, np.float32)).ravel()
                           for v in values])
    return float(np.percentile(flat, percentile))


def calibrate(config, variables, pairs: Iterable[Tuple[np.ndarray,
                                                       np.ndarray]],
              percentile: float = DEFAULT_PERCENTILE,
              divis_by: int = 32) -> Dict:
    """Collect activation ranges over ``pairs`` of (left, right) HxWx3
    images and return the scale record (see module docstring).

    Runs the UNQUANTIZED forward — calibration measures the fp32/bf16
    distribution the int8 grid must cover, so ``config.quant`` is forced
    off for the pass; the pyramid is rebuilt here with the same
    ``build_corr_volume``/``build_corr_pyramid`` math the backends use.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.models.corr import (build_corr_pyramid,
                                             build_corr_volume, pool_axis)
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.ops.padding import InputPadder

    cfg = dataclasses.replace(config, quant="off")
    model = RAFTStereo(cfg)
    dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32

    level_vals: List[List[np.ndarray]] = [[] for _ in range(cfg.corr_levels)]
    f1_vals: List[np.ndarray] = []
    f2_level_vals: List[List[np.ndarray]] = [[] for _ in
                                             range(cfg.corr_levels)]
    act_vals: Dict[str, List[np.ndarray]] = {}

    def fmaps(img1, img2):
        """The feature maps the correlation backend consumes, via the
        model's own encoder params (mirrors models/raft_stereo.py)."""
        x1 = (2 * (img1 / 255.0) - 1.0).astype(dtype)
        x2 = (2 * (img2 / 255.0) - 1.0).astype(dtype)
        if cfg.shared_backbone:
            both = jnp.concatenate([x1, x2], axis=0)

            def shared_fmap(m, b):
                _levels, v = m.cnet(b)
                return m.conv2_out(m.conv2_res(v))

            fmap, inter = model.apply(variables, both, method=shared_fmap,
                                      capture_intermediates=True)
            f1, f2 = jnp.split(fmap, 2, axis=0)
        else:
            both = jnp.concatenate([x1, x2], axis=0)
            fmap, inter = model.apply(
                variables, both, method=lambda m, b: m.fnet(b),
                capture_intermediates=True)
            f1, f2 = jnp.split(fmap, 2, axis=0)
            # cnet ranges ride the same record (context encoder layers).
            _, inter_c = model.apply(
                variables, x1, method=lambda m, i: m.cnet(i),
                capture_intermediates=True)
            _merge_intermediates(act_vals, inter_c.get("intermediates", {}),
                                 prefix="cnet")
        _merge_intermediates(act_vals, inter.get("intermediates", {}),
                             prefix="fnet" if not cfg.shared_backbone
                             else "cnet")
        return f1, f2

    n_pairs = 0
    for left, right in pairs:
        left = np.asarray(left)
        right = np.asarray(right)
        padder = InputPadder((1,) + left.shape, divis_by=divis_by)
        pl_, pr_, pt, pb = padder.pads
        spec = ((pt, pb), (pl_, pr_), (0, 0))
        p1 = jnp.asarray(np.pad(left, spec, mode="edge")[None],
                         jnp.float32)
        p2 = jnp.asarray(np.pad(right, spec, mode="edge")[None],
                         jnp.float32)
        f1, f2 = fmaps(p1, p2)
        f1_vals.append(np.asarray(f1, np.float32))
        # The reg volume math, exactly as make_corr_fn_reg* builds it.
        pyramid = build_corr_pyramid(
            build_corr_volume(f1.astype(jnp.float32),
                              f2.astype(jnp.float32)), cfg.corr_levels)
        f2_lvl = f2
        for i, vol in enumerate(pyramid):
            level_vals[i].append(np.asarray(vol, np.float32))
            f2_level_vals[i].append(np.asarray(f2_lvl, np.float32))
            if i + 1 < cfg.corr_levels:
                f2_lvl = pool_axis(f2_lvl, axis=2)
        n_pairs += 1
    if n_pairs == 0:
        raise ValueError("calibration needs at least one (left, right) "
                         "pair")

    record = {
        "version": SCALES_VERSION,
        "mode": "int8",
        "percentile": percentile,
        "n_pairs": n_pairs,
        "config": json.loads(cfg.to_json()),
        "corr_levels": [
            round(_percentile_absmax(vals, percentile), 8)
            for vals in level_vals],
        "features": {
            "fmap1": round(_percentile_absmax(f1_vals, percentile), 8),
            "fmap2_levels": [
                round(_percentile_absmax(vals, percentile), 8)
                for vals in f2_level_vals]},
        "activations": {
            site: {"absmax_clipped":
                   round(_percentile_absmax(vals, percentile), 8)}
            for site, vals in sorted(act_vals.items())},
    }
    del jax  # imported for the side effects of backend init ordering
    return record


def _merge_intermediates(acc: Dict[str, List[np.ndarray]], tree,
                         prefix: str) -> None:
    """Flatten a Flax ``capture_intermediates`` tree into
    ``acc["prefix/module/path"]`` value lists."""
    if isinstance(tree, (tuple, list)):
        for v in tree:
            _merge_intermediates(acc, v, prefix)
        return
    if isinstance(tree, dict):
        for name, sub in tree.items():
            key = prefix if name == "__call__" else f"{prefix}/{name}"
            _merge_intermediates(acc, sub, key)
        return
    acc.setdefault(prefix, []).append(np.asarray(tree, np.float32))


def conv_input_scales(record: Dict) -> Dict[str, float]:
    """The per-conv activation scales of one calibration record, keyed
    by "/"-joined PARAM-tree module paths (``"fnet/trunk/conv1"``) — the
    ``act_scales`` argument of ``quant/core.quantize_variables`` for the
    int8_mxu compute path.

    Sites come from ``QuantConv``'s ``qin`` sow (the conv's INPUT —
    mostly relu/norm outputs the automatic ``__call__`` capture never
    sees).  Record keys carry the calibration pass's merge prefix as
    their first component (``"fnet/fnet/trunk/conv1/qin"``); strip it
    and the ``/qin`` suffix to recover the module path.  A path seen by
    more than one pass keeps the widest range (conservative).  Records
    from builds predating the qin sow simply yield {} — callers fall
    back to dynamic in-graph scales."""
    from raft_stereo_tpu.quant.core import clipped_scale

    out: Dict[str, float] = {}
    absmax: Dict[str, float] = {}
    for site, entry in record.get("activations", {}).items():
        parts = site.split("/")
        if parts[-1] != "qin" or len(parts) < 3:
            continue
        path = "/".join(parts[1:-1])
        v = float(entry["absmax_clipped"])
        absmax[path] = max(absmax.get(path, 0.0), v)
    for path, v in absmax.items():
        out[path] = clipped_scale(v)
    return out


def corr_scales(record: Dict) -> Tuple[float, ...]:
    """The per-level int8 volume scales of one calibration record — what
    ``RaftStereoConfig.quant_corr_scales`` carries into the compiled
    program (models/corr.py)."""
    from raft_stereo_tpu.quant.core import clipped_scale

    return tuple(clipped_scale(v) for v in record["corr_levels"])


def save_scales(path: str, record: Dict) -> str:
    """Write the checkpoint-adjacent scale file (atomic; stable key
    order so identical calibrations are byte-identical files)."""
    blob = json.dumps(record, indent=1, sort_keys=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(blob + "\n")
    os.replace(tmp, path)
    return path


def load_scales(path: str) -> Dict:
    with open(path) as f:
        record = json.load(f)
    if record.get("version") != SCALES_VERSION:
        raise ValueError(
            f"scale file {path}: version {record.get('version')!r} != "
            f"{SCALES_VERSION} (recalibrate with this build)")
    if record.get("mode") != "int8":
        raise ValueError(f"scale file {path}: mode "
                         f"{record.get('mode')!r} is not 'int8'")
    return record

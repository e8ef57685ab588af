"""Seeded weights for a RAFT-Stereo configuration, made on the device.

The benchmark's own table of the architecture's parameters (name, shape,
kind) built from the configuration's sizes alone, and one jitted call that
fills it from ``--seed``.  The program under test gets the tree through its
checkpoint format; the plain reference (``reference.py``) calls
``make_weights`` itself with the same seed and takes nothing from the
program.

Names follow the published module layout (cnet / fnet / update_block ...),
joined by ``/``; ``nest`` turns the flat table into the nested
``{"params": ..., "batch_stats": ...}`` tree a checkpoint holds.

Gains.  Untrained Kaiming weights make the refinement loop expansive (a
rounding difference grows ~5x an iteration, PERF.md §6 PR 22), so no limit
could pass fp32 and fail bf16 after 32 iterations.  The same shapes with a
small gain on the recurrent convolutions and on the disparity head's last
layer make the loop non-expansive, as training does; the work per pair is
unchanged.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

# (path, shape, kind); kind is one of kernel, bias, norm_scale, norm_bias,
# stat_mean, stat_var.
Spec = List[Tuple[str, Tuple[int, ...], str]]

GAINS = {
    "update_block/flow_head/conv2": 0.05,
    "update_block/gru08/convq": 0.5,
    "update_block/gru16/convq": 0.5,
    "update_block/gru32/convq": 0.5,
}


def _conv(spec: Spec, path: str, k, cin: int, cout: int) -> None:
    kh, kw = (k, k) if isinstance(k, int) else k
    spec.append((f"params/{path}/kernel", (kh, kw, cin, cout), "kernel"))
    spec.append((f"params/{path}/bias", (cout,), "bias"))


def _norm(spec: Spec, path: str, kind: str, c: int) -> None:
    if kind == "batch":
        spec.append((f"params/{path}/scale", (c,), "norm_scale"))
        spec.append((f"params/{path}/bias", (c,), "norm_bias"))
        spec.append((f"batch_stats/{path}/mean", (c,), "stat_mean"))
        spec.append((f"batch_stats/{path}/var", (c,), "stat_var"))
    elif kind != "instance":
        raise ValueError(f"norm {kind!r}: the benchmark's configurations "
                         f"use 'batch' and 'instance' only")


def _res_block(spec: Spec, path: str, norm: str, cin: int, planes: int,
               stride: int) -> None:
    _conv(spec, f"{path}/conv1", 3, cin, planes)
    _norm(spec, f"{path}/norm1", norm, planes)
    _conv(spec, f"{path}/conv2", 3, planes, planes)
    _norm(spec, f"{path}/norm2", norm, planes)
    if stride != 1 or cin != planes:
        _conv(spec, f"{path}/downsample_conv", 1, cin, planes)
        _norm(spec, f"{path}/norm3", norm, planes)


def trunk_strides(n_downsample: int) -> Tuple[int, int, int, int]:
    """Strides of the stem conv and of the three residual stages."""
    return (1 + (n_downsample > 2), 1, 1 + (n_downsample > 1),
            1 + (n_downsample > 0))


def _trunk(spec: Spec, path: str, norm: str, n_downsample: int) -> None:
    _conv(spec, f"{path}/conv1", 7, 3, 64)
    _norm(spec, f"{path}/norm1", norm, 64)
    cin = 64
    strides = trunk_strides(n_downsample)[1:]
    for i, (dim, stride) in enumerate(zip((64, 96, 128), strides), start=1):
        _res_block(spec, f"{path}/layer{i}_0", norm, cin, dim, stride)
        _res_block(spec, f"{path}/layer{i}_1", norm, dim, dim, 1)
        cin = dim


def param_spec(cfg: dict) -> Spec:
    """The parameter table of one configuration (``configs/<name>.json``'s
    ``model`` group)."""
    hd = list(cfg["hidden_dims"])
    cd = list(cfg.get("context_dims") or hd)
    n = cfg["n_gru_layers"]
    nd = cfg["n_downsample"]
    cnorm, fnorm = cfg["context_norm"], cfg["fnet_norm"]
    spec: Spec = []

    _trunk(spec, "cnet/trunk", cnorm, nd)
    for h, dims in enumerate((hd, cd)):
        _res_block(spec, f"cnet/outputs08_{h}_res", cnorm, 128, 128, 1)
        _conv(spec, f"cnet/outputs08_{h}_conv", 3, 128, dims[0])
    if n >= 2:
        _res_block(spec, "cnet/layer4_0", cnorm, 128, 128, 2)
        _res_block(spec, "cnet/layer4_1", cnorm, 128, 128, 1)
        for h, dims in enumerate((hd, cd)):
            _res_block(spec, f"cnet/outputs16_{h}_res", cnorm, 128, 128, 1)
            _conv(spec, f"cnet/outputs16_{h}_conv", 3, 128, dims[1])
    if n >= 3:
        _res_block(spec, "cnet/layer5_0", cnorm, 128, 128, 2)
        _res_block(spec, "cnet/layer5_1", cnorm, 128, 128, 1)
        for h, dims in enumerate((hd, cd)):
            _conv(spec, f"cnet/outputs32_{h}_conv", 3, 128, dims[2])
    for l in range(n):
        _conv(spec, f"context_zqr_conv{l}", 3, cd[l], 3 * hd[l])
    if cfg["shared_backbone"]:
        _res_block(spec, "conv2_res", "instance", 128, 128, 1)
        _conv(spec, "conv2_out", 3, 128, cfg["fnet_dim"])
    else:
        _trunk(spec, "fnet/trunk", fnorm, nd)
        _conv(spec, "fnet/conv2", 1, 128, cfg["fnet_dim"])

    corr_ch = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)
    ub = "update_block"
    _conv(spec, f"{ub}/encoder/convc1", 1, corr_ch, 64)
    _conv(spec, f"{ub}/encoder/convc2", 3, 64, 64)
    _conv(spec, f"{ub}/encoder/convf1", 7, 2, 64)
    _conv(spec, f"{ub}/encoder/convf2", 3, 64, 64)
    _conv(spec, f"{ub}/encoder/conv", 3, 128, 126)
    # GRU input widths: hidden + what the level is coupled to.
    gru_in = {0: hd[0] + 128 + (hd[1] if n > 1 else 0)}
    if n == 2:
        gru_in[1] = hd[1] + hd[0]
    if n == 3:
        gru_in[1] = hd[1] + hd[0] + hd[2]
        gru_in[2] = hd[2] + hd[1]
    for l, name in enumerate(("gru08", "gru16", "gru32")[:n]):
        _conv(spec, f"{ub}/{name}/convzr", 3, gru_in[l], 2 * hd[l])
        _conv(spec, f"{ub}/{name}/convq", 3, gru_in[l], hd[l])
    _conv(spec, f"{ub}/flow_head/conv1", 3, hd[0], 256)
    _conv(spec, f"{ub}/flow_head/conv2", 3, 256, 2)
    _conv(spec, f"{ub}/mask_conv1", 3, hd[0], 256)
    _conv(spec, f"{ub}/mask_conv2", 1, 256, 9 * (2 ** nd) ** 2)
    return spec


def make_weights(cfg: dict, seed: int) -> Dict[str, "jax.Array"]:
    """``{path: float32 array}`` for ``cfg`` from ``seed``: one jitted call,
    made where jax's default device is.  Every leaf's stream is keyed by its
    path, so the table's order does not matter."""
    import jax
    import jax.numpy as jnp

    spec = param_spec(cfg)

    def build(key):
        out = {}
        for path, shape, kind in spec:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "kernel":
                fan_out = shape[0] * shape[1] * shape[3]
                gain = GAINS.get(path[len("params/"):-len("/kernel")], 1.0)
                out[path] = z * (gain * math.sqrt(2.0 / fan_out))
            elif kind in ("bias", "norm_bias", "stat_mean"):
                out[path] = 0.02 * z
            else:                       # norm_scale, stat_var: around one
                out[path] = 1.0 + 0.1 * jnp.tanh(z)
        return out

    # A seed is any whole number up to a little over 2**31: fold it in as
    # two 16-bit halves rather than hand PRNGKey more than 32 signed bits.
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFF),
                             (seed >> 16) & 0xFFFFFF)
    return jax.jit(build)(key)


def nest(flat: Dict[str, object]) -> dict:
    """``{"a/b/c": x}`` to ``{"a": {"b": {"c": x}}}``."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree

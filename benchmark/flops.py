"""Operations and bytes of the work, counted from the configuration's
shapes — never from the implementation, and never from XLA's
``cost_analysis`` (which counts a loop body once and a Pallas call as 0).
A later PR that replaces a kernel leaves these numerators where they are.

Only products are counted (2 operations a multiply-add): convolutions, the
all-pairs correlation, the convex upsampling.  Norms, activations and the
lookup's interpolation are left out of the model's count; the lookup has
its own count below for its roofline.
"""

from __future__ import annotations

from benchmark.weights import param_spec, trunk_strides


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _conv_out(n: int, stride: int) -> int:
    return _ceil_div(n, stride)     # symmetric k//2 padding, odd kernels


def feature_hw(cfg: dict, h: int, w: int):
    f = 2 ** cfg["n_downsample"]
    return h // f, w // f


def _valid_taps(n_in: int, n_out: int, k: int, stride: int) -> int:
    """Taps of a k-wide kernel (padding k//2) that fall on the input, summed
    over the outputs of one axis."""
    pad = k // 2
    return sum(1 for o in range(n_out) for t in range(k)
               if 0 <= o * stride + t - pad < n_in)


def forward_flops(cfg: dict, h: int, w: int, iters: int,
                  padding_taps: bool = True) -> float:
    """``padding_taps=False`` leaves out the products with a convolution's
    zero padding, as XLA's ``cost_analysis`` does; the benchmark's count
    keeps them, as the usual convention does.

    Model FLOPs of one pair's test-mode forward at padded size (h, w):
    both encoders, the all-pairs volume once (the published definition; the
    no-volume lookup's per-iteration products are that kernel's business,
    not the model's), ``iters`` refinements, one upsampling."""
    kernels = {p[len("params/"):-len("/kernel")]: s
               for p, s, kind in param_spec(cfg) if kind == "kernel"}
    n, nd = cfg["n_gru_layers"], cfg["n_downsample"]
    s = trunk_strides(nd)
    h8, w8 = feature_hw(cfg, h, w)
    total = 0.0

    def conv(path, ho, wo, images=1, stride=1):
        kh, kw, cin, cout = kernels[path]
        taps = ho * wo * kh * kw
        if not padding_taps:
            taps = (_valid_taps(ho * stride, ho, kh, stride)
                    * _valid_taps(wo * stride, wo, kw, stride))
        return 2.0 * taps * cin * cout * images

    def res(path, hi, wi, stride, images=1):
        ho, wo = _conv_out(hi, stride), _conv_out(wi, stride)
        f = conv(f"{path}/conv1", ho, wo, images, stride)
        f += conv(f"{path}/conv2", ho, wo, images)
        if f"{path}/downsample_conv" in kernels:
            f += conv(f"{path}/downsample_conv", ho, wo, images, stride)
        return f, ho, wo

    def trunk(path, images):
        hi, wi = _conv_out(h, s[0]), _conv_out(w, s[0])
        f = conv(f"{path}/conv1", hi, wi, images, s[0])
        for i in (1, 2, 3):
            df, hi, wi = res(f"{path}/layer{i}_0", hi, wi, s[i], images)
            f += df
            df, hi, wi = res(f"{path}/layer{i}_1", hi, wi, 1, images)
            f += df
        assert (hi, wi) == (h8, w8), ((hi, wi), (h8, w8))
        return f

    shared = cfg["shared_backbone"]
    total += trunk("cnet/trunk", 2 if shared else 1)
    if shared:
        total += res("conv2_res", h8, w8, 1, 2)[0]
        total += conv("conv2_out", h8, w8, 2)
    else:
        total += trunk("fnet/trunk", 2) + conv("fnet/conv2", h8, w8, 2)
    # context heads, level by level
    lh, lw = h8, w8
    sizes = [(h8, w8)]
    for head in (0, 1):
        total += res(f"cnet/outputs08_{head}_res", lh, lw, 1)[0]
        total += conv(f"cnet/outputs08_{head}_conv", lh, lw)
    if n >= 2:
        df, lh, lw = res("cnet/layer4_0", lh, lw, 2)
        total += df + res("cnet/layer4_1", lh, lw, 1)[0]
        sizes.append((lh, lw))
        for head in (0, 1):
            total += res(f"cnet/outputs16_{head}_res", lh, lw, 1)[0]
            total += conv(f"cnet/outputs16_{head}_conv", lh, lw)
    if n >= 3:
        df, lh, lw = res("cnet/layer5_0", lh, lw, 2)
        total += df + res("cnet/layer5_1", lh, lw, 1)[0]
        sizes.append((lh, lw))
        for head in (0, 1):
            total += conv(f"cnet/outputs32_{head}_conv", lh, lw)
    for l in range(n):
        total += conv(f"context_zqr_conv{l}", *sizes[l])
    total += 2.0 * h8 * w8 * w8 * cfg["fnet_dim"]       # all-pairs volume

    ub = "update_block"
    per_iter = sum(conv(f"{ub}/encoder/{c}", h8, w8)
                   for c in ("convc1", "convc2", "convf1", "convf2", "conv"))
    names = ("gru08", "gru16", "gru32")[:n]
    gru = [conv(f"{ub}/{g}/convzr", *sizes[l])
           + conv(f"{ub}/{g}/convq", *sizes[l])
           for l, g in enumerate(names)]
    per_iter += sum(gru)
    if cfg["slow_fast_gru"]:        # the extra coarse-level updates
        if n == 3:
            per_iter += 2 * gru[2] + gru[1]
        elif n == 2:
            per_iter += gru[1]
    per_iter += sum(conv(f"{ub}/{c}", h8, w8)
                    for c in ("flow_head/conv1", "flow_head/conv2"))
    total += iters * per_iter
    # the upsampling mask is needed once, from the last hidden state (a
    # program that computes it in every iteration does work the answer
    # does not need, and gets no credit for it)
    total += conv(f"{ub}/mask_conv1", h8, w8) + conv(f"{ub}/mask_conv2",
                                                     h8, w8)
    total += 2.0 * h8 * w8 * 9 * (2 ** nd) ** 2         # convex upsampling
    return total


def lookup_taps(cfg: dict, h: int, w: int) -> int:
    """Elements one lookup of one pair writes: 2r+1 taps at each level for
    each pixel of the 1/f map."""
    h8, w8 = feature_hw(cfg, h, w)
    return h8 * w8 * cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)


def lookup_work(cfg: dict, h: int, w: int, itemsize: int) -> dict:
    """One pyramid lookup of one pair, as the algorithm needs it: each
    pixel of the 1/f map reads, at each level, the 2r+2 volume entries its
    2r+1 linear taps touch, its own position, and writes its taps.
    ``itemsize`` is the bytes of a stored volume entry."""
    h8, w8 = feature_hw(cfg, h, w)
    lv, r = cfg["corr_levels"], cfg["corr_radius"]
    taps = lv * (2 * r + 1)
    return {"flops": 3.0 * h8 * w8 * taps,
            "bytes": h8 * w8 * (lv * (2 * r + 2) * itemsize + 4
                                + taps * itemsize)}


def alt_lookup_work(cfg: dict, h: int, w: int, itemsize: int) -> dict:
    """One no-volume lookup of one pair: each pixel's feature vector against
    the 2r+2 right-feature vectors under its taps at each level (products
    over D channels), both feature maps read once, taps written."""
    h8, w8 = feature_hw(cfg, h, w)
    lv, r, d = cfg["corr_levels"], cfg["corr_radius"], cfg["fnet_dim"]
    taps = lv * (2 * r + 1)
    right = sum(h8 * (w8 // 2 ** i) * d for i in range(lv))
    return {"flops": 2.0 * h8 * w8 * lv * (2 * r + 2) * d
            + 3.0 * h8 * w8 * taps,
            "bytes": (h8 * w8 * d + right) * itemsize
            + h8 * w8 * (4 + taps * itemsize)}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """The least time the chip could take for ``work`` and which of the two
    peaks bounds it."""
    t_c = work["flops"] / peaks["bf16_flops_per_s"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

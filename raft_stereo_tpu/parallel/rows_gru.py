"""Context-parallel (image-row-sharded) GRU refinement loop.

``parallel/rows_sharded.py`` shards the encoders' full-resolution segment;
this module extends context parallelism through the REST of the forward —
the correlation volume, the per-iteration multilevel ConvGRU updates, and
convex upsampling.  The O(H) heavyweights — full-resolution stem
activations, the correlation volume, and the train scan's per-iteration
carries of every GRU level — stay sharded end to end; only the static
fine-level (1/2^nd-resolution) feature/context maps are replicated per
device, a deliberate sharding pin at the executor boundary (see the
``_pin`` note at the bottom).  That is what makes full-resolution TRAINING
scale across chips: the scan carries are memory a single chip cannot hold
at Middlebury-F-class frames.

Design — clamped extended windows, refreshed halos:

* Row geometry.  Device ``i`` owns fine-level rows ``[i*slab, (i+1)*slab)``
  and computes on the clamped window ``[start_i, start_i + slab + 2*halo)``
  with ``start_i = clamp(i*slab - halo, 0, H - slab - 2*halo)``.  Clamping
  (instead of zero-padding out-of-image halo rows) means every window row is
  a REAL image row, so the update block needs no row masking: at window
  edges interior to the image, SAME-padding pollution stays ≥ halo rows away
  from owned rows; at the image's true top/bottom the window edge COINCIDES
  with the image edge and SAME padding is exactly correct.
* Static tensors (feature maps → correlation volume/pyramid, per-level
  context biases) are windowed ONCE per forward via a neighbor
  ``lax.ppermute`` exchange.  Per-level halos halve with resolution
  (``halo >> level``), keeping windows aligned across the GRU pyramid.
* Per-iteration state (GRU hidden states, disparity) is cropped to owned
  rows at the end of each iteration and re-windowed at the start of the
  next — the only steady-state communication, ``2*halo`` boundary rows per
  level per iteration over ICI.
* Cross-resolution coupling.  ``pool2x`` is window-local by alignment.  The
  align-corners bilinear ``interp`` is NOT shift-invariant (its sampling
  grid depends on GLOBAL heights — ops/resize.py), so each device applies
  the GLOBAL interpolation matrix restricted to its window rows
  (host-precomputed, shipped as a mesh-sharded ``(n, dst, src)`` input).
  Source rows falling just outside the window (≤1, a property of the
  align-corners grid) are clamped to the window edge; the affected outputs
  are window-EDGE rows, swallowed by the halo margin.
* Exactness.  Owned-row outputs equal the unsharded computation up to float
  reassociation provided ``halo ≥`` the update block's per-iteration row
  receptive field (see ``default_gru_halo``); gradients are exact the same
  way because cropping zeroes every polluted row's cotangent and ``ppermute``
  transposes to the reverse permutation (tests/test_rows_gru.py asserts
  forward AND training-step parity on CPU meshes).

Reference parity note: the reference has no multi-device refinement path at
all — its only parallelism is ``nn.DataParallel`` batch replication
(train_stereo.py:134), and its alt backend exists because one GPU cannot
hold the full-resolution volume (core/corr.py:64-107).  This module is
capability beyond the reference, the stereo analog of ring-attention-style
sequence parallelism: halo exchange instead of all-to-all because stereo
correlation is per-row (epipolar) and convolution receptive fields are
local.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.ops.grids import coords_grid_x
from raft_stereo_tpu.ops.resize import _interp_matrix
from raft_stereo_tpu.ops.upsample import convex_upsample


def default_gru_halo(cfg: RaftStereoConfig) -> int:
    """Fine-level halo rows covering one iteration's row receptive field.

    Audit of one full update (models/update.py): motion encoder ≤5 rows
    (7×7 flow conv dominates) + ConvGRU convs ≤2 + flow/mask heads ≤2 +
    interp window-edge error ≤2 → ≤11 fine rows; mid/coarse levels shrink
    ≤5/≤2 at their own resolution against halves of the halo.  16 covers it
    with margin.  ``slow_fast_gru`` with 3 GRU levels runs the coarse GRU
    three times per iteration (core/raft_stereo.py:124-130 analog), tripling
    the coarse-level shrink against a quarter of the halo → 32.
    ``slow_fast_gru`` with 2 GRU levels (the realtime preset) doubles only
    the MID-level update: 2 updates × ≤5 rows at level-1 resolution = ≤10
    mid rows against halo/2 = 8 fine rows = 16 mid... — conservatively, the
    mid-level window carries halo/2 = 8 mid rows ≥ the 2×≤2-row GRU-conv
    shrink plus the one ≤5-row encoder pass (run once at the fine level
    only), so 16 still covers it; ``test_rows_gru_slow_fast_two_level``
    pins this empirically at halo=16."""
    if cfg.slow_fast_gru and cfg.n_gru_layers == 3:
        return 32
    return 16


def _geometry(h_f: int, n: int, halo: int):
    """Per-device clamped window geometry at the fine level (numpy)."""
    slab = h_f // n
    idx = np.arange(n)
    starts = np.clip(idx * slab - halo, 0, h_f - slab - 2 * halo)
    off_ext = starts - (idx * slab - 2 * halo)   # offset into the 4h-extended slab
    own_off = idx * slab - starts                # owned rows' offset in the window
    return slab, starts, off_ext, own_off


def _restricted_rows_interp(h_src: int, h_dst: int, starts_src, starts_dst,
                            len_src: int, len_dst: int) -> np.ndarray:
    """Global align-corners interp matrix restricted to each device's window.

    Returns (n, len_dst, len_src): rows ``starts_dst[i] : +len_dst`` of the
    global ``(h_dst, h_src)`` matrix, with source columns clamped into
    ``starts_src[i] : +len_src`` (only window-edge outputs are affected —
    module docstring)."""
    mg = _interp_matrix(h_src, h_dst)            # (h_dst, h_src)
    n = len(starts_src)
    out = np.zeros((n, len_dst, len_src), np.float32)
    for i in range(n):
        block = mg[starts_dst[i]:starts_dst[i] + len_dst]      # (len_dst, h_src)
        rel = np.arange(h_src) - starts_src[i]
        cols = np.clip(rel, 0, len_src - 1)
        # the window-edge clamp is sound only while align-corners sources
        # fall at most 1 row outside the window (module docstring); if
        # _interp_matrix semantics ever change (e.g. half-pixel centers),
        # fail loudly at trace time instead of silently misplacing weight
        carries = np.abs(block).sum(axis=0) > 0                # (h_src,)
        clamp_dist = np.abs(rel - cols)
        # explicit raise, not `assert`: python -O strips asserts, which
        # would silently misplace weight — the exact failure this check
        # exists to make loud (it runs once at trace time, in NumPy)
        if int(clamp_dist[carries].max(initial=0)) > 1:
            raise AssertionError(
                "rows_gru: interp source row falls more than 1 row outside "
                "its device window — _interp_matrix semantics changed; "
                "re-derive the halo geometry")
        acc = np.zeros((len_src, len_dst), np.float32)
        np.add.at(acc, cols, block.T)
        out[i] = acc.T
    return out


def _make_window_interp(row_mats):
    """Build the update block's ``interp_fn`` from per-device row matrices.

    ``row_mats``: {(src_rows, dst_rows): (dst_rows, src_rows) traced array}.
    Width interpolation uses the global matrix unchanged (W is unsharded)."""

    def interp_fn(x, dest):
        sh, sw = x.shape[1], x.shape[2]
        dh, dw = dest.shape[1], dest.shape[2]
        m = row_mats.get((sh, dh))
        if m is None:
            # A window-local align-corners resize would be SILENTLY wrong
            # (its grid must come from GLOBAL heights — module docstring);
            # fail at trace time instead.
            raise KeyError(
                f"rows_gru: no restricted interp matrix for window rows "
                f"{sh}->{dh}; registered sites: {sorted(row_mats)} — a new "
                f"update-block interp site must be added to the executor's "
                f"interp_shapes")
        y = jnp.einsum("bhwc,oh->bowc", x, m.astype(x.dtype),
                       precision=jax.lax.Precision.HIGHEST)
        if sw != dw:
            mx = jnp.asarray(_interp_matrix(sw, dw), dtype=x.dtype)
            y = jnp.einsum("bhwc,ow->bhoc", y, mx,
                           precision=jax.lax.Precision.HIGHEST)
        return y

    return interp_fn


def validate_rows_gru(cfg: RaftStereoConfig, h_f: int, n: int) -> int:
    """Check geometry constraints; return the fine-level halo."""
    halo = cfg.rows_gru_halo or default_gru_halo(cfg)
    align = 2 ** (cfg.n_gru_layers - 1)
    if h_f % n:
        raise ValueError(f"rows_gru: fine-level height {h_f} not divisible "
                         f"by rows_shards={n}")
    slab = h_f // n
    if slab % align or halo % 4:
        raise ValueError(
            f"rows_gru: per-shard fine rows {slab} must be divisible by "
            f"{align} and halo {halo} by 4 (GRU pyramid alignment)")
    if slab < 2 * halo:
        raise ValueError(
            f"rows_gru: per-shard fine rows H/f/n = {slab} < 2*halo = "
            f"{2 * halo}; a single ppermute exchange can only source rows "
            f"from the adjacent shard — use fewer shards, a larger image, "
            f"or a smaller rows_gru_halo (≥ the per-iteration receptive "
            f"field; see default_gru_halo)")
    return halo


def rows_sharded_gru_loop(cfg: RaftStereoConfig, dtype, update_params,
                          fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                          net_list: Sequence[jnp.ndarray],
                          context: Sequence[Tuple[jnp.ndarray, ...]],
                          disp0: jnp.ndarray, iters: int, test_mode: bool,
                          mesh: Mesh, axis: str):
    """Run the refinement loop with image rows sharded over ``mesh[axis]``.

    All array arguments are GLOBAL (B, H_l, W_l, ...) tensors from the
    encoders.  Returns exactly what the model's scan section returns:
    per-iteration full-resolution flows (train) or ``(disp_low, flow_up)``
    (test mode) — numerically equal to the unsharded loop on owned rows.
    """
    from raft_stereo_tpu.models.corr import make_corr_fn
    from raft_stereo_tpu.models.update import BasicMultiUpdateBlock

    n = mesh.shape[axis]
    if n != cfg.rows_shards or n < 2:
        raise ValueError(f"rows_gru: mesh axis {axis!r} size {n} != "
                         f"rows_shards={cfg.rows_shards} (need >= 2)")
    levels = cfg.n_gru_layers
    b, h_f, w_f, _ = net_list[0].shape
    factor = cfg.downsample_factor
    halo = validate_rows_gru(cfg, h_f, n)
    slab, starts, off_ext, own_off = _geometry(h_f, n, halo)

    for l in range(levels):
        if net_list[l].shape[1] != (h_f >> l):
            raise ValueError(
                f"rows_gru: level {l} height {net_list[l].shape[1]} != "
                f"{h_f >> l} — GRU levels must be exact halves")

    # Per-device offsets for every level, shipped as mesh-sharded inputs so
    # the shard body needs no axis_index branching.  Level-l values are the
    # fine values >> l — exact because slab, halo, and the clamp bound are
    # all divisible by 2**(levels-1).
    off_ext_arr = np.stack([off_ext >> l for l in range(levels)],
                           axis=1).astype(np.int32)       # (n, levels)
    own_off_arr = np.stack([own_off >> l for l in range(levels)],
                           axis=1).astype(np.int32)

    # Restricted interp matrices for the two cross-resolution sites
    # (coarse→mid, mid→fine), keyed by (src_rows, dst_rows) window sizes.
    interp_shapes = []
    interp_mats = []
    for l in range(levels - 1):           # site: level l+1 → level l
        len_dst = (slab >> l) + 2 * (halo >> l)
        len_src = (slab >> (l + 1)) + 2 * (halo >> (l + 1))
        interp_shapes.append((len_src, len_dst))
        interp_mats.append(_restricted_rows_interp(
            h_f >> (l + 1), h_f >> l, starts >> (l + 1), starts >> l,
            len_src, len_dst))

    param_specs = jax.tree_util.tree_map(lambda _: P(), update_params)
    rows = P(None, axis)
    ctx_specs = tuple(tuple(rows for _ in lvl) for lvl in context)
    net_specs = tuple(rows for _ in net_list)
    mat_specs = tuple(P(axis) for _ in interp_mats)

    if test_mode:
        out_specs = (rows, rows)
    else:
        out_specs = P(None, None, axis)   # (iters, B, H, W)

    perm_dn = [(j, j + 1) for j in range(n - 1)]   # rows from device i-1
    perm_up = [(j + 1, j) for j in range(n - 1)]   # rows from device i+1

    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names={axis},
        in_specs=(param_specs, rows, rows, net_specs, ctx_specs, rows,
                  P(axis), P(axis), mat_specs),
        out_specs=out_specs)
    def run(ub_params, fmap1_l, fmap2_l, net_l, ctx_l, disp_l,
            off_ext_l, own_off_l, mats_l):
        off = off_ext_l[0]     # (levels,) this device's window offsets
        own = own_off_l[0]
        row_mats = {interp_shapes[l]: mats_l[l][0] for l in range(levels - 1)}

        def window(x, lvl):
            """Local slab → clamped extended window via neighbor exchange."""
            hl = halo >> lvl
            top = jax.lax.ppermute(x[:, -2 * hl:], axis, perm_dn)
            bot = jax.lax.ppermute(x[:, :2 * hl], axis, perm_up)
            ext = jnp.concatenate([top, x, bot], axis=1)
            return jax.lax.dynamic_slice_in_dim(
                ext, off[lvl], x.shape[1] + 2 * hl, axis=1)

        def crop(x, lvl, scale=1):
            return jax.lax.dynamic_slice_in_dim(
                x, own[lvl] * scale, (slab >> lvl) * scale, axis=1)

        # -------- static per-forward windows: features → corr, context
        fmap1_w = window(fmap1_l, 0)
        fmap2_w = window(fmap2_l, 0)
        ctx_w = [tuple(window(t, l) for t in ctx_l[l]) for l in range(levels)]
        corr_fn = make_corr_fn(cfg, fmap1_w, fmap2_w)

        # parent=None: this executor may run inside the model's own call
        # (a live flax module scope) — construct the functional twin
        # detached so flax doesn't try to register it as a submodule.
        ub = BasicMultiUpdateBlock(cfg, dtype=dtype,
                                   interp_fn=_make_window_interp(row_mats),
                                   parent=None)

        def apply_ub(*args, **kwargs):
            return ub.apply({"params": ub_params}, *args, **kwargs)

        rows_w = slab + 2 * halo
        grid_x = coords_grid_x(b, rows_w, w_f, dtype=jnp.float32)

        def gru_iter(net_w, disp_w):
            """One refinement iteration on windowed tensors — mirrors the
            model's ``gru_step`` (models/raft_stereo.py) exactly."""
            disp_w = jax.lax.stop_gradient(disp_w)
            corr = checkpoint_name(
                corr_fn(grid_x + disp_w).astype(dtype), "corr_lookup")
            flow2 = jnp.stack([disp_w, jnp.zeros_like(disp_w)],
                              axis=-1).astype(dtype)
            net_w = list(net_w)
            if levels == 3 and cfg.slow_fast_gru:
                net_w = apply_ub(net_w, ctx_w, iter_fine=False,
                                 iter_mid=False, update=False)
            if levels >= 2 and cfg.slow_fast_gru:
                net_w = apply_ub(net_w, ctx_w, iter_fine=False,
                                 iter_coarse=(levels == 3), update=False)
            net_w, up_mask, delta_flow = apply_ub(
                net_w, ctx_w, corr, flow2,
                iter_mid=(levels >= 2), iter_coarse=(levels == 3))
            disp_w = disp_w + delta_flow[..., 0].astype(jnp.float32)
            return net_w, disp_w, up_mask

        def upsample(disp_w, mask_w):
            up = convex_upsample(disp_w[..., None],
                                 mask_w.astype(jnp.float32), factor)
            return up[..., 0]

        if test_mode:
            def step(carry, _):
                net_o, disp_o, _m = carry
                net_w = [window(t, l) for l, t in enumerate(net_o)]
                net_w, disp_w, up_mask = gru_iter(net_w, window(disp_o, 0))
                return (tuple(crop(t, l) for l, t in enumerate(net_w)),
                        crop(disp_w, 0), crop(up_mask, 0)), None

            mask0 = jnp.zeros((b, slab, w_f, cfg.mask_channels), dtype)
            # the scan's step returns a device-varying cropped mask; the
            # constant initial carry must carry the same varying type
            mask0 = jax.lax.pcast(mask0, (axis,), to="varying")
            (net_o, disp_o, mask_o), _ = jax.lax.scan(
                step, (tuple(net_l), disp_l, mask0), None, length=iters)
            flow_up_w = upsample(window(disp_o, 0), window(mask_o, 0))
            return disp_o, crop(flow_up_w, 0, factor)

        def step(carry, _):
            net_o, disp_o = carry
            net_w = [window(t, l) for l, t in enumerate(net_o)]
            net_w, disp_w, up_mask = gru_iter(net_w, window(disp_o, 0))
            flow_up = crop(upsample(disp_w, up_mask), 0, factor)
            return (tuple(crop(t, l) for l, t in enumerate(net_w)),
                    crop(disp_w, 0)), flow_up

        if cfg.remat_gru:
            step = jax.checkpoint(
                step, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *cfg.remat_save))
        _, flow_ups = jax.lax.scan(step, (tuple(net_l), disp_l), None,
                                   length=iters)
        return flow_ups

    # Pin the executor's inputs' H sharding in the surrounding auto-sharded
    # world.  Pure rows mesh (no data/corr axis — the full-resolution
    # -training regime): keep H SHARDED over the rows axis so the encoders'
    # ≤1/2-res tail stays row-sharded end to end — measured on the 8-dev
    # virtual mesh at 2048x2880, an UNSHARDED pin left ~49 GiB/device of
    # replicated tail backward stores (an iters-6 compile probe, not
    # re-measured on the v5e), dwarfing the sharded loop.  With a data
    # axis > 1 the pin flips to H-UNSHARDED: tail convs over (batch x rows)
    # simultaneously hit XLA's SPMD conv-KERNEL-gradient double-count
    # (reproduced and documented for the trunk executor,
    # parallel/rows_sharded.py); there the reshard happens at the
    # shard_map boundary and only the full-res segment + scan carries
    # stay sharded.
    from jax.sharding import NamedSharding
    unc = P.UNCONSTRAINED
    h_spec = axis if mesh.devices.size == n else None

    def _pin(x):
        spec = (P(unc, h_spec, unc, unc) if x.ndim == 4
                else P(unc, h_spec, unc))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    fmap1, fmap2, disp0 = _pin(fmap1), _pin(fmap2), _pin(disp0)
    net_list = tuple(_pin(t) for t in net_list)
    context = tuple(tuple(_pin(t) for t in lvl) for lvl in context)

    return run(update_params, fmap1, fmap2, tuple(net_list),
               tuple(tuple(lvl) for lvl in context), disp0,
               jnp.asarray(off_ext_arr), jnp.asarray(own_off_arr),
               tuple(jnp.asarray(m) for m in interp_mats))

"""Data-parallel histogram comms: Reduce-Scatter + shard-local split finding.

Reference: src/treelearner/data_parallel_tree_learner.cpp:285-299 — the
data-parallel learner never all-reduces full histograms.  Each worker owns a
feature slice: histogram blocks are Reduce-Scattered, every worker finds the
best split over ITS features only, and the workers Allreduce nothing but tiny
SplitInfo records (gain, feature, threshold, default direction, left sums — a
few hundred bytes, vs the multi-MB histogram block).

GSPMD re-design (hist_comms=reduce_scatter, docs/DISTRIBUTED.md): inside the
same shard_map that runs the per-device streaming kernel,

  * the per-device histogram block is `jax.lax.psum_scatter` over the
    feature-GROUP axis, so each device receives only its G/D group slice —
    bitwise equal to the psum result restricted to the slice (XLA reduces
    contributions in rank order for both collectives);
  * split finding runs shard-locally on that slice through a per-shard
    static sub-FeatureLayout (built here, ordered by ascending global
    feature id so local argmax tie-breaks reproduce the global scan's
    lowest-feature-index rule);
  * only the per-shard best-split records are `all_gather`ed and combined
    with the exact (max gain, lowest feature id) tie-break — trees are
    BIT-IDENTICAL to the psum path.

`hist_comms_dtype=bf16_pair` additionally halves the wire payload: remote
contributions ride the HIGH half of the f32 high/low bf16 split (the same
two-pass trick the histogram kernel uses, pallas/stream_kernel._wsplit), each
device's own-slice contribution stays exact f32 (its low half never needed
the wire), and the cross-device accumulation runs in f32 — contributions are
quantized at most once and partial sums never round to bf16.  Opt-in: not
bit-identical to psum (the quantized-GBDT line of work shows histogram
payloads tolerate reduced wire precision).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.split import (EPS_HESS, NEG_INF, FeatureLayout,
                         categorical_left_bitset, find_best_splits,
                         gather_feature_histograms)

HIST_COMMS_MODES = ("psum", "reduce_scatter")
HIST_COMMS_DTYPES = ("f32", "bf16_pair")

_BIGF = jnp.int32(2 ** 30)


class ShardPlan(NamedTuple):
    """Static per-shard feature ownership for reduce_scatter comms.

    Groups are sliced contiguously: shard s owns groups [s*gs, (s+1)*gs) of
    the G axis padded to g_pad = d*gs; a feature belongs to the shard that
    owns its storage group (EFB bundles live entirely inside one group, so
    a feature never straddles shards).  The sub-layout stacks carry one row
    per shard (leading axis d), features sorted by ascending GLOBAL id and
    padded to fmax with never-matching entries."""
    d: int
    g_pad: int
    gs: int                    # groups per shard
    fmax: int                  # max features owned by any shard (>= 1)
    feat_gid: np.ndarray       # (d, fmax) i32 global feature id, -1 pad
    gather_idx: np.ndarray     # (d, fmax, Bmax) i32 into flat (gs * Bmax)
    valid_mask: np.ndarray     # (d, fmax, Bmax) bool
    residual_pos: np.ndarray   # (d, fmax) i32
    nan_bin: np.ndarray        # (d, fmax) i32
    is_cat: np.ndarray         # (d, fmax) bool
    num_bins: np.ndarray       # (d, fmax) i32
    mzero_bin: Optional[np.ndarray]  # (d, fmax) i32 or None


def build_shard_plan(layout: FeatureLayout, routing, num_groups: int,
                     bmax: int, d: int) -> ShardPlan:
    """Slice the training FeatureLayout into d per-shard sub-layouts."""
    try:
        gather_idx = np.asarray(layout.gather_idx)
        valid_mask = np.asarray(layout.valid_mask)
        residual_pos = np.asarray(layout.residual_pos)
        nan_bin = np.asarray(layout.nan_bin)
        is_cat = np.asarray(layout.is_cat)
        num_bins = np.asarray(layout.num_bins)
        mzero = (np.asarray(layout.mzero_bin)
                 if layout.mzero_bin is not None else None)
        feat_group = np.asarray(routing.feat_group)
    except Exception as e:  # traced layouts cannot be sliced statically
        raise ValueError(
            "hist_comms=reduce_scatter needs concrete (non-traced) feature "
            f"layouts: {e}") from e
    F = gather_idx.shape[0]
    gs = -(-num_groups // d)
    g_pad = gs * d
    shard_of = feat_group[:F] // gs
    fmax = 1
    per_shard = []
    for s in range(d):
        ids = np.where(shard_of == s)[0].astype(np.int32)  # ascending ids
        per_shard.append(ids)
        fmax = max(fmax, len(ids))

    def stack(src, pad, dtype):
        out = np.full((d, fmax) + src.shape[1:], pad, dtype)
        for s, ids in enumerate(per_shard):
            out[s, :len(ids)] = src[ids]
        return out

    # group-local gather: subtract the shard's flat offset (every entry of
    # feature f indexes inside group feat_group[f]'s Bmax span)
    local_gidx = gather_idx - (shard_of * gs * bmax)[:, None]
    return ShardPlan(
        d=d, g_pad=g_pad, gs=gs, fmax=fmax,
        feat_gid=stack(np.arange(F, dtype=np.int32), -1, np.int32),
        gather_idx=stack(local_gidx.astype(np.int32), 0, np.int32),
        valid_mask=stack(valid_mask, False, bool),
        residual_pos=stack(residual_pos.astype(np.int32), -1, np.int32),
        nan_bin=stack(nan_bin.astype(np.int32), -1, np.int32),
        is_cat=stack(is_cat, False, bool),
        num_bins=stack(num_bins.astype(np.int32), 1, np.int32),
        mzero_bin=(stack(mzero.astype(np.int32), -1, np.int32)
                   if mzero is not None else None),
    )


def _local_layout(plan: ShardPlan, gi, vm, rp, nb, ic, nbins, mz
                  ) -> FeatureLayout:
    return FeatureLayout(
        gather_idx=gi[0], valid_mask=vm[0], residual_pos=rp[0],
        nan_bin=nb[0], is_cat=ic[0], num_bins=nbins[0],
        mzero_bin=(mz[0] if mz is not None else None))


def _plan_args(plan: ShardPlan):
    args = [plan.feat_gid, plan.gather_idx, plan.valid_mask,
            plan.residual_pos, plan.nan_bin, plan.is_cat, plan.num_bins]
    if plan.mzero_bin is not None:
        args.append(plan.mzero_bin)
    return [jnp.asarray(a) for a in args]


def reduce_hist(h: jax.Array, axis: str, g_dim: int, plan: ShardPlan,
                dtype: str = "f32", chunks: int = 1) -> jax.Array:
    """Reduce-Scatter the per-device histogram block over the group axis.

    Called INSIDE shard_map: h is this device's local block with
    h.shape[g_dim] == num_groups; returns the device's reduced
    (g_pad / d)-group slice.  dtype="f32" is one `psum_scatter`, bitwise
    equal to `psum` restricted to the slice; "bf16_pair" exchanges remote
    contributions as the high bf16 half (half the wire bytes), keeps the
    own-slice contribution exact f32, and accumulates in f32.

    ``chunks`` > 1 DOUBLE-BUFFERS the exact-wire collective (f32 / int32
    psum_scatter; the bf16_pair path pipelines through its all_to_all
    instead and ignores the knob — the engine resolves chunks=1 there):
    the slot axis (dim 0 —
    the round's child-slot channels, independent of the scatter's group
    axis) is split into ``chunks`` independent ``psum_scatter`` calls, so
    the XLA latency-hiding scheduler can start chunk 0's wire transfer
    while chunk 1's operand copy/packing still runs, and downstream
    consumers of already-delivered chunks overlap the tail (the classic
    comms/compute pipeline of pjit training stacks).  Each element rides
    the SAME rank-ordered reduction either way, so any chunking is
    bitwise identical to chunks=1 (asserted by the A/B suite)."""
    G = h.shape[g_dim]
    if plan.g_pad != G:
        pad = [(0, 0)] * h.ndim
        pad[g_dim] = (0, plan.g_pad - G)
        h = jnp.pad(h, pad)
    if dtype == "f32" or jnp.issubdtype(h.dtype, jnp.integer):
        # int32 quantized-gradient histograms are already the compressed,
        # exactly-summable wire format — bf16_pair would only lose bits
        n_slots = h.shape[0]
        if chunks > 1 and n_slots >= 2 * chunks:
            cut = n_slots // chunks
            parts = []
            for c in range(chunks):
                lo = c * cut
                hi = n_slots if c == chunks - 1 else lo + cut
                with jax.named_scope(f"hist_reduce_scatter_c{c}"):
                    parts.append(jax.lax.psum_scatter(
                        h[lo:hi], axis, scatter_dimension=g_dim,
                        tiled=True))
            return jnp.concatenate(parts, axis=0)
        with jax.named_scope("hist_reduce_scatter"):
            return jax.lax.psum_scatter(h, axis, scatter_dimension=g_dim,
                                        tiled=True)
    # bf16_pair: chunk the group axis per destination shard, ship the high
    # bf16 half, restore the exact f32 own-chunk, reduce in f32 rank order
    shape = h.shape
    hr = h.reshape(shape[:g_dim] + (plan.d, plan.gs) + shape[g_dim + 1:])
    with jax.named_scope("hist_all_to_all_bf16"):
        recv = jax.lax.all_to_all(hr.astype(jnp.bfloat16), axis,
                                  split_axis=g_dim, concat_axis=g_dim)
    me = jax.lax.axis_index(axis)
    own = jax.lax.dynamic_slice_in_dim(hr, me, 1, axis=g_dim)
    contrib = jax.lax.dynamic_update_slice_in_dim(
        recv.astype(jnp.float32), own, me, axis=g_dim)
    return jnp.sum(contrib, axis=g_dim)


def split_limbs(h: jax.Array) -> jax.Array:
    """int32 partial sums -> their high and low 16 bits stacked along axis 0
    (`h == hi * 65536 + lo`, lo in [0, 65535], hi signed), for a collective
    whose TOTAL may pass 2^31 though no device's part does: each limb sums
    over up to 2^15 devices without overflow.  Twice the wire bytes;
    `join_limbs` puts the total together as float32."""
    with jax.named_scope("hist_limbs_split"):
        return jnp.concatenate([h >> 16, h & 0xFFFF], axis=0)


def join_limbs(s: jax.Array) -> jax.Array:
    """The reduced limbs of `split_limbs` -> the totals as float32, rounded
    once (each limb converts exactly), i.e. what `.astype(float32)` of the
    exact integer total gives."""
    n = s.shape[0] // 2
    with jax.named_scope("hist_limbs_join"):
        return (s[:n].astype(jnp.float32) * 65536.0
                + s[n:].astype(jnp.float32))


def reduce_hist_rows(h: jax.Array, axis: str, g_dim: int, plan=None,
                     dtype: str = "f32", chunks: int = 1,
                     limbs: int = 1) -> jax.Array:
    """The histogram collective of a row-sharded mesh, called INSIDE
    shard_map by both growers: `psum` of the whole block (``plan`` None) or
    `reduce_hist` to the device's group slice; with ``limbs`` == 2 an int32
    block crosses in two 16-bit limbs and comes back float32."""
    wide = limbs == 2 and jnp.issubdtype(h.dtype, jnp.integer)
    if wide:
        h = split_limbs(h)
    if plan is not None:
        h = reduce_hist(h, axis, g_dim, plan, dtype, chunks=chunks)
    else:
        with jax.named_scope("hist_psum"):
            h = jax.lax.psum(h, axis)
    return join_limbs(h) if wide else h


def pack_gh_wire(h: jax.Array, axis: str, width: int, d: int):
    """Quantize-and-pack an int32 (…, 2) grad/hess histogram block into ONE
    integer lane per pair for the cross-device collective (hist_packed_width;
    reference contract: gradient_discretizer.cpp keeps quality with 16-bit
    packed accumulators on the wire).

    Called INSIDE shard_map on each device's exact int32 partial sums.
    width=16 packs the pair into one int32 lane (grad in the signed high 16
    bits, hess in the unsigned low 16) — HALF the wire bytes of the two-lane
    int32 block; width=8 packs into one int16 lane (8+8) — a QUARTER.

    Requantization is a shared power-of-two right shift chosen from the
    cross-device abs-max (`pmax`) so that d device partials sum without
    overflowing their field, and the hess field's sum stays < 2**hbits —
    carry-free into the signed grad field above it (hessian grid sums are
    non-negative for every supported objective).  A pow2 shift of integers
    with round-half-away is deterministic regardless of stochastic_rounding
    upstream, and is exact (shift 0) whenever the block magnitudes fit the
    field — the documented-ulp contract of the packed widths.

    Returns (packed, scales) with scales=(s_g, s_h) f32 pow2 factors the
    matching :func:`unpack_gh_wire` multiplies back after the collective."""
    g = h[..., 0]
    hh = h[..., 1]
    gbits, hbits = (15, 16) if width == 16 else (7, 8)
    # -8 margin: the f32 log2 bound below may round the int32 max down
    cap_g = (2 ** gbits - 8) // d
    cap_h = (2 ** hbits - 8) // d
    mg = jnp.max(jnp.abs(g)).astype(jnp.float32)
    mh = jnp.max(hh).astype(jnp.float32)
    if axis is not None:
        mg = jax.lax.pmax(mg, axis)
        mh = jax.lax.pmax(mh, axis)

    def _shift(m, cap):
        sh = jnp.ceil(jnp.log2(jnp.maximum(m, 1.0) / cap))
        return jnp.maximum(sh, 0.0).astype(jnp.int32)

    def _rshift_round(v, sh):
        half = jnp.where(sh > 0, (1 << jnp.maximum(sh - 1, 0)), 0)
        q = (jnp.abs(v) + half) >> sh
        return jnp.sign(v) * q

    sh_g, sh_h = _shift(mg, cap_g), _shift(mh, cap_h)
    gq = _rshift_round(g, sh_g)
    hq = _rshift_round(hh, sh_h)
    if width == 16:
        packed = gq * 65536 + hq
    else:
        packed = (gq * 256 + hq).astype(jnp.int16)
    scales = jnp.stack([jnp.exp2(sh_g.astype(jnp.float32)),
                        jnp.exp2(sh_h.astype(jnp.float32))])
    return packed, scales


def unpack_gh_wire(packed: jax.Array, scales: jax.Array,
                   width: int) -> jax.Array:
    """Inverse of :func:`pack_gh_wire` AFTER the summing collective: split
    the carry-free fields back out (floored mod keeps the low field
    non-negative; the high field's floor division is exact) and multiply the
    pow2 scales back, returning the usual f32 (…, 2) grid-valued block."""
    base = 65536 if width == 16 else 256
    p = packed.astype(jnp.int32)
    hq = jnp.mod(p, base)
    gq = (p - hq) // base
    return jnp.stack([gq.astype(jnp.float32) * scales[0],
                      hq.astype(jnp.float32) * scales[1]], axis=-1)


def make_sharded_finder(mesh, axis: str, plan: ShardPlan, scan_kw: dict):
    """shard_map-wrapped shard-local split finder.

    Returns find(hist, parent_g, parent_h, parent_c, col_mask) where hist
    is the GLOBAL (R, g_pad, Bmax, 2) histogram array sharded over its
    group axis; the result is a replicated 7-tuple (gain, feature,
    threshold, dir_flags, left_g, left_h, left_c) equal field-for-field to
    the full-F find_best_splits scan: each shard scans only its own
    features, and the tiny per-shard best records are all_gathered and
    combined with the exact (max gain, lowest global feature id)
    tie-break."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows

    has_mz = plan.mzero_bin is not None
    fmax = plan.fmax

    def _local(hist_s, pg, ph, pc, col_mask, fg, gi, vm, rp, nb, ic,
               nbins, *mz):
        sub = _local_layout(plan, gi, vm, rp, nb, ic, nbins,
                            mz[0] if has_mz else None)
        fg0 = fg[0]                                      # (fmax,)
        R = hist_s.shape[0]
        cm = col_mask[jnp.maximum(fg0, 0)] & (fg0 >= 0)
        with jax.named_scope("find_splits_shard_local"):
            res = find_best_splits(
                hist_s, pg, ph, pc, layout=sub,
                col_mask=jnp.broadcast_to(cm[None, :], (R, fmax)),
                **scan_kw)
        has = res.gain > NEG_INF / 2
        gfeat = jnp.where(has, fg0[res.feature], _BIGF)
        fstack = jnp.stack([res.gain, res.left_sum_g, res.left_sum_h,
                            res.left_count], axis=0)     # (4, R) f32
        istack = jnp.stack([gfeat, res.threshold, res.dir_flags], axis=0)
        with jax.named_scope("best_split_allgather"):
            gf = jax.lax.all_gather(fstack, axis)        # (D, 4, R)
            gi_ = jax.lax.all_gather(istack, axis)       # (D, 3, R)
        gains, feats = gf[:, 0], gi_[:, 0]
        # exact global-scan tie-break: max gain, then lowest feature id
        maxg = jnp.max(gains, axis=0)                    # (R,)
        cand = gains == maxg
        fsel = jnp.min(jnp.where(cand, feats, _BIGF), axis=0)
        pick = cand & (feats == fsel)
        dsel = jnp.argmax(pick, axis=0)                  # (R,) owner shard
        ar = jnp.arange(gains.shape[1])
        gain = gf[dsel, 0, ar]
        none = gain <= NEG_INF / 2
        feature = jnp.where(none, 0, fsel)               # argmax-of-empty = 0
        return (gain, feature.astype(jnp.int32),
                gi_[dsel, 1, ar].astype(jnp.int32),
                gi_[dsel, 2, ar].astype(jnp.int32),
                gf[dsel, 1, ar], gf[dsel, 2, ar], gf[dsel, 3, ar])

    rep = P()
    n_plan = 8 if has_mz else 7
    wrapped = shard_map_rows(
        _local, mesh,
        (P(None, axis, None, None), rep, rep, rep, rep)
        + (P(axis),) * n_plan,
        (rep,) * 7)
    plan_args = _plan_args(plan)

    def find(hist, pg, ph, pc, col_mask):
        return wrapped(hist, pg, ph, pc, col_mask, *plan_args)

    return find


def make_sharded_bitset(mesh, axis: str, plan: ShardPlan, cat_smooth: float,
                        min_data_per_group: int):
    """shard_map-wrapped categorical left-bitset: the OWNER shard of each
    chosen split's feature recomputes the (Bmax,) membership mask from its
    local histogram slice — identical arithmetic to the replicated path —
    and a tiny masked psum replicates it (S * Bmax floats, vs shipping the
    whole histogram block to every device)."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows

    has_mz = plan.mzero_bin is not None

    def _local(hist_s, feat, thr, dirf, pg, ph, pc, fg, gi, vm, rp, nb,
               ic, nbins, *mz):
        sub = _local_layout(plan, gi, vm, rp, nb, ic, nbins,
                            mz[0] if has_mz else None)
        fg0 = fg[0]
        R = hist_s.shape[0]
        ar = jnp.arange(R)
        own_f = fg0[None, :] == feat[:, None]            # (R, fmax)
        owned = jnp.any(own_f, axis=1)
        lfi = jnp.argmax(own_f, axis=1)                  # local feature idx
        hf = gather_feature_histograms(hist_s, sub, pg, ph)
        hf_feat = hf[ar, lfi]                            # (R, Bmax, 2)
        bitset = categorical_left_bitset(
            hf_feat, thr, dirf, sub.valid_mask[lfi], cat_smooth,
            min_data_per_group, pc / jnp.maximum(ph, EPS_HESS))
        with jax.named_scope("cat_bitset_psum"):
            out = jax.lax.psum(
                jnp.where(owned[:, None] & bitset, 1.0, 0.0), axis)
        return out > 0.5

    rep = P()
    n_plan = 8 if has_mz else 7
    wrapped = shard_map_rows(
        _local, mesh,
        (P(None, axis, None, None),) + (rep,) * 6 + (P(axis),) * n_plan,
        rep)
    plan_args = _plan_args(plan)

    def bitset(hist, feat, thr, dirf, pg, ph, pc):
        return wrapped(hist, feat, thr, dirf, pg, ph, pc, *plan_args)

    return bitset


def make_sharded_hist(mesh, axis: str, backend: str, num_slots: int,
                      bmax: int, acc_dtype):
    """shard_map-wrapped LOCAL histogram build for the feature-parallel
    learner: bins is sharded over its GROUP axis (rows replicated), so each
    device builds the (S, G/D, Bmax, 3) block for its own feature groups
    with NO collective at all — the reference's feature-parallel workers
    each histogram only their feature subset
    (feature_parallel_tree_learner.cpp:25-83).  Per-group sums are
    independent of other groups, so every shard's block is bitwise equal
    to the corresponding slice of the serial build."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows
    from ..ops.histogram import build_histograms

    def _local(bins_s, slot, grad, hess, cnt):
        with jax.named_scope("hist_shard_local"):
            return build_histograms(bins_s, slot, grad, hess, cnt,
                                    num_slots, bmax, backend=backend,
                                    acc_dtype=acc_dtype)

    rep = P()
    return shard_map_rows(
        _local, mesh,
        (P(None, axis), rep, rep, rep, rep),
        P(None, axis, None, None))


def make_sharded_hist_2d(mesh, row_axis: str, feature_axis: str,
                         backend: str, num_slots: int, bmax: int,
                         acc_dtype, k_classes: int = 0):
    """shard_map-wrapped histogram build for the 2D (rows x feature-groups)
    mesh: bins is sharded over BOTH axes, so device (f, r) holds an
    (N / D_rows, G / D_feat) block.  Each device builds the full local
    block — ZERO feature-axis collective, exactly the feature-parallel
    build of :func:`make_sharded_hist` — and ONE ``psum_scatter`` over the
    ROW axis (PR 5's reduce, data_parallel_tree_learner.cpp:285-299)
    delivers its G / (D_rows * D_feat) group slice.  The feature-local
    group count is gs * D_rows by construction (the engine pads groups to
    a multiple of D_rows * D_feat), so the tiled scatter needs no
    in-kernel padding, and flat shard s = f * D_rows + r holds groups
    [s * gs, (s+1) * gs) — the ShardPlan's contiguous-slice convention
    under the compound ``(feature, data)`` spec.

    ``k_classes`` > 0 builds the batched-multiclass (K, S, G, Bmax, 3)
    block instead (slot/grad/hess are (K, N); cnt stays (N,))."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows
    from ..ops.histogram import build_histograms, build_histograms_k

    k_mode = k_classes > 0
    g_dim = 2 if k_mode else 1

    def _local(bins_s, slot, grad, hess, cnt):
        with jax.named_scope("hist_2d_local"):
            if k_mode:
                h = build_histograms_k(bins_s, slot, grad, hess, cnt,
                                       k_classes, num_slots, bmax,
                                       backend=backend,
                                       acc_dtype=acc_dtype)
            else:
                h = build_histograms(bins_s, slot, grad, hess, cnt,
                                     num_slots, bmax, backend=backend,
                                     acc_dtype=acc_dtype)
        with jax.named_scope("hist_2d_row_scatter"):
            return jax.lax.psum_scatter(h, row_axis,
                                        scatter_dimension=g_dim,
                                        tiled=True)

    row = P(row_axis)
    per_row = P(None, row_axis) if k_mode else row
    out_g = (feature_axis, row_axis)
    out_spec = (P(None, None, out_g, None, None) if k_mode
                else P(None, out_g, None, None))
    return shard_map_rows(
        _local, mesh,
        (P(row_axis, feature_axis), per_row, per_row, per_row, row),
        out_spec)


def make_sharded_bin_gather_2d(mesh, row_axis: str, feature_axis: str,
                               g_loc: int, batched: bool = False):
    """Per-row stored-bin fetch on the 2D mesh: the chosen split feature's
    bins column lives on ONE feature shard of each row block, so the owner
    reads its local column slice and a psum over the FEATURE axis only
    replicates the value across that row block — the row axis never
    communicates (every row lives on exactly one row shard, and the
    result stays row-sharded).  ``g_loc`` is the per-feature-shard group
    count G / D_feat; ``grp`` holds GLOBAL group indices.  ``batched``
    handles the (K, N) multiclass-lockstep shape (rows on dim 1)."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows

    def _local(bins_s, grp):
        me = jax.lax.axis_index(feature_axis)
        local = grp.astype(jnp.int32) - me * g_loc
        owned = (local >= 0) & (local < bins_s.shape[1])
        idx = jnp.clip(local, 0, bins_s.shape[1] - 1)
        if batched:
            vals = jnp.take_along_axis(bins_s, idx.T, axis=1).T
        else:
            vals = jnp.take_along_axis(bins_s, idx[:, None], axis=1)[:, 0]
        with jax.named_scope("route_bin_psum_2d"):
            return jax.lax.psum(
                jnp.where(owned, vals.astype(jnp.int32), 0), feature_axis)

    grp_spec = P(None, row_axis) if batched else P(row_axis)
    return shard_map_rows(_local, mesh,
                          (P(row_axis, feature_axis), grp_spec), grp_spec)


def make_sharded_bin_gather(mesh, axis: str, gs: int):
    """shard_map-wrapped per-row stored-bin fetch for feature-parallel
    routing: rows are replicated but the bins column of a chosen split
    feature lives only on its owner shard, so the owner reads its local
    column slice and a tiny (N,) psum replicates the values — the routing
    decision costs one int32 per row per round, never a histogram column.
    ``grp`` is the (N,) replicated GLOBAL group index per row."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_rows

    def _local(bins_s, grp):
        me = jax.lax.axis_index(axis)
        local = grp.astype(jnp.int32) - me * gs
        owned = (local >= 0) & (local < bins_s.shape[1])
        idx = jnp.clip(local, 0, bins_s.shape[1] - 1)
        vals = jnp.take_along_axis(bins_s, idx[:, None], axis=1)[:, 0]
        with jax.named_scope("route_bin_psum"):
            return jax.lax.psum(
                jnp.where(owned, vals.astype(jnp.int32), 0), axis)

    return shard_map_rows(_local, mesh, (P(None, axis), P()), P())


def feature_bytes_per_round(num_slots: int, d: int, bmax: int,
                            has_categorical: bool, n_rows: int = 0,
                            num_class: int = 1) -> int:
    """Analytic per-device payload DELIVERED per growth round under
    tree_learner=feature: ZERO histogram bytes — only the 7-field
    per-shard best-split records (all_gather), the owner-recomputed
    categorical bitset psum when categorical features exist, and the
    per-row route-bin psum (one int32 per row; pass n_rows=0 to count
    split-decision traffic only)."""
    rec = d * num_class * num_slots * 7 * 4
    if has_categorical:
        rec += num_class * num_slots * bmax * 4
    return rec + n_rows * 4


def voting_bytes_per_round(num_slots: int, num_features: int, top_k2: int,
                           bmax: int, num_class: int = 1) -> int:
    """Analytic per-device payload DELIVERED per growth round under
    tree_learner=voting (PV-Tree): the one-hot vote psum (S x F f32) plus
    ONLY the elected top-2k features' histogram columns (S x 2k x Bmax x
    3 channels) — O(2k·B) instead of the data-parallel O(F·B)
    (voting_parallel_tree_learner.cpp:104/396)."""
    votes = num_class * num_slots * num_features * 4
    elected = num_class * num_slots * top_k2 * bmax * 3 * 4
    return votes + elected


def hist_comms_bytes_per_round(num_slots: int, num_groups: int, bmax: int,
                               d: int, mode: str, dtype: str = "f32",
                               num_class: int = 1,
                               packed_width: int = 32,
                               d_feat: int = 1) -> int:
    """Analytic per-device histogram payload DELIVERED per growth round.

    Convention (docs/DISTRIBUTED.md): bytes of reduced histogram payload a
    device materializes out of the round's collective — psum delivers the
    whole (K, S, G, Bmax, 2) block to every device (unpadded: only rs pads
    the group axis to a multiple of d); reduce_scatter delivers only the
    G/D group slice (plus the all_gathered best-split records, counted
    too).  bf16_pair halves the per-element wire width of the slice.
    Distinct from link-level ring traffic, which the mode also cuts
    (all-reduce moves ~2x a reduce-scatter).

    ``packed_width`` (hist_packed_width under use_quantized_grad +
    stream): 16 packs each (grad, hess) int pair into ONE int32 lane (4
    bytes per pair instead of 8 — half), 8 packs the pair into ONE int16
    lane (2 bytes per pair — quarter).  The two scale scalars ride the
    best-split record exchange; their bytes are noise and not counted.

    ``d_feat`` > 1 is the 2D (rows x feature-groups) mesh: the feature
    axis ships ZERO histogram bytes (each feature shard builds only its
    own groups, like tree_learner=feature), the row axis psum_scatters
    each feature-local block so a device materializes only its
    G / (d * d_feat) group slice, and the best-split records all_gather
    over BOTH axes (d * d_feat shards).  The 2D path runs the exact-f32
    contraction build (no stream kernel per feature shard), so the wire
    is always 4-byte f32 there — hist_packed_width and bf16_pair resolve
    to 32-wide f32 (documented in docs/DISTRIBUTED.md "2D mesh")."""
    if d_feat > 1:
        gs = -(-num_groups // (d * d_feat))
        elems_slice = num_class * num_slots * gs * bmax * 2
        record_bytes = (d * d_feat) * num_class * num_slots * 7 * 4
        return elems_slice * 4 + record_bytes
    per_elem = {32: 4, 16: 2, 8: 1}[packed_width]
    if mode == "psum":
        return num_class * num_slots * num_groups * bmax * 2 * per_elem
    gs = -(-num_groups // d)
    elems_slice = num_class * num_slots * gs * bmax * 2
    width = 2 if dtype == "bf16_pair" else 4
    if packed_width != 32:
        width = per_elem
    # + per-shard best records: 7 fields x 4 bytes from each of d shards
    record_bytes = d * num_class * num_slots * 7 * 4
    return elems_slice * width + record_bytes


def make_rs_context(mesh, axis: str, layout: FeatureLayout, routing,
                    num_groups: int, bmax: int, params):
    """Everything a grow function needs for reduce_scatter comms: the
    static ShardPlan, a SplitResult-shaped shard-local finder, and the
    owner-shard categorical bitset (None without categorical features).
    Shared by grow_tree and grow_tree_k so the scan kwargs can never
    drift between the two growth paths.

    ``axis`` may be a TUPLE of mesh axis names (the 2D mesh passes
    ``(feature, data)``): the plan then slices groups over the COMBINED
    d = prod(sizes) shards, and every collective inside the finder /
    bitset (all_gather, psum) runs over the compound axis — jax orders
    tuple-axis collectives first-named-major, so flat shard
    f * D_rows + r matches the post-psum_scatter slice ownership."""
    from ..ops.split import SplitResult

    axes = axis if isinstance(axis, tuple) else (axis,)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    plan = build_shard_plan(layout, routing, num_groups, bmax, n_dev)
    scan_kw = dict(
        lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
        min_data_in_leaf=max(params.min_data_in_leaf, 1),
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        cat_l2=params.cat_l2, cat_smooth=params.cat_smooth,
        max_cat_threshold=params.max_cat_threshold,
        max_cat_to_onehot=params.max_cat_to_onehot,
        min_data_per_group=params.min_data_per_group,
        enable_categorical=params.has_categorical,
        max_delta_step=params.max_delta_step)
    rs_find = make_sharded_finder(mesh, axis, plan, scan_kw)
    rs_bitset = (make_sharded_bitset(mesh, axis, plan, params.cat_smooth,
                                     params.min_data_per_group)
                 if params.has_categorical else None)

    def rs_split(hist_rows, pg, ph, pc, cmask):
        g, f, t, d_, lg, lh, lc = rs_find(hist_rows, pg, ph, pc, cmask)
        return SplitResult(gain=g, feature=f, threshold=t, dir_flags=d_,
                           left_sum_g=lg, left_sum_h=lh, left_count=lc,
                           right_sum_g=pg - lg, right_sum_h=ph - lh,
                           right_count=pc - lc)

    return plan, rs_split, rs_bitset

"""Batched best-first (leaf-wise) tree growth.

Reference: src/treelearner/serial_tree_learner.cpp:183-249 (Train: leaf-wise loop with
histogram subtraction and an LRU histogram pool) and src/treelearner/cuda/
cuda_single_gpu_tree_learner.cpp (the all-on-device variant this design mirrors).

TPU re-design decisions:
  * No DataPartition row reindexing — a ``leaf_id[N]`` vector is updated in place
    (dense elementwise ops; matches the CUDADataPartition idea but without compaction).
  * Growth is *batched best-first*: each device round selects the top-K splittable
    leaves by gain (K = max_splits_per_round) and splits them together, building
    histograms for all K new "smaller" children in ONE one-hot-matmul pass; the larger
    sibling comes from histogram subtraction. With K=1 this is exactly the reference's
    serial leaf-wise order; larger K trades a slightly different split order near the
    num_leaves budget for ~log-depth many passes over the data instead of num_leaves.
  * The whole growth loop is a lax.while_loop with static shapes, so one tree build is
    a single XLA program — and under pjit/shard_map the row dimension shards across a
    mesh and the histogram contraction turns into psum (data-parallel training; the
    reference's ReduceScatter specialisation in data_parallel_tree_learner.cpp:285-299
    falls out of XLA's GSPMD partitioning instead of hand-written collectives).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tree import TreeArrays
from .histogram import (build_histograms, build_histograms_k,
                        check_hist_backend, mesh_kind)
from .split import (NEG_INF, EPS_HESS, FeatureLayout, SplitResult,
                    categorical_left_bitset, constrained_child_outputs,
                    find_best_splits, gather_feature_histograms, leaf_output,
                    round_int, smooth_output)


class GrowParams(NamedTuple):
    """Static hyper-parameters of one tree build."""
    num_leaves: int
    max_depth: int
    max_splits_per_round: int
    lambda_l1: float
    lambda_l2: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    max_delta_step: float
    cat_l2: float
    cat_smooth: float
    max_cat_threshold: int
    max_cat_to_onehot: int
    min_data_per_group: int
    hist_backend: str = "auto"
    has_categorical: bool = True
    # constraints / sampling extensions (reference: monotone_constraints.hpp,
    # col_sampler.hpp, feature_histogram.hpp path_smooth + extra_trees)
    has_monotone: bool = False
    monotone_penalty: float = 0.0
    # intermediate method: per-round recompute of every leaf's bounds from
    # the opposite subtrees' ACTUAL outputs (monotone_constraints.hpp:330+
    # IntermediateLeafConstraints), instead of the basic method's frozen
    # split-midpoint bounds
    monotone_intermediate: bool = False
    # advanced method: per-threshold constraint refinement — each leaf's
    # output bound becomes a function of the split threshold, derived from
    # the ACTUAL outputs of the constraining (contiguous) leaves
    # (monotone_constraints.hpp:859 AdvancedLeafConstraints)
    monotone_advanced: bool = False
    path_smooth: float = 0.0
    has_interaction: bool = False
    extra_trees: bool = False
    bynode_fraction: float = 1.0
    hist_two_pass: bool = True   # two-pass bf16 hist weights (f32-accurate)
    # float64 histograms + split scan (hist_precision=double; segsum/onehot
    # backends under jax.enable_x64): reproduces the reference's
    # f32-gradients-into-double-histograms arithmetic so near-tied split
    # gains resolve exactly as stock LightGBM resolves them
    hist_double: bool = False
    int_hist: bool = False       # int8 quantized-gradient histograms (stream)
    # int32 words an int histogram entry is reduced across a mesh in: 2
    # (high and low 16 bits apart, comms.split_limbs) where the whole
    # table's sum of one level could pass 2^31 though a device's cannot
    hist_reduce_limbs: int = 1
    # bucketed one-hot M-axis for the stream kernel: static runs of
    # (bucket_bins, group_count) over the bucket-sorted group layout
    # (binning.device_group_order); None = uniform G * Bmax rows
    bin_buckets: tuple = None
    # cost-effective gradient boosting (cost_effective_gradient_boosting.hpp)
    has_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # data-parallel histogram collective (docs/DISTRIBUTED.md): "psum"
    # all-reduces the full histogram block each round; "reduce_scatter"
    # Reduce-Scatters feature-group slices, finds splits shard-locally and
    # all_gathers only the tiny best-split records (the reference's
    # data_parallel_tree_learner.cpp:285-299 pattern). Trees bit-identical.
    hist_comms: str = "psum"
    hist_comms_dtype: str = "f32"   # f32 | bf16_pair (compressed wire)
    # double-buffered reduce_scatter (parallel/comms.reduce_hist): number
    # of independent psum_scatter chunks along the slot/class axis so the
    # collective overlaps compute — bitwise identical to 1
    hist_comms_chunks: int = 1
    # packed-wire quantized histograms (docs/PERF.md "histogram-formulation
    # floor"): 16 re-quantizes the int32 grad/hess pair per round into
    # (int15, uint16) digits packed into ONE int32 lane, halving collective
    # bytes; 8 packs (int7, uint8) into int16 — a quarter.  The kernel
    # accumulation stays exact int32; only the WIRE is requantized (pow2
    # scales, documented-ulp).  32 = off.  No-op without a mesh.
    hist_packed_width: int = 32
    # GOSS+stream fusion (resolved by the engine from Config.route_fusion):
    # skip the per-round full-data route-only pass and replay the stored
    # round tables over all rows in ONE fused launch after growth —
    # bit-identical leaf ids, bins stream from HBM once per tree instead of
    # once per round
    route_fusion: bool = False

    @property
    def plain_growth(self) -> bool:
        """No non-plain growth feature active — the single predicate the
        voting learner, hist_comms=reduce_scatter, and batched multiclass
        growth all gate on (forced splits are per-run state the caller
        checks separately)."""
        return not (self.has_monotone or self.has_interaction
                    or self.has_cegb or self.extra_trees
                    or self.bynode_fraction < 1.0 or self.path_smooth > 0.0)


# pairs a step of a histogram round's tail where the round adapts to its count
TAIL_CHUNK = 8


def tail_chunk(budget: int) -> int:
    """How many pairs (a split leaf and its new sibling) a step of what
    follows a histogram round's kernel call in grow_tree — the histogram
    subtraction, the cache update and the children's split scan — takes
    under a split budget: a round that knows its split count k runs
    ceil(k / chunk) steps, so a round of one stops paying for the budget's
    64.  0 where the budget is not cut: under two chunks, or not whole
    chunks."""
    return (TAIL_CHUNK if budget >= 2 * TAIL_CHUNK
            and budget % TAIL_CHUNK == 0 else 0)


def _cache_order(hist: jax.Array) -> jax.Array:
    """(S, G, Bmax, 2) histograms as grow_tree's cache holds them: (S, 2 *
    Bmax, G), a leaf's grad bins then its hess bins, the groups minor-most.
    Over the 4-D shape XLA's gather and scatter each want the whole cache in
    a layout of their own (the size-2 channel axis draws a (2, 128) tiling
    for the one, the bins an (8, 128) for the other): two copies of the
    cache a round, 257 MB each on a 2,000-column table.  Over this shape
    they agree, and a round moves its pairs' slabs only."""
    hist = jnp.swapaxes(hist, 1, 3)
    return hist.reshape(hist.shape[0], -1, hist.shape[3])


def _hist_order(cache: jax.Array) -> jax.Array:
    """Rows of the cache back as (S, G, Bmax, 2) histograms."""
    return jnp.swapaxes(
        cache.reshape(cache.shape[0], 2, -1, cache.shape[2]), 1, 3)


class RoutingLayout(NamedTuple):
    """Static per-feature arrays used to route rows at a split."""
    feat_group: jax.Array       # (F,) i32 — group column holding the feature
    span_start: jax.Array       # (F,) i32 — group-local start of feature's bins
    default_bin: jax.Array      # (F,) i32 — feature-local default (zero) bin
    bundled: jax.Array          # (F,) bool — True if in a multi-feature bundle
    nan_bin: jax.Array          # (F,) i32 — feature-local NaN bin, -1 if none
    num_bins: jax.Array         # (F,) i32
    mzero_bin: jax.Array = None  # (F,) i32 — zero-as-missing bin, -1 if none


class _GrowState(NamedTuple):
    leaf_id: jax.Array
    # compacted-view leaf ids (GOSS/bagging row compaction; (1,) dummy when
    # compaction is off — the histogram pass routes the compacted rows, the
    # full-data route-only pass keeps `leaf_id` current for every row)
    leaf_id_c: jax.Array
    # node arrays (L-1 padded to L)
    split_feature: jax.Array
    threshold_bin: jax.Array
    dir_flags: jax.Array
    left_child: jax.Array
    right_child: jax.Array
    split_gain: jax.Array
    internal_value: jax.Array
    internal_weight: jax.Array
    internal_count: jax.Array
    cat_bitset: jax.Array
    # per-leaf arrays (L)
    sum_g: jax.Array
    sum_h: jax.Array
    cnt: jax.Array
    depth: jax.Array
    leaf_parent: jax.Array
    # constraint state (size-1 dummies when the feature is off — static branches)
    out_lo: jax.Array           # (L,) f32 — monotone lower bound on leaf output
    out_hi: jax.Array           # (L,) f32 — upper bound
    leaf_out: jax.Array         # (L,) f32 — constrained/smoothed output of each leaf
    # intermediate-monotone ancestry ((1,1)/(1,) dummies when off):
    anc_left: jax.Array         # (L, L) bool — leaf row is in node col's LEFT subtree
    anc_right: jax.Array        # (L, L) bool
    node_mono: jax.Array        # (L,) i32 — monotone dir of each internal node's feature
    node_depth: jax.Array       # (L,) i32 — depth of each internal node
    rect_lo: jax.Array          # (L, F) i32 — leaf's bin-space hyperrectangle [lo, hi)
    rect_hi: jax.Array          # (L, F) i32
    leaf_in_mono: jax.Array     # (L,) bool — leaf under a monotone split
                                # (IntermediateLeafConstraints::leaf_is_in_monotone_subtree_)
    adv_vmin: jax.Array         # (L, F, Bmax) f32 — advanced-method constraint
    adv_vmax: jax.Array         # slabs (see advanced_constraint_slabs)
    adv_split_ok: jax.Array     # (L, F) bool — sticky per-(leaf, feature)
                                # is_splittable_ (advanced method; (1,1) dummy
                                # when off). Children inherit, scans update.
    used_feat: jax.Array        # (L, F) bool — features on the leaf's path (interaction)
    cegb_used: jax.Array        # (F,) bool — features used anywhere in the model
    cegb_lazy: jax.Array        # (N, F) bool — per-row feature acquisition
                                # bitset (CEGB lazy costs; (1,1) dummy when off)
    round_idx: jax.Array        # () i32 — for PRNG folding (bynode / extra_trees)
    hist_passes: jax.Array      # () i32 — passes over the rows that built a
                                # histogram: the root pass + every round body
                                # run with_hist (the route-only sprint round
                                # is not one)
    hist_small_passes: jax.Array  # () i32 — those of hist_passes that took
                                # the stream kernel's small-slot pass (a
                                # round that split one or two leaves)
    scan_slots: jax.Array       # () i32 — pairs (a split leaf and its new
                                # sibling) the rounds' subtraction and child
                                # split scan ran over: whole chunks of
                                # tail_chunk() where a round adapts to its
                                # split count, the round's budget elsewhere
    best_gain: jax.Array
    best_feat: jax.Array
    best_thr: jax.Array
    best_dir: jax.Array
    best_left_g: jax.Array
    best_left_h: jax.Array
    best_left_c: jax.Array
    hist: jax.Array             # (L, 2 * Bmax, G): _cache_order
    num_leaves_cur: jax.Array   # () i32
    progressed: jax.Array       # () bool
    col_mask: jax.Array         # (F,) bool feature sampling mask for this tree
    # GOSS+stream fusion table buffer ((rounds_buf * NUM_TAB, L) f32; (1, 1)
    # dummy when fusion is off): round r's route tables land at rows
    # [r*NUM_TAB, (r+1)*NUM_TAB) and are replayed over ALL rows in ONE
    # fused launch after growth (pallas.stream_kernel.route_replay)
    tabs_buf: jax.Array


def intermediate_monotone_bounds(anc_left, anc_right, node_mono, leaf_out,
                                 big):
    """Per-leaf output bounds under the INTERMEDIATE monotone method.

    Reference: monotone_constraints.hpp IntermediateLeafConstraints — after
    any leaf output changes, the bounds of leaves in the OPPOSITE subtrees
    of its monotone ancestors are refreshed against actual outputs
    (GoUpToFindLeavesToUpdate + UpdateConstraintsWithOutputs). Here the
    lazy walk becomes a dense recompute: for every internal node, take the
    min/max leaf output of each side, then every leaf's bound is the
    tightest over its monotone ancestors. An increasing split requires
    left-subtree outputs <= right-subtree outputs, so a left leaf is capped
    by min(right outputs) and a right leaf floored by max(left outputs)."""
    lmax = jnp.max(jnp.where(anc_left, leaf_out[:, None], -big), axis=0)
    lmin = jnp.min(jnp.where(anc_left, leaf_out[:, None], big), axis=0)
    rmax = jnp.max(jnp.where(anc_right, leaf_out[:, None], -big), axis=0)
    rmin = jnp.min(jnp.where(anc_right, leaf_out[:, None], big), axis=0)
    inc = (node_mono > 0)[None, :]
    dec = (node_mono < 0)[None, :]
    hi = jnp.min(jnp.minimum(
        jnp.where(anc_left & inc, rmin[None, :], big),
        jnp.where(anc_right & dec, lmin[None, :], big)), axis=1)
    lo = jnp.max(jnp.maximum(
        jnp.where(anc_right & inc, lmax[None, :], -big),
        jnp.where(anc_left & dec, rmax[None, :], -big)), axis=1)
    return lo, hi


def advanced_constraint_slabs(anc_l, anc_r, node_mono, node_depth, node_feat,
                              node_thr, node_num, rect_lo, rect_hi, leaf_out,
                              bmax: int, big):
    """Per-(leaf, feature, bin) constraint value slabs for the ADVANCED
    monotone method (monotone_constraints.hpp:859 AdvancedLeafConstraints).

    The reference recomputes, per scanned leaf P and feature f, a
    piecewise-constant constraint over f's thresholds from the ACTUAL
    outputs of the constraining leaves (GoUpToFindConstrainingLeaves /
    GoDownToFindConstrainingLeaves / UpdateConstraints). Dense equivalent:

      * a leaf Q constrains P through exactly ONE ancestor — their LCA
        (Q sits in the opposite subtree of precisely that node);
      * the walk's (feature, side) dedup gate (OppositeChildShouldBeUpdated)
        becomes `recorded[P, lca]`, and its descent pruning
        (ShouldKeepGoingLeftRight) becomes a rectangle-overlap check of Q
        against every recorded plane deeper than the LCA;
      * UpdateConstraints' threshold slices are Q's bin-space interval on f
        (leaf hyperrectangles), and the piecewise max/min over constraining
        leaves is a per-bin max/min.

    Returns (v_min, v_max): (L, F, bmax) f32 — v_min[P, f, b] is the max
    over min-constraining leaves whose f-interval covers bin b of their
    output (-big where none), v_max the min over max-constraining leaves
    (+big where none). The scan turns these into per-threshold child bounds
    with prefix/suffix running extrema."""
    L = anc_l.shape[0]
    anc = anc_l | anc_r                      # (P leaves, B nodes)
    # recorded[P, B]: numerical ancestor with no deeper same-(feat, side)
    same_feat = node_feat[:, None] == node_feat[None, :]       # (B', B)
    deeper = node_depth[:, None] > node_depth[None, :]         # (B', B)
    sides_eq = anc_r[:, :, None] == anc_r[:, None, :]          # (P, B', B)
    blocked = jnp.any(anc[:, :, None] & node_num[None, :, None]
                      & same_feat[None] & sides_eq & deeper[None], axis=1)
    recorded = anc & node_num[None, :] & ~blocked              # (P, B)

    # LCA of every (P, Q) leaf pair
    common = anc[:, None, :] & anc[None, :, :]                 # (P, Q, B)
    d_masked = jnp.where(common, node_depth[None, None, :], -1)
    lca = jnp.argmax(d_masked, axis=2)                         # (P, Q)
    has_common = jnp.max(d_masked, axis=2) >= 0
    lca_depth = node_depth[lca]
    arQ = jnp.arange(L)
    rec_at = jnp.take_along_axis(recorded, lca, axis=1)        # (P, Q)
    mono_at = node_mono[lca]
    sideP = jnp.take_along_axis(anc_r, lca, axis=1)            # P right of LCA
    sideQ = anc_r[arQ[None, :], lca]                           # Q right of LCA
    opposite = sideP != sideQ
    # polarity: Q constrains P's MIN iff (mono>0 & P right) | (mono<0 & P left)
    upd_min = jnp.where(mono_at > 0, sideP, ~sideP)
    # reach: Q's rectangle must be compatible with every recorded plane of
    # P's chain deeper than the LCA (side taken from P's path)
    okR = rect_hi[:, node_feat] > (node_thr[None, :] + 1)      # (Q, B)
    okL = rect_lo[:, node_feat] <= node_thr[None, :]           # (Q, B)
    ok2 = jnp.where(anc_r[:, None, :], okR[None], okL[None])   # (P, Q, B)
    bad = jnp.any(recorded[:, None, :]
                  & (node_depth[None, None, :] > lca_depth[:, :, None])
                  & ~ok2, axis=2)
    C = has_common & rec_at & (mono_at != 0) & opposite & ~bad  # (P, Q)

    # Constraint slice of Q on P's threshold axis for feature f
    # (UpdateConstraints it_start/it_end): the intersection
    #   [max(Plo - 1, Qlo_eff), min(Phi, Qhi_eff))
    # where the P-side lower bound extends ONE bin below P's interval (the
    # up-walk records a right-descent's threshold itself, not threshold+1)
    # and Q's bound FACING the LCA's plane is dropped when the LCA splits
    # on f — that is exactly how an across-the-plane neighbour lands on
    # P's boundary bin and, via the prefix/suffix extrema, constrains only
    # the adjacent child at every threshold.
    bb = jnp.arange(bmax)
    F_dim = rect_lo.shape[1]
    BIGI = jnp.asarray(2 ** 30, jnp.int32)
    f_iota = jnp.arange(F_dim)

    def _slab(cmask_all, upd_sel, fill, reduce_fn):
        def one(args):
            crow, plo, phi, lca_row = args
            thrA = node_thr[lca_row]                           # (Q,)
            featA = node_feat[lca_row]
            numA = node_num[lca_row]
            q_right = anc_r[jnp.arange(L), lca_row]            # Q right of A
            facing = (f_iota[None, :] == featA[:, None]) & numA[:, None]
            qlo_eff = jnp.where(
                facing & q_right[:, None]
                & (rect_lo[:, :] == (thrA + 1)[:, None]),
                -BIGI, rect_lo)
            qhi_eff = jnp.where(
                facing & ~q_right[:, None]
                & (rect_hi[:, :] == (thrA + 1)[:, None]),
                BIGI, rect_hi)
            lo_s = jnp.maximum(plo[None, :] - 1, qlo_eff)      # (Q, F)
            hi_s = jnp.minimum(phi[None, :], qhi_eff)
            sel = (crow[:, None, None]
                   & (bb[None, None, :] >= lo_s[:, :, None])
                   & (bb[None, None, :] < hi_s[:, :, None]))   # (Q, F, bmax)
            vals = jnp.where(sel, leaf_out[:, None, None], fill)
            return reduce_fn(vals, axis=0)                     # (F, bmax)
        return jax.lax.map(one, (cmask_all & upd_sel, rect_lo, rect_hi, lca))

    v_min = _slab(C, upd_min, -big, jnp.max)
    v_max = _slab(C, ~upd_min, big, jnp.min)
    return v_min, v_max


def feature_local_bin(group_bin: jax.Array, feat: jax.Array,
                      routing: RoutingLayout) -> jax.Array:
    """Map a group-local stored bin to the feature-local bin for per-row routing."""
    span_start = routing.span_start[feat]
    default_bin = routing.default_bin[feat]
    bundled = routing.bundled[feat]
    nb = routing.num_bins[feat]
    v = group_bin.astype(jnp.int32)
    # bundled: stored span holds the nb-1 non-default bins starting at span_start
    ls = v - span_start
    in_span = (ls >= 0) & (ls < nb - 1)
    fb_b = jnp.where(in_span, ls + (ls >= default_bin).astype(jnp.int32), default_bin)
    return jnp.where(bundled, fb_b, v)


def _int_counts(use_stream: bool, mesh) -> bool:
    """The growers' one static choice of count dtype: leaf counts are int32
    where a row mesh runs the stream kernel - a device's float32 slot
    counts are exact (under 2^24 rows of a round's smaller child a device:
    any leaf under 33.5M rows a device), their sum across devices, the
    parent's count and the larger child's are taken in int32, and
    `leaf_count` / `internal_count` come out int32 - and the histogram
    dtype elsewhere (one device: float32 steps by 2 above 2^24 rows,
    ROADMAP C12)."""
    return use_stream and mesh is not None


def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array, cnt_w: jax.Array,
              col_mask: jax.Array, layout: FeatureLayout, routing: RoutingLayout,
              params: GrowParams, monotone: Optional[jax.Array] = None,
              interaction_groups: Optional[jax.Array] = None,
              key: Optional[jax.Array] = None,
              packed=None, forced=None, cegb_coupled=None,
              cegb_used=None, cegb_lazy=None, cegb_lazy_pen=None,
              gh_scales: Optional[jax.Array] = None,
              mesh=None, row_axis: Optional[str] = None,
              feature_axis: Optional[str] = None,
              compact_rows: int = 0,
              with_passes: bool = False,
              ) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree. Returns (TreeArrays, leaf_id[N]); with_passes=True
    appends the (3,) i32 counts of histogram-building passes over the rows
    this tree took (root pass + rounds), of those among them that took the
    small-slot pass, and of the pairs its rounds' tails ran over
    (tail_chunk; the fused iteration sums them into its state for
    telemetry.hist_pass_count() and the flag poll's record).

    grad/hess must already include any bagging mask; cnt_w is the mask itself.
    monotone: (F,) i32 in {-1,0,1} (reference: monotone_constraints.hpp, basic method).
    interaction_groups: (C, F) bool — allowed-feature groups (col_sampler.hpp).
    key: PRNGKey for per-node feature sampling / extra_trees random thresholds.
    packed: precomputed packed-bin layout of the stream backend (a StreamLayout
    or its bins_T) — bins never change, so the engine packs once per training
    run instead of once per tree.
    forced: static forced-split levels (reference: serial_tree_learner.cpp:628
    ForceSplits) — tuple of (leaf_ids, feats, thr_bins, default_lefts) tuples
    applied as unrolled rounds before gain-driven growth.
    mesh/row_axis: when set, the streaming kernel runs per-device under
    shard_map over the row axis and its histogram block is psum'd — the
    reference's per-worker fast histogram path + ReduceScatter
    (data_parallel_tree_learner.cpp:285-299); all other backends partition
    via GSPMD without this.
    mesh/feature_axis: the FEATURE-PARALLEL learner (tree_learner=feature,
    docs/DISTRIBUTED.md): bins arrives sharded over its feature-GROUP axis
    (rows replicated), each device builds histograms and runs the full
    split scan over ONLY its G/D group slice through the static per-shard
    sub-FeatureLayouts (parallel/comms.py), and only 7-field per-shard
    best-split records are all_gathered with the exact (max gain, lowest
    global feature id) tie-break — ZERO histogram bytes cross the wire
    (the reference Allreduces SplitInfo records only,
    feature_parallel_tree_learner.cpp:25-83).  Trees are bit-identical to
    the serial learner.
    mesh + row_axis + feature_axis TOGETHER: the 2D (rows x
    feature-groups) mesh (docs/DISTRIBUTED.md "2D mesh") — bins is
    sharded over BOTH axes, histograms build shard-locally over the
    feature axis and psum_scatter over the row axis, the split scan runs
    on each device's G/(D_rows*D_feat) slice through the same ShardPlan
    machinery keyed by the compound (feature, data) axis, and best-split
    records all_gather over both axes with the exact tie-break.  Per-row
    arrays stay sharded over rows only (replicated over feature).
    compact_rows: static PER-SHARD row capacity for GOSS/bagging row
    compaction (0 = off).  One stable partition per tree (ops/compact:
    a streaming kernel on the stream engine, a sort's permutation on the
    others) moves the in-bag rows to the front and every histogram pass
    runs over `compact_rows` rows instead of N — the dominant MAC cost
    scales with the sampled row count (reference analog:
    bag_data_indices_ prefix scans).  A per-round full-data ROUTE-ONLY
    kernel pass keeps leaf_id current for all N rows (score update, renew
    paths).  The caller guarantees compact_rows covers the in-bag count,
    is a multiple of the kernel block, and — under a mesh — divides the
    per-device shard."""
    N, G = bins.shape
    L = params.num_leaves
    S = min(params.max_splits_per_round, max(L - 1, 1))
    Bmax = layout.valid_mask.shape[1]
    F = layout.gather_idx.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    # leaf sums / histograms / gains dtype (see GrowParams.hist_double)
    hdt = jnp.float64 if params.hist_double else jnp.float32

    use_mono = params.has_monotone and monotone is not None
    use_imono = use_mono and params.monotone_intermediate
    use_amono = use_imono and params.monotone_advanced
    use_inter = params.has_interaction and interaction_groups is not None
    use_smooth = params.path_smooth > 0.0
    use_output = use_mono or use_smooth
    use_bynode = params.bynode_fraction < 1.0 and key is not None
    use_cegb = params.has_cegb
    use_lazy = use_cegb and cegb_lazy is not None and cegb_lazy_pen is not None
    use_extra = params.extra_trees and key is not None
    BIG = jnp.asarray(1e30, f32)

    find_splits = functools.partial(
        find_best_splits,
        layout=layout,
        lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
        min_data_in_leaf=max(params.min_data_in_leaf, 1),
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        cat_l2=params.cat_l2, cat_smooth=params.cat_smooth,
        max_cat_threshold=params.max_cat_threshold,
        max_cat_to_onehot=params.max_cat_to_onehot,
        min_data_per_group=params.min_data_per_group,
        enable_categorical=params.has_categorical,
        monotone=monotone if use_mono else None,
        monotone_penalty=params.monotone_penalty,
        path_smooth=params.path_smooth,
        max_delta_step=params.max_delta_step,
    )

    def cegb_pen(counts, used_mask, lazy_unused=None):
        """(R, F) CEGB gain penalty (DeltaGain, cegb hpp:80): tradeoff *
        (penalty_split * n_leaf + coupled[f] * not-yet-used +
        lazy[f] * rows-in-leaf-not-yet-charged-for-f)."""
        pen = params.cegb_tradeoff * params.cegb_penalty_split * counts[:, None]
        if cegb_coupled is not None:
            pen = pen + params.cegb_tradeoff * cegb_coupled[None, :] * \
                (~used_mask)[None, :]
        if lazy_unused is not None:
            pen = pen + params.cegb_tradeoff * cegb_lazy_pen[None, :] * \
                lazy_unused
        return jnp.broadcast_to(pen, (counts.shape[0], F))

    def lazy_unused_counts(used, slot, nslots):
        """(R, F) count of rows in each slot's leaf that have NOT yet paid
        feature f's lazy acquisition cost (CalculateOndemandCosts,
        cegb hpp:140: rows outside the feature_used_in_data_ bitset)."""
        sv = jnp.where(slot >= 0, slot, nslots)
        return jax.ops.segment_sum(
            (~used).astype(jnp.float32), sv,
            num_segments=nslots + 1)[:nslots]

    def node_col_mask(base_mask, used_feat_rows, rkey, rows):
        """Per-node feature mask: tree-level sampling & interaction-allowed &
        bynode sampling (reference: col_sampler.hpp GetByNode)."""
        m = jnp.broadcast_to(base_mask, (rows, F))
        if use_inter:
            # allowed = union of constraint groups that contain the leaf's path set
            contains = ~jnp.any(used_feat_rows[:, None, :]
                                & ~interaction_groups[None, :, :], axis=-1)  # (R, C)
            allowed = jnp.any(contains[:, :, None] & interaction_groups[None], axis=1)
            m = m & allowed
        if use_bynode:
            # sample ceil(fraction * available) from the node's ALLOWED set
            # (reference: col_sampler.hpp GetByNode samples from valid features)
            u = jnp.where(m, jax.random.uniform(rkey, (rows, F)), -1.0)
            avail = jnp.sum(m, axis=1, keepdims=True)
            kcnt = jnp.maximum(
                jnp.ceil(params.bynode_fraction * avail), 1.0).astype(jnp.int32)
            order = jnp.argsort(-u, axis=1)
            rank = jnp.argsort(order, axis=1)
            m = m & (rank < kcnt)
        return m

    # ---- root ----
    use_stream = params.hist_backend == "stream"
    use_fp = mesh is not None and feature_axis is not None
    int_counts = _int_counts(use_stream, mesh)
    cdt = i32 if int_counts else hdt

    def count_h(c):
        """A count of the state's (`cdt`) in the histogram dtype."""
        return c.astype(hdt) if int_counts else c
    # 2D rows x feature-groups mesh: the fp machinery keyed by the
    # COMPOUND (feature, data) axis + a row-axis psum_scatter in the build
    use_2d = use_fp and row_axis is not None
    use_compact = compact_rows > 0
    # feature-parallel replicates rows, so its compaction is the
    # single-device stable partition (bins' sharded GROUP axis is untouched
    # by the row gather); the 2D mesh shards rows too, and GOSS/bagging run
    # there via exact zero-weight masking
    check_hist_backend(params.hist_backend,
                       mesh=mesh_kind(mesh, row_axis, feature_axis),
                       double=params.hist_double, compact=use_compact)
    fuse, R_buf = False, 1   # GOSS+stream fusion (resolved in the stream block)
    Bpad = -(-Bmax // 8) * 8
    # reduce_scatter comms (docs/DISTRIBUTED.md): the histogram block is
    # Reduce-Scattered over the feature-group axis instead of psum'd whole,
    # split finding runs shard-locally on each device's G/D slice, and only
    # the per-shard best-split records are all_gathered — the reference's
    # data_parallel_tree_learner.cpp:285-299 comms pattern, bit-identical
    # to the psum path (A/B via hist_comms / LGBTPU_HIST_COMMS)
    use_rs = (mesh is not None and use_stream
              and params.hist_comms == "reduce_scatter")
    G_h = G   # histogram-state group count (mesh-padded in rs mode)
    plan = None
    if use_rs:
        if not params.plain_growth or forced or params.hist_double:
            raise ValueError(
                "hist_comms=reduce_scatter supports the plain feature set "
                "only; the engine falls back to hist_comms=psum for "
                "constraint features and forced splits")
        from ..parallel.comms import make_rs_context, reduce_hist
        plan, rs_split, rs_bitset = make_rs_context(
            mesh, row_axis, layout, routing, G, Bmax, params)
        G_h = plan.g_pad
    # FEATURE-PARALLEL (tree_learner=feature): the histogram state itself
    # is sharded over the group axis and built shard-locally (no
    # collective); the split scan reuses the SAME ShardPlan machinery the
    # rs path proved bit-identical, minus the reduce — the only wire
    # traffic is best-split records, owner-shard categorical bitsets, and
    # one int32 per row for routing
    if use_fp:
        if not params.plain_growth or forced:
            raise ValueError(
                "feature-sharded growth (tree_learner=feature or the 2D "
                "mesh) supports the plain feature set only (no monotone/"
                "interaction constraints, CEGB, forced splits, path "
                "smoothing, extra_trees, or feature_fraction_bynode)")
        from ..parallel.comms import (make_rs_context, make_sharded_hist,
                                      make_sharded_hist_2d,
                                      make_sharded_bin_gather,
                                      make_sharded_bin_gather_2d)
        fp_axis = (feature_axis, row_axis) if use_2d else feature_axis
        fp_plan, fp_split, fp_bitset = make_rs_context(
            mesh, fp_axis, layout, routing, G, Bmax, params)
        if fp_plan.g_pad != G:
            raise ValueError(
                f"feature-sharded bins must arrive group-padded to a "
                f"multiple of the mesh shard count (got {G} groups, need "
                f"{fp_plan.g_pad}); the engine pads at construction")
        G_h = G
        if use_2d:
            d_feat = int(mesh.shape[feature_axis])
            fp_hist_1 = make_sharded_hist_2d(mesh, row_axis, feature_axis,
                                             params.hist_backend, 1, Bmax,
                                             hdt)
            fp_hist_S = make_sharded_hist_2d(mesh, row_axis, feature_axis,
                                             params.hist_backend, S, Bmax,
                                             hdt)
            fp_bin = make_sharded_bin_gather_2d(mesh, row_axis,
                                                feature_axis, G // d_feat)
        else:
            fp_hist_1 = make_sharded_hist(mesh, feature_axis,
                                          params.hist_backend, 1, Bmax, hdt)
            fp_hist_S = make_sharded_hist(mesh, feature_axis,
                                          params.hist_backend, S, Bmax, hdt)
            fp_bin = make_sharded_bin_gather(mesh, feature_axis, fp_plan.gs)
    if use_stream:
        from ..pallas.stream_kernel import (NUM_TAB, build_route_tables,
                                            pack_bins_T, route_and_hist_live,
                                            route_replay, small_pass_index,
                                            stream_tiling)
        # rows a kernel block, and groups an M-tile where the table's
        # one-hot does not fit VMEM whole (0: one tile)
        T_rows, tile_groups = stream_tiling(
            Bmax, G, params.int_hist, bin_buckets=params.bin_buckets)[:2]
        if packed is None:
            with jax.named_scope("pack_bins"):
                bins_T = pack_bins_T(bins, T_rows, max_bins=Bmax,
                                     tile_groups=tile_groups).bins_T
        else:
            # bare array (int metadata would turn into tracers as a jit arg)
            bins_T = packed.bins_T if hasattr(packed, "bins_T") else packed
        n_pad = bins_T.shape[1]
        use_int = params.int_hist and gh_scales is not None
        if use_int:
            # integer-valued rows for the int8 contraction; histograms come
            # back as exact int32 sums and are unscaled to the usual
            # grid-valued f32 (reference: gradient_discretizer.cpp)
            inv_g = 1.0 / jnp.maximum(gh_scales[0], 1e-30)
            inv_h = 1.0 / jnp.maximum(gh_scales[1], 1e-30)
            w_grad, w_hess = grad * inv_g, hess * inv_h
            hscale = gh_scales                                # (2,)
        else:
            w_grad, w_hess = grad, hess
        w_T = jnp.zeros((8, n_pad), f32)
        w_T = (w_T.at[0, :N].set(w_grad).at[1, :N].set(w_hess)
                  .at[2, :N].set(cnt_w))

        # ---- GOSS/bagging row compaction: one stable partition per tree
        # (never a per-round gather; one streaming kernel, the in-bag rows
        # placed by their prefix counts — pallas/compact_kernel.py) builds
        # the compact view every histogram pass of this tree streams; the
        # columns past the in-bag rows carry exact zero weights, so
        # truncating them changes no f32 sum (the full vs compacted
        # bit-identity the A/B suite asserts)
        bins_T_h, w_T_h = bins_T, w_T
        if use_compact:
            from .compact import compact_transposed_view
            bins_T_h, w_T_h = compact_transposed_view(
                bins_T, w_T, 2, compact_rows, T_rows,
                mesh=mesh, row_axis=row_axis, tile_groups=tile_groups)
        n_pad_h = bins_T_h.shape[1]

        # ---- GOSS+stream fusion (docs/PERF.md "histogram-formulation
        # floor"): only the COMPACTED path runs a per-round full-data
        # route-only pass, and fusion removes it — each round's route
        # tables are stashed in a buffer and replayed over ALL rows in ONE
        # launch after growth (bins stream from HBM once per tree, not once
        # per round; bit-identical by _route_step sharing).  Gated off for
        # features that read every row's CURRENT leaf id mid-growth (CEGB
        # lazy costs), categorical trees (bitset overlays are not in the
        # round tables), forced splits / depth limits (non-sprint
        # schedules), and leaf budgets whose table buffer would not stay
        # VMEM-resident.
        fuse = (params.route_fusion and use_compact and not forced
                and S >= 64 and params.max_depth <= 0
                and params.plain_growth and not use_lazy
                and not params.has_categorical and L <= 256)
        # round bound: 7 budget-64 prefix rounds + <= L-1 splitting rounds
        # + one zero-split round + the sprint (round_idx increments once
        # per body)
        R_buf = L + 10 if fuse else 1

        if mesh is not None:
            # data-parallel stream path: per-device kernel + histogram psum —
            # the reference's per-worker histogram construction followed by
            # ReduceScatter (data_parallel_tree_learner.cpp:285-299)
            from jax.sharding import PartitionSpec as P
            from ..parallel.comms import reduce_hist_rows
            from ..parallel.mesh import shard_map_rows

            # packed-wire quantized histograms (hist_packed_width 16 / 8):
            # the kernel's exact int32 grad/hess pair is re-quantized per
            # round (pow2 scales, cross-device agreed) and packed into ONE
            # int32 / int16 lane at the collective seam — half / quarter
            # the wire bytes, carry-free summation by cap construction,
            # exact unpack on the far side (documented-ulp overall)
            use_packed = use_int and params.hist_packed_width < 32
            if use_packed:
                from ..parallel.comms import pack_gh_wire, unpack_gh_wire
                packed_w = params.hist_packed_width
                D_rows = mesh.shape[row_axis]

            def _rh(bT, lid_row, wT, tb, bi, num_slots, with_hist=True,
                    root=False, live_slots=None):
                def _local(bT, lid_row, wT, tb, bi, live=None):
                    nl, h, c = route_and_hist_live(
                        live, bT, lid_row, wT, tb, bi, num_slots, Bmax, G, L,
                        block_rows=T_rows, has_cat=params.has_categorical,
                        two_pass=params.hist_two_pass, int_weights=use_int,
                        with_hist=with_hist,
                        bin_buckets=params.bin_buckets, root=root,
                        tile_groups=tile_groups)
                    if with_hist:
                        if use_packed:
                            pw, pscales = pack_gh_wire(h, row_axis, packed_w,
                                                       D_rows)
                            if use_rs:
                                pw = reduce_hist(
                                    pw, row_axis, 1, plan, "f32",
                                    chunks=params.hist_comms_chunks)
                            else:
                                with jax.named_scope("hist_psum_packed"):
                                    pw = jax.lax.psum(pw, row_axis)
                            h = unpack_gh_wire(pw, pscales, packed_w)
                        else:
                            h = reduce_hist_rows(
                                h, row_axis, 1, plan,
                                params.hist_comms_dtype,
                                params.hist_comms_chunks,
                                params.hist_reduce_limbs)
                    elif use_rs:
                        # route-only rounds: slice-shaped zeros keep the
                        # sharded out_spec consistent (hist never read)
                        h = jnp.zeros(h.shape[:1] + (plan.gs,) + h.shape[2:],
                                      h.dtype)
                    # route-only psum rounds return all-zero hists on every
                    # device — already replicated, no collective needed
                    with jax.named_scope("slot_count_psum"):
                        return nl, h, jax.lax.psum(c.astype(i32), row_axis)

                hspec = (P(None, row_axis, None, None) if use_rs
                         else P(None, None, None, None))
                # the round's split count is replicated: every device takes
                # the same branch, and the psum follows as for any pass
                live = () if live_slots is None else (live_slots,)
                wrapped = shard_map_rows(
                    _local, mesh,
                    (P(None, row_axis), P(None, row_axis),
                     P(None, row_axis), P(None, None), P(None, None))
                    + (P(),) * len(live),
                    (P(None, row_axis), hspec, P(None)))
                return wrapped(bT, lid_row, wT, tb, bi, *live)
        else:
            def _rh(bT, lid_row, wT, tb, bi, num_slots, with_hist=True,
                    root=False, live_slots=None):
                return route_and_hist_live(
                    live_slots, bT, lid_row, wT, tb, bi, num_slots, Bmax, G, L,
                    block_rows=T_rows, has_cat=params.has_categorical,
                    two_pass=params.hist_two_pass, int_weights=use_int,
                    with_hist=with_hist, bin_buckets=params.bin_buckets,
                    root=root, tile_groups=tile_groups)

        zL = jnp.zeros(L, i32)
        tabs0 = build_route_tables(zL, zL, zL, zL, zL, zL, zL,
                                   zL.at[0].set(1), routing, L)
        bits0 = jnp.zeros((Bpad, L), jnp.bfloat16)
        leaf_id = jnp.zeros(n_pad, i32)
        leaf_id_c = jnp.zeros(n_pad_h if use_compact else 1, i32)
        lid0 = leaf_id_c if use_compact else leaf_id
        # every row in leaf 0, tabs0 splits nothing: route_and_hist takes
        # the factored root contraction where root_pass_kind() allows it
        _, root_hist, _ = _rh(bins_T_h, lid0.reshape(1, -1), w_T_h, tabs0,
                              bits0, 1, root=True)
        if use_int:
            root_hist = root_hist.astype(f32) * hscale
    else:
        if use_fp:
            # shard-local build: each device histograms only its G/D group
            # slice (zero collective — per-group sums are independent)
            def _build_ns(bins_x, slot_x, g_x, h_x, c_x, nslots):
                return (fp_hist_1 if nslots == 1 else fp_hist_S)(
                    bins_x, slot_x, g_x, h_x, c_x)
        else:
            def _build_ns(bins_x, slot_x, g_x, h_x, c_x, nslots):
                return build_histograms(
                    bins_x, slot_x, g_x, h_x, c_x, nslots, Bmax,
                    backend=params.hist_backend, acc_dtype=hdt)
        leaf_id = jnp.zeros(N, i32)
        leaf_id_c = jnp.zeros(1, i32)
        if use_compact:
            # contraction/segsum backends: the per-tree partition plan feeds
            # the histogram build a compact (compact_rows,) row view; the
            # per-round slot gather below is O(compact_rows), not O(N)
            from .compact import compact_row_views
            bins_c, grad_c, hess_c, cnt_c, c_perm = compact_row_views(
                bins, grad, hess, cnt_w, compact_rows)
            root_hist = _build_ns(
                bins_c, jnp.zeros(compact_rows, i32), grad_c, hess_c, cnt_c,
                1)[..., :2]
        else:
            root_hist = _build_ns(
                bins, leaf_id, grad, hess, cnt_w, 1)[..., :2]
    root_g = jnp.sum(grad, dtype=hdt)
    root_h = jnp.sum(hess, dtype=hdt)
    if int_counts:
        root_n = jnp.sum(cnt_w.astype(i32), dtype=i32)   # cnt_w: a 0/1 mask
        root_c = root_n.astype(hdt)
    else:
        root_n = root_c = jnp.sum(cnt_w, dtype=hdt)
    root_out = leaf_output(root_g, root_h, params.lambda_l1, params.lambda_l2,
                           params.max_delta_step)
    used0 = jnp.zeros((L if use_inter else 1, F if use_inter else 1), bool)
    root_mask = node_col_mask(col_mask[None, :],
                              jnp.zeros((1, F), bool),
                              jax.random.fold_in(key, 0) if key is not None else None,
                              rows=1)
    cegb_used0 = (cegb_used if cegb_used is not None
                  else jnp.zeros(F, bool)) if use_cegb else None
    root_lazy = (lazy_unused_counts(cegb_lazy, jnp.zeros(N, i32), 1)
                 if use_lazy else None)
    if use_rs or use_fp:
        root_split = (rs_split if use_rs else fp_split)(
            root_hist, root_g[None], root_h[None], root_c[None], col_mask)
    else:
        root_split = find_splits(
            root_hist, root_g[None], root_h[None], root_c[None],
            col_mask=root_mask,
            cegb_penalty=(cegb_pen(root_c[None], cegb_used0, root_lazy)
                          if use_cegb else None),
            out_lo=(-BIG[None]) if use_output else None,
            out_hi=(BIG[None]) if use_output else None,
            slot_depth=jnp.zeros(1, i32) if use_mono else None,
            parent_out=root_out[None] if use_output else None,
            extra_key=jax.random.fold_in(key, 1) if use_extra else None,
            adv_bounds=((jnp.full((1, F, Bmax), -BIG, f32),
                         jnp.full((1, F, Bmax), BIG, f32))
                        if use_amono else None))

    hist = jnp.zeros((L, 2 * Bmax, G_h), hdt).at[0].set(
        _cache_order(root_hist)[0])
    if use_fp:
        # pin the histogram STATE to the group sharding for the whole
        # while_loop: every per-round build/subtract then stays shard-local
        # (the 2D mesh pins the COMPOUND (feature, data) group spec so the
        # state matches the post-psum_scatter slice ownership)
        from jax.sharding import NamedSharding, PartitionSpec as _P
        g_spec = (feature_axis, row_axis) if use_2d else feature_axis
        hist = jax.lax.with_sharding_constraint(
            hist, NamedSharding(mesh, _P(None, None, g_spec)))
    state = _GrowState(
        leaf_id=leaf_id,
        leaf_id_c=leaf_id_c,
        split_feature=jnp.zeros(L, i32), threshold_bin=jnp.zeros(L, i32),
        dir_flags=jnp.zeros(L, i32),
        left_child=jnp.zeros(L, i32), right_child=jnp.zeros(L, i32),
        split_gain=jnp.zeros(L, f32),
        internal_value=jnp.zeros(L, f32), internal_weight=jnp.zeros(L, f32),
        internal_count=jnp.zeros(L, i32 if int_counts else f32),
        cat_bitset=jnp.zeros((L, Bmax), bool),
        sum_g=jnp.zeros(L, hdt).at[0].set(root_g),
        sum_h=jnp.zeros(L, hdt).at[0].set(root_h),
        cnt=jnp.zeros(L, cdt).at[0].set(root_n),
        depth=jnp.zeros(L, i32),
        leaf_parent=jnp.full(L, -1, i32),
        out_lo=jnp.full(L if use_output else 1, -BIG, f32),
        out_hi=jnp.full(L if use_output else 1, BIG, f32),
        leaf_out=(jnp.zeros(L, f32).at[0].set(root_out.astype(f32))
                  if use_output else jnp.zeros(1, f32)),
        anc_left=jnp.zeros((L, L) if use_imono else (1, 1), bool),
        anc_right=jnp.zeros((L, L) if use_imono else (1, 1), bool),
        node_mono=jnp.zeros(L if use_imono else 1, i32),
        node_depth=jnp.zeros(L if use_imono else 1, i32),
        rect_lo=jnp.zeros((L, F) if use_imono else (1, 1), i32),
        rect_hi=jnp.full((L, F) if use_imono else (1, 1), 2 ** 30, i32),
        leaf_in_mono=jnp.zeros(L if use_imono else 1, bool),
        adv_vmin=jnp.full((L, F, Bmax) if use_amono else (1, 1, 1), -BIG, f32),
        adv_vmax=jnp.full((L, F, Bmax) if use_amono else (1, 1, 1), BIG, f32),
        adv_split_ok=(jnp.ones((L, F), bool).at[0].set(root_split.feat_ok[0])
                      if use_amono else jnp.ones((1, 1), bool)),
        used_feat=used0,
        cegb_used=(cegb_used0 if use_cegb else jnp.zeros(1, bool)),
        cegb_lazy=(cegb_lazy if use_lazy else jnp.zeros((1, 1), bool)),
        round_idx=jnp.asarray(0, i32),
        hist_passes=jnp.asarray(1, i32),
        hist_small_passes=jnp.asarray(0, i32),
        scan_slots=jnp.asarray(0, i32),
        best_gain=jnp.full(L, NEG_INF, hdt).at[0].set(root_split.gain[0]),
        best_feat=jnp.zeros(L, i32).at[0].set(root_split.feature[0]),
        best_thr=jnp.zeros(L, i32).at[0].set(root_split.threshold[0]),
        best_dir=jnp.zeros(L, i32).at[0].set(root_split.dir_flags[0]),
        best_left_g=jnp.zeros(L, hdt).at[0].set(root_split.left_sum_g[0]),
        best_left_h=jnp.zeros(L, hdt).at[0].set(root_split.left_sum_h[0]),
        best_left_c=jnp.zeros(L, hdt).at[0].set(root_split.left_count[0]),
        hist=hist,
        num_leaves_cur=jnp.asarray(1, i32),
        progressed=jnp.asarray(True),
        col_mask=col_mask,
        tabs_buf=(jnp.zeros((R_buf * NUM_TAB, L), f32) if fuse
                  else jnp.zeros((1, 1), f32)),
    )

    def cond(st: _GrowState):
        return st.progressed & (st.num_leaves_cur < L)

    def make_body(S: int, forced_level=None, with_hist: bool = True):
        """Round body with a static per-round split budget S. The streaming
        kernel's MXU cost is linear in S, so early rounds (<= 2^r possible
        splits) run cheaper specialized bodies (see the unrolled prefix
        below); the reference's analog is growing leaf-by-leaf until the
        histogram pool warms up (serial_tree_learner.cpp).
        forced_level: static (leaf_ids, feats, thr_bins, default_lefts) —
        split exactly these leaves instead of the top-K by gain.
        with_hist=False builds the FINAL sprint round: a tree's last round
        never scans its children's histograms, so the route-only kernel
        skips the dominant one-hot contraction, the histogram subtraction
        and the child split scans (stream backend only)."""
      # noqa: E999 -- body below re-indented under the factory
        def body(st: _GrowState) -> _GrowState:
            cur = st.num_leaves_cur
            remaining = L - cur
            drop = jnp.asarray(2**30, i32)
            if forced_level is not None:
                # ---- forced splits (serial_tree_learner.cpp:628) ----
                f_leaves, f_feats, f_thrs, f_dl = forced_level
                nf = len(f_leaves)
                assert nf <= S
                k = jnp.asarray(nf, i32)
                pair_valid = jnp.arange(S) < nf
                pair_old = jnp.asarray(list(f_leaves) + [0] * (S - nf), i32)
                pair_new = jnp.where(pair_valid, cur + jnp.arange(S, dtype=i32), 0)
                pair_node = jnp.where(pair_valid, (cur - 1) + jnp.arange(S, dtype=i32), 0)
                node_idx = jnp.where(pair_valid, pair_node, drop)
                new_idx = jnp.where(pair_valid, pair_new, drop)
                old_idx = jnp.where(pair_valid, pair_old, drop)
                feat = jnp.asarray(list(f_feats) + [0] * (S - nf), i32)
                thr = jnp.asarray(list(f_thrs) + [0] * (S - nf), i32)
                dirf = jnp.asarray([1 if d else 0 for d in f_dl]
                                   + [0] * (S - nf), i32)
                pg, ph, pn = (st.sum_g[pair_old], st.sum_h[pair_old],
                              st.cnt[pair_old])
                pc = count_h(pn)
                # left sums from the leaf histogram at the forced threshold
                hf_f = gather_feature_histograms(
                    _hist_order(st.hist[pair_old]), layout, pg, ph)
                hsel = hf_f[jnp.arange(S), feat]             # (S, Bmax, 2)
                bin_le = (jnp.arange(Bmax)[None, :] <= thr[:, None])
                nanb = routing.nan_bin[feat]                 # (S,)
                nan_part = jnp.where(
                    (nanb >= 0)[:, None]
                    & (jnp.arange(Bmax)[None, :] == nanb[:, None])
                    & (dirf[:, None] == 1), True, False)
                take = (bin_le & ~((nanb >= 0)[:, None]
                                   & (jnp.arange(Bmax)[None, :]
                                      == nanb[:, None]))) | nan_part
                lg = jnp.sum(jnp.where(take, hsel[..., 0], 0.0), axis=1)
                lh = jnp.sum(jnp.where(take, hsel[..., 1], 0.0), axis=1)
                lc = round_int(lh * pc / jnp.maximum(ph, EPS_HESS))
                gain = jnp.zeros(S, f32)
                rg, rh, rc = pg - lg, ph - lh, pc - lc
            else:
                # ---- candidate selection: top-K splittable leaves by gain ----
                depth_ok = (params.max_depth <= 0) | (st.depth < jnp.asarray(
                    params.max_depth if params.max_depth > 0 else 2**30, i32))
                cand = jnp.where((st.best_gain > 0) & depth_ok, st.best_gain,
                                 NEG_INF)
                order = jnp.argsort(-cand)                    # (L,) desc
                k_budget = jnp.minimum(remaining, S)
                ranks = jnp.arange(L)
                sorted_gain = cand[order]
                chosen_rank = (ranks < k_budget) & (sorted_gain > 0)
                k = jnp.sum(chosen_rank, dtype=i32)

                # pair arrays over S slots (i = rank)
                pair_valid = jnp.arange(S) < k                # (S,)
                pair_old = jnp.where(pair_valid, order[:S].astype(i32), 0)
                pair_new = jnp.where(pair_valid, cur + jnp.arange(S, dtype=i32), 0)
                pair_node = jnp.where(pair_valid, (cur - 1) + jnp.arange(S, dtype=i32), 0)
                node_idx = jnp.where(pair_valid, pair_node, drop)
                new_idx = jnp.where(pair_valid, pair_new, drop)
                old_idx = jnp.where(pair_valid, pair_old, drop)

                feat = st.best_feat[pair_old]
                thr = st.best_thr[pair_old]
                dirf = st.best_dir[pair_old]
                gain = st.best_gain[pair_old]
                pg, ph, pn = (st.sum_g[pair_old], st.sum_h[pair_old],
                              st.cnt[pair_old])
                pc = count_h(pn)
                lg, lh, lc = (st.best_left_g[pair_old],
                              st.best_left_h[pair_old],
                              st.best_left_c[pair_old])
                rg, rh, rc = pg - lg, ph - lh, pc - lc

            # ---- categorical bitsets for the chosen splits ----
            if params.has_categorical:
                parent_hist = _hist_order(st.hist[pair_old])      # (S, G, Bmax, 2)
            if params.has_categorical and (use_rs or use_fp):
                # owner-shard recompute + tiny masked psum (the histogram
                # slice never leaves its device)
                bitset = (rs_bitset if use_rs else fp_bitset)(
                    parent_hist, feat, thr, dirf, pg, ph, pc)
            elif params.has_categorical:
                hf = gather_feature_histograms(parent_hist, layout, pg, ph)
                hf_feat = hf[jnp.arange(S), feat]                 # (S, Bmax, 2)
                bitset = categorical_left_bitset(
                    hf_feat, thr, dirf, layout.valid_mask[feat],
                    params.cat_smooth, params.min_data_per_group,
                    pc / jnp.maximum(ph, EPS_HESS))               # (S, Bmax)
            else:
                bitset = jnp.zeros((S, Bmax), bool)

            # ---- node array updates ----
            out = leaf_output(pg, ph, params.lambda_l1, params.lambda_l2,
                              params.max_delta_step)
            st2 = st._replace(
                split_feature=st.split_feature.at[node_idx].set(feat, mode="drop"),
                threshold_bin=st.threshold_bin.at[node_idx].set(thr, mode="drop"),
                dir_flags=st.dir_flags.at[node_idx].set(dirf, mode="drop"),
                split_gain=st.split_gain.at[node_idx].set(gain.astype(f32), mode="drop"),
                internal_value=st.internal_value.at[node_idx].set(out.astype(f32), mode="drop"),
                internal_weight=st.internal_weight.at[node_idx].set(ph.astype(f32), mode="drop"),
                internal_count=st.internal_count.at[node_idx].set(
                    pn if int_counts else pc.astype(f32), mode="drop"),
                cat_bitset=st.cat_bitset.at[node_idx].set(bitset, mode="drop"),
                left_child=st.left_child.at[node_idx].set(~pair_old, mode="drop"),
                right_child=st.right_child.at[node_idx].set(~pair_new, mode="drop"),
            )
            # link parents: the split leaf was some node's (left|right) leaf child
            parent_of_old = st.leaf_parent[pair_old]
            was_left = (st2.left_child[jnp.where(parent_of_old >= 0, parent_of_old, 0)]
                        == ~pair_old) & (parent_of_old >= 0)
            lp_idx = jnp.where(pair_valid & (parent_of_old >= 0) & was_left,
                               parent_of_old, drop)
            rp_idx = jnp.where(pair_valid & (parent_of_old >= 0) & ~was_left,
                               parent_of_old, drop)
            st2 = st2._replace(
                left_child=st2.left_child.at[lp_idx].set(pair_node, mode="drop"),
                right_child=st2.right_child.at[rp_idx].set(pair_node, mode="drop"),
                leaf_parent=(st2.leaf_parent
                             .at[old_idx].set(pair_node, mode="drop")
                             .at[new_idx].set(pair_node, mode="drop")),
            )

            # ---- route rows of chosen leaves ----
            leaf_chosen = jnp.zeros(L, bool).at[old_idx].set(pair_valid, mode="drop")
            leaf_new_id = jnp.zeros(L, i32).at[old_idx].set(pair_new, mode="drop")
            leaf_feat = jnp.zeros(L, i32).at[old_idx].set(feat, mode="drop")
            leaf_thr = jnp.zeros(L, i32).at[old_idx].set(thr, mode="drop")
            leaf_dir = jnp.zeros(L, i32).at[old_idx].set(dirf, mode="drop")
            smaller_is_left = lc <= rc
            which, took_small = None, 0

            if use_stream:
                # fused route+hist streaming kernel: one sequential pass over rows
                si1 = jnp.arange(S, dtype=i32) + 1
                sl1 = jnp.zeros(L, i32).at[old_idx].set(
                    jnp.where(smaller_is_left, si1, 0), mode="drop")
                sr1 = jnp.zeros(L, i32).at[old_idx].set(
                    jnp.where(smaller_is_left, 0, si1), mode="drop")
                bits_l = jnp.zeros((L, Bpad), jnp.bfloat16).at[old_idx].set(
                    jnp.pad(bitset, ((0, 0), (0, Bpad - Bmax))).astype(jnp.bfloat16),
                    mode="drop")
                tabs = build_route_tables(
                    leaf_chosen.astype(i32), leaf_feat, leaf_thr, leaf_dir,
                    leaf_new_id, sl1, sr1, jnp.zeros(L, i32), routing, L)
                lid_h = st.leaf_id_c if use_compact else st.leaf_id
                # live slots are 0..k-1 (si1, pair_valid): a round of one
                # or two takes the small-slot pass where there is one
                live = k if with_hist else None
                which = small_pass_index(live, bins_T_h.dtype, use_int, S)
                if which is not None:
                    took_small = (which > 0).astype(i32)
                with jax.named_scope("route_and_hist"):
                    new_leaf_row, hist_small, slot_cnt = _rh(
                        bins_T_h, lid_h.reshape(1, -1), w_T_h, tabs,
                        bits_l.T, S, with_hist=with_hist, live_slots=live)
                if use_compact and fuse:
                    # GOSS+stream fusion: stash this round's tables — the
                    # full-data route-only pass is REPLAYED in one fused
                    # launch after growth, so every-row leaf ids stay stale
                    # until then (nothing reads them mid-growth under the
                    # fusion eligibility gate)
                    st2 = st2._replace(
                        tabs_buf=jax.lax.dynamic_update_slice(
                            st.tabs_buf, tabs, (st.round_idx * NUM_TAB, 0)))
                    new_leaf_id = st.leaf_id
                    new_leaf_c = new_leaf_row.reshape(-1)
                elif use_compact:
                    # full-data ROUTE-ONLY pass (no one-hot contraction, no
                    # VMEM histogram block): every row's leaf id stays
                    # current for the score update / renew / CEGB paths
                    with jax.named_scope("route_full"):
                        nl_full, _, _ = _rh(
                            bins_T, st.leaf_id.reshape(1, -1), w_T, tabs,
                            bits_l.T, S, with_hist=False)
                    new_leaf_id = nl_full.reshape(-1)
                    new_leaf_c = new_leaf_row.reshape(-1)
                else:
                    new_leaf_id = new_leaf_row.reshape(-1)
                    new_leaf_c = st.leaf_id_c
            else:
                leaf_bits = jnp.zeros((L, Bmax), bool).at[old_idx].set(bitset,
                                                                       mode="drop")
                r_chosen = leaf_chosen[st.leaf_id]
                r_feat = leaf_feat[st.leaf_id]
                r_grp = routing.feat_group[r_feat]
                if use_fp:
                    # owner-shard column read + (N,) int32 psum: the split
                    # feature's bins column lives on one shard only
                    gb = fp_bin(bins, r_grp)
                else:
                    gb = jnp.take_along_axis(
                        bins, r_grp[:, None].astype(jnp.int32), axis=1)[:, 0]
                fb = feature_local_bin(gb, r_feat, routing)
                r_thr = leaf_thr[st.leaf_id]
                r_dir = leaf_dir[st.leaf_id]
                is_cat = (r_dir & 2) != 0
                default_left = (r_dir & 1) != 0
                is_nan = (routing.nan_bin[r_feat] >= 0) & (fb == routing.nan_bin[r_feat])
                mzb_r = (routing.mzero_bin[r_feat]
                         if routing.mzero_bin is not None
                         else jnp.full_like(r_feat, -1))
                is_miss = is_nan | ((mzb_r >= 0) & (fb == mzb_r))
                go_left_num = jnp.where(is_miss, default_left, fb <= r_thr)
                # flat gather of one bit per row avoids materialising (N, Bmax)
                go_left_cat = leaf_bits.reshape(-1)[st.leaf_id * Bmax + fb]
                go_left = jnp.where(is_cat, go_left_cat, go_left_num)
                new_leaf_id = jnp.where(r_chosen & ~go_left,
                                        leaf_new_id[st.leaf_id], st.leaf_id)
                new_leaf_c = st.leaf_id_c

            # ---- histograms for the smaller children + EXACT slot counts ----
            if not use_stream:   # stream path built these in the fused kernel
                smaller_id = jnp.where(smaller_is_left, pair_old, pair_new)
                slot_map = jnp.full(L, -1, i32).at[
                    jnp.where(pair_valid, smaller_id, drop)].set(
                        jnp.arange(S, dtype=i32), mode="drop")
                slot = slot_map[new_leaf_id]
                if use_compact:
                    # O(compact_rows) slot gather + histogram over the
                    # compact row view (the partition plan is per-tree)
                    hist3 = _build_ns(bins_c, jnp.take(slot, c_perm, axis=0),
                                      grad_c, hess_c, cnt_c, S)
                else:
                    hist3 = _build_ns(bins, slot, grad, hess, cnt_w, S)
                hist_small = hist3[..., :2]
                # any one group's bins partition the slot's rows, so group 0's
                # count channel sums to the exact per-slot data count
                slot_cnt = hist3[:, 0, :, 2].sum(axis=-1)

            # exact child counts from the routed partition (reference:
            # serial_tree_learner.cpp:798 overwrites the estimated SplitInfo
            # counts with DataPartition::leaf_count after the split)
            lc_x = jnp.where(smaller_is_left, slot_cnt, pn - slot_cnt)
            rc_x = pn - lc_x

            # ---- per-leaf stats for the children ----
            st2 = st2._replace(
                leaf_id=new_leaf_id,
                leaf_id_c=new_leaf_c,
                sum_g=st2.sum_g.at[old_idx].set(lg, mode="drop")
                              .at[new_idx].set(rg, mode="drop"),
                sum_h=st2.sum_h.at[old_idx].set(lh, mode="drop")
                              .at[new_idx].set(rh, mode="drop"),
                cnt=st2.cnt.at[old_idx].set(lc_x, mode="drop")
                          .at[new_idx].set(rc_x, mode="drop"),
                depth=st2.depth.at[new_idx].set(st.depth[pair_old] + 1, mode="drop")
                              .at[old_idx].set(st.depth[pair_old] + 1, mode="drop"),
            )

            # ---- constraint propagation (reference: BasicLeafConstraints::Update:
            # mid = (left_out + right_out)/2; increasing: left.max=mid, right.min=mid) ----
            if use_imono:
                # INTERMEDIATE method — a dense, traced replay of
                # IntermediateLeafConstraints (monotone_constraints.hpp:517):
                #   * per-leaf [min, max] entries tightened with the ACTUAL
                #     constrained child outputs (UpdateConstraintsWithOutputs),
                #     not the basic method's midpoints;
                #   * after each split, leaves in the opposite subtrees of
                #     every monotone ancestor that are CONTIGUOUS with the new
                #     leaves get their bound tightened with the new outputs
                #     (GoUpToFindLeavesToUpdate / GoDownToFindLeavesToUpdate).
                # The recursive walk becomes: a bottom-up scan over the split
                # leaf's ancestor chain carrying (a) a (feature, side) dedup
                # set (OppositeChildShouldBeUpdated) and (b) a per-leaf
                # reachability mask derived from leaf hyperrectangles in bin
                # space (ShouldKeepGoingLeftRight prunes exactly the leaves
                # whose rectangle misses the original leaf's interval on each
                # recorded ancestor feature). Splits replay serially (the
                # reference is serial; best-gain order matches its leaf-wise
                # order); heavy work (routing/histograms) stays batched.
                def _one_split(i, carry):
                    (lo_v, hi_v, lov, anc_l, anc_r, nmono, ndepth,
                     rlo, rhi, inmono, bchg_min, bchg_max, avmn,
                     avmx) = carry
                    val = pair_valid[i]
                    o = jnp.where(val, pair_old[i], L)
                    nw = jnp.where(val, pair_new[i], L)
                    nd = jnp.where(val, pair_node[i], L)
                    o_c = pair_old[i]                       # unclamped index
                    if use_amono:
                        # bounds the WINNING scan used when it chose this
                        # split: the reverse scan walks the cumulative
                        # segments per threshold, the forward scan's
                        # cumulative indices never advance so its left child
                        # reads bin 0 and its right child the whole-slab
                        # extrema (CumulativeFeatureConstraint::Update only
                        # decrements; default_left records the winner)
                        bbA = jnp.arange(Bmax)
                        vmn = st.adv_vmin[o_c, feat[i]]
                        vmx = st.adv_vmax[o_c, feat[i]]
                        left_m = bbA <= thr[i]
                        was_rev = (dirf[i] & 1) != 0        # DIR_DEFAULT_LEFT
                        a_lo_l = jnp.where(
                            was_rev, jnp.max(jnp.where(left_m, vmn, -BIG)),
                            vmn[0])
                        a_hi_l = jnp.where(
                            was_rev, jnp.min(jnp.where(left_m, vmx, BIG)),
                            vmx[0])
                        a_lo_r = jnp.where(
                            was_rev, jnp.max(jnp.where(~left_m, vmn, -BIG)),
                            jnp.max(vmn))
                        a_hi_r = jnp.where(
                            was_rev, jnp.min(jnp.where(~left_m, vmx, BIG)),
                            jnp.min(vmx))
                        cat_sp = (dirf[i] & 2) != 0
                        a_lo_l = jnp.where(cat_sp, -BIG, a_lo_l)
                        a_hi_l = jnp.where(cat_sp, BIG, a_hi_l)
                        a_lo_r = jnp.where(cat_sp, -BIG, a_lo_r)
                        a_hi_r = jnp.where(cat_sp, BIG, a_hi_r)
                        ol_i, _ = constrained_child_outputs(
                            lg[i], lh[i], lc[i], rg[i], rh[i], rc[i],
                            params.lambda_l1, params.lambda_l2,
                            a_lo_l, a_hi_l, params.path_smooth, lov[o_c],
                            params.max_delta_step)
                        _, or_i = constrained_child_outputs(
                            lg[i], lh[i], lc[i], rg[i], rh[i], rc[i],
                            params.lambda_l1, params.lambda_l2,
                            a_lo_r, a_hi_r, params.path_smooth, lov[o_c],
                            params.max_delta_step)
                    else:
                        ol_i, or_i = constrained_child_outputs(
                            lg[i], lh[i], lc[i], rg[i], rh[i], rc[i],
                            params.lambda_l1, params.lambda_l2,
                            lo_v[o_c], hi_v[o_c],
                            params.path_smooth, lov[o_c],
                            params.max_delta_step)
                    lov = lov.at[o].set(ol_i.astype(f32), mode="drop") \
                             .at[nw].set(or_i.astype(f32), mode="drop")
                    anc_o_l = anc_l[o_c]                    # PROPER ancestors
                    anc_o_r = anc_r[o_c]                    # of the new node
                    is_num = (dirf[i] & 2) == 0
                    m_split = jnp.where(is_num, monotone[feat[i]], 0)
                    flag = (m_split != 0) | inmono[o_c]     # BeforeSplit
                    depth_o = st.depth[o_c]
                    sf, stb = feat[i], thr[i]

                    # ---- children entries (UpdateConstraintsWithOutputs):
                    # right clones left's entry, then monotone tightening with
                    # the actual outputs (gated on leaf_is_in_monotone_subtree)
                    lo_o, hi_o = lo_v[o_c], hi_v[o_c]
                    g_num = flag & is_num
                    new_hi_o = jnp.where(g_num & (m_split > 0),
                                         jnp.minimum(hi_o, or_i), hi_o)
                    new_lo_o = jnp.where(g_num & (m_split < 0),
                                         jnp.maximum(lo_o, or_i), lo_o)
                    new_lo_nw = jnp.where(g_num & (m_split > 0),
                                          jnp.maximum(lo_o, ol_i), lo_o)
                    new_hi_nw = jnp.where(g_num & (m_split < 0),
                                          jnp.minimum(hi_o, ol_i), hi_o)
                    lo_v = lo_v.at[o].set(new_lo_o.astype(f32), mode="drop") \
                               .at[nw].set(new_lo_nw.astype(f32), mode="drop")
                    hi_v = hi_v.at[o].set(new_hi_o.astype(f32), mode="drop") \
                               .at[nw].set(new_hi_nw.astype(f32), mode="drop")

                    # ---- contiguity walk up the ancestor chain ----
                    use_l_P = (rlo[:, sf] <= stb) | ~is_num      # (L,) leaves
                    use_r_P = (rhi[:, sf] > stb + 1) | ~is_num
                    vmax = jnp.where(use_l_P & use_r_P,
                                     jnp.maximum(ol_i, or_i),
                                     jnp.where(use_l_P, ol_i, or_i)).astype(f32)
                    vmin = jnp.where(use_l_P & use_r_P,
                                     jnp.minimum(ol_i, or_i),
                                     jnp.where(use_l_P, ol_i, or_i)).astype(f32)
                    splittable = st.best_gain > NEG_INF / 2

                    def _walk(j, wc):
                        (lo_w, hi_w, bad, seen, chgmin, chgmax,
                         avmn_w, avmx_w) = wc
                        d = depth_o - 1 - j
                        one = anc_o_l | anc_o_r
                        at_d = one & (ndepth == d) & \
                            (jnp.arange(L) < (cur - 1) + i + 1)
                        has_A = jnp.any(at_d) & (d >= 0)
                        Aidx = jnp.argmax(at_d)
                        Af = st.split_feature[Aidx]
                        At = st.threshold_bin[Aidx]
                        Anum = (st.dir_flags[Aidx] & 2) == 0
                        side_r = anc_o_r[Aidx]              # o right of A
                        Amono = nmono[Aidx]
                        recorded = has_A & Anum & ~seen[Af, side_r.astype(i32)]
                        doup = recorded & (Amono != 0) & flag & val
                        opp = jnp.where(side_r, anc_l[:, Aidx], anc_r[:, Aidx])
                        target = doup & opp & splittable & ~bad & \
                            (use_l_P | use_r_P)
                        # (monotone<0 ? o-left : o-right) updates opposite MAX
                        upd_max = jnp.where(Amono < 0, ~side_r, side_r)
                        hi_n = jnp.where(target & upd_max,
                                         jnp.minimum(hi_w, vmin), hi_w)
                        lo_n = jnp.where(target & ~upd_max,
                                         jnp.maximum(lo_w, vmax), lo_w)
                        # leaves whose entry actually tightened need their
                        # best split re-found (leaves_to_update_; Update*
                        # AndReturnBoolIfChanged semantics). The advanced
                        # entry applies the value as a whole-slab clamp AND
                        # always reports changed ("could have been
                        # unconstrained"), flagging a fresh lazy rebuild of
                        # the touched SIDE (AdvancedFeatureConstraints::
                        # UpdateMin/UpdateMax with trigger_a_recompute)
                        if use_amono:
                            t_min = target & ~upd_max
                            t_max = target & upd_max
                            chgmin = chgmin | t_min
                            chgmax = chgmax | t_max
                            avmn_w = jnp.where(
                                t_min[:, None, None],
                                jnp.maximum(avmn_w, vmax[:, None, None]),
                                avmn_w)
                            avmx_w = jnp.where(
                                t_max[:, None, None],
                                jnp.minimum(avmx_w, vmin[:, None, None]),
                                avmx_w)
                        else:
                            chgmin = chgmin | (hi_n < hi_w) | (lo_n > lo_w)
                        hi_w, lo_w = hi_n, lo_n
                        # extend the reachability prune with A's plane
                        okP = jnp.where(side_r, rhi[:, Af] > At + 1,
                                        rlo[:, Af] <= At)
                        bad = bad | (recorded & ~okP)
                        seen = seen.at[Af, side_r.astype(i32)].set(
                            seen[Af, side_r.astype(i32)] | recorded)
                        return (lo_w, hi_w, bad, seen, chgmin, chgmax,
                                avmn_w, avmx_w)

                    (lo_v, hi_v, _, _, bchg_min, bchg_max, avmn,
                     avmx) = jax.lax.fori_loop(
                        0, jnp.maximum(depth_o, 0), _walk,
                        (lo_v, hi_v, jnp.zeros(L, bool),
                         jnp.zeros((F, 2), bool), bchg_min, bchg_max,
                         avmn, avmx))

                    # ---- bookkeeping: ancestry, rectangles, node info ----
                    anc_l = anc_l.at[nw].set(anc_o_l, mode="drop")
                    anc_r = anc_r.at[nw].set(anc_o_r, mode="drop")
                    anc_l = anc_l.at[o, nd].set(True, mode="drop")
                    anc_r = anc_r.at[nw, nd].set(True, mode="drop")
                    nmono = nmono.at[nd].set(m_split, mode="drop")
                    ndepth = ndepth.at[nd].set(depth_o, mode="drop")
                    rlo = rlo.at[nw].set(rlo[o_c], mode="drop")
                    rhi = rhi.at[nw].set(rhi[o_c], mode="drop")
                    rhi = rhi.at[o, sf].set(
                        jnp.where(is_num, jnp.minimum(rhi[o_c, sf], stb + 1),
                                  rhi[o_c, sf]), mode="drop")
                    rlo = rlo.at[nw, sf].set(
                        jnp.where(is_num, jnp.maximum(rlo[o_c, sf], stb + 1),
                                  rlo[o_c, sf]), mode="drop")
                    inmono = inmono.at[o].set(flag, mode="drop") \
                                   .at[nw].set(flag, mode="drop")
                    if use_amono:
                        # AdvancedConstraintEntry semantics: the right child
                        # CLONES the left's piecewise slabs, then both get the
                        # split's scalar clamp across all (feature, bin)
                        # (UpdateConstraintsWithOutputs with lazy=false);
                        # walk-touched leaves are only FLAGGED — their slabs
                        # rebuild fresh at the next scan (lazy recompute)
                        avmn = avmn.at[nw].set(avmn[o_c], mode="drop")
                        avmx = avmx.at[nw].set(avmx[o_c], mode="drop")
                        up_hi_o = g_num & (m_split > 0)
                        up_lo_o = g_num & (m_split < 0)
                        # (outputs are f64 under hist_precision=double;
                        # the f32 slabs take them by an explicit cast)
                        avmx = avmx.at[o].set(
                            jnp.where(up_hi_o, jnp.minimum(avmx[o_c], or_i),
                                      avmx[o_c]).astype(f32), mode="drop")
                        avmn = avmn.at[o].set(
                            jnp.where(up_lo_o, jnp.maximum(avmn[o_c], or_i),
                                      avmn[o_c]).astype(f32), mode="drop")
                        avmn = avmn.at[nw].set(
                            jnp.where(up_hi_o, jnp.maximum(avmn[nw], ol_i),
                                      avmn[nw]).astype(f32), mode="drop")
                        avmx = avmx.at[nw].set(
                            jnp.where(up_lo_o, jnp.minimum(avmx[nw], ol_i),
                                      avmx[nw]).astype(f32), mode="drop")
                    return (lo_v, hi_v, lov, anc_l, anc_r, nmono, ndepth,
                            rlo, rhi, inmono, bchg_min, bchg_max, avmn, avmx)

                carry = jax.lax.fori_loop(
                    0, S, _one_split,
                    (st.out_lo, st.out_hi, st2.leaf_out,
                     st2.anc_left, st2.anc_right, st2.node_mono,
                     st2.node_depth, st2.rect_lo, st2.rect_hi,
                     st2.leaf_in_mono, jnp.zeros(L, bool),
                     jnp.zeros(L, bool), st.adv_vmin, st.adv_vmax))
                st2 = st2._replace(out_lo=carry[0], out_hi=carry[1],
                                   leaf_out=carry[2], anc_left=carry[3],
                                   anc_right=carry[4], node_mono=carry[5],
                                   node_depth=carry[6], rect_lo=carry[7],
                                   rect_hi=carry[8], leaf_in_mono=carry[9])
                imono_changed = carry[10] | carry[11]
                if use_amono:
                    # fresh slabs ONLY for walk-flagged leaves — and only the
                    # flagged SIDE, min taking precedence (the lazy
                    # RecomputeConstraintsIfNeeded rebuilds ONE
                    # FeatureMinOrMaxConstraints then clears both flags);
                    # everyone else keeps the inherited/clamped slabs
                    v_mn, v_mx = advanced_constraint_slabs(
                        st2.anc_left, st2.anc_right, st2.node_mono,
                        st2.node_depth, st2.split_feature, st2.threshold_bin,
                        (st2.dir_flags & 2) == 0, st2.rect_lo, st2.rect_hi,
                        st2.leaf_out, Bmax, BIG)
                    fm_min = carry[10][:, None, None]
                    fm_max = (carry[11] & ~carry[10])[:, None, None]
                    st2 = st2._replace(
                        adv_vmin=jnp.where(fm_min, v_mn, carry[12]),
                        adv_vmax=jnp.where(fm_max, v_mx, carry[13]))
            elif use_output:
                lo_p = st.out_lo[pair_old]
                hi_p = st.out_hi[pair_old]
                po = st.leaf_out[pair_old]
                ol, orr = constrained_child_outputs(
                    lg, lh, lc, rg, rh, rc, params.lambda_l1, params.lambda_l2,
                    lo_p, hi_p, params.path_smooth, po,
                    params.max_delta_step)
                mid = (ol + orr) / 2.0
                if use_mono:
                    mt = monotone[feat]
                    mt = jnp.where((dirf & 2) != 0, 0, mt)   # cat splits unconstrained
                else:
                    mt = jnp.zeros(S, i32)
                l_hi = jnp.where(mt > 0, jnp.minimum(hi_p, mid), hi_p)
                l_lo = jnp.where(mt < 0, jnp.maximum(lo_p, mid), lo_p)
                r_lo = jnp.where(mt > 0, jnp.maximum(lo_p, mid), lo_p)
                r_hi = jnp.where(mt < 0, jnp.minimum(hi_p, mid), hi_p)
                st2 = st2._replace(
                    out_lo=st2.out_lo.at[old_idx].set(l_lo.astype(f32), mode="drop")
                                     .at[new_idx].set(r_lo.astype(f32), mode="drop"),
                    out_hi=st2.out_hi.at[old_idx].set(l_hi.astype(f32), mode="drop")
                                     .at[new_idx].set(r_hi.astype(f32), mode="drop"),
                    leaf_out=st2.leaf_out.at[old_idx].set(ol.astype(f32), mode="drop")
                                         .at[new_idx].set(orr.astype(f32), mode="drop"))
            if use_inter:
                fe_oh = jax.nn.one_hot(feat, F, dtype=jnp.int32).astype(bool)
                new_used = st.used_feat[pair_old] | fe_oh       # (S, F)
                st2 = st2._replace(
                    used_feat=st2.used_feat.at[old_idx].set(new_used, mode="drop")
                                           .at[new_idx].set(new_used, mode="drop"))
            if use_cegb:
                f_m = jnp.where(pair_valid, feat, F + 1)
                st2 = st2._replace(cegb_used=st2.cegb_used.at[f_m].set(
                    True, mode="drop"))
            if use_lazy:
                # charge the split leaves' rows for their split feature
                # (UpdateLeafBestSplits -> InsertBitset, cegb hpp:126)
                lz_chosen = jnp.zeros(L, bool).at[old_idx].set(
                    pair_valid, mode="drop")
                lz_feat = jnp.zeros(L, i32).at[old_idx].set(feat, mode="drop")
                rch = lz_chosen[st.leaf_id]
                rft = lz_feat[st.leaf_id]
                mark = (jnp.arange(F, dtype=i32)[None, :] == rft[:, None]) \
                    & rch[:, None]
                st2 = st2._replace(cegb_lazy=st2.cegb_lazy | mark)

            if not with_hist:
                # sprint round: the tree is complete after these splits —
                # children's histograms/scans would never be read
                return st2._replace(num_leaves_cur=cur + k,
                                    progressed=k > 0,
                                    round_idx=st.round_idx + 1)

            if use_amono:
                # fresh children inherit the parent's sticky is_splittable_
                # flags (FindBestSplits propagates parent-unsplittable to
                # both children without scanning, serial_tree_learner.cpp:399)
                st2 = st2._replace(adv_split_ok=st2.adv_split_ok.at[
                    new_idx].set(st2.adv_split_ok[pair_old], mode="drop"))

            # the part of the round that follows the kernel call runs a
            # chunk of C pairs a step, as many steps as hold the round's k
            # live pairs (they are pairs 0..k-1), where the round knows its
            # count and no draw depends on the width (by-node sampling and
            # extra_trees draw a row a pair); the whole budget at once
            # elsewhere (C = 0)
            adapts = (which is not None and forced_level is None
                      and not params.has_categorical
                      and not (use_imono or use_bynode or use_extra))
            C = tail_chunk(S) if adapts else 0

            def tail(lo, cache, best):
                """Pairs lo .. lo + C - 1 of the round (all S where C is 0):
                the larger siblings' histograms by subtraction, both
                children's into the cache, and the children's best splits
                into the records `best`.  No pair reads another's, so the
                chunks may run one after the other and the pairs past k not
                at all."""
                def cut(a):
                    return jax.lax.dynamic_slice_in_dim(a, lo, C) if C else a
                p_old, p_new, p_valid = (cut(pair_old), cut(pair_new),
                                         cut(pair_valid))
                small_left = cut(smaller_is_left)
                small = cut(hist_small)
                if use_stream and use_int:
                    small = small.astype(f32) * hscale
                    if adapts and S >= 2 * TAIL_CHUNK:
                        # the slots past k hold zeros already: the select
                        # keeps a CPU from contracting the scale into the
                        # subtraction below, which it does in one fusion and
                        # not in another (cut or uncut, 8 pairs or 64; the
                        # chip rounds twice wherever the two land).  Smaller
                        # budgets keep the expression their trees grew by
                        small = jnp.where(p_valid[:, None, None, None],
                                          small, 0.0)
                # ---- histogram subtraction for the larger siblings ----
                parent = (cut(parent_hist) if params.has_categorical
                          else _hist_order(cache[p_old]))     # (C, G, Bmax, 2)
                large = parent - small
                sm_idx = jnp.where(p_valid,
                                   jnp.where(small_left, p_old, p_new), drop)
                lg_idx = jnp.where(p_valid,
                                   jnp.where(small_left, p_new, p_old), drop)
                cache = (cache.at[sm_idx].set(_cache_order(small),
                                              mode="drop")
                              .at[lg_idx].set(_cache_order(large),
                                              mode="drop"))

                # ---- best splits for the 2C children ----
                # Under intermediate monotone constraints, other leaves'
                # entries may have tightened, which invalidates their cached
                # best splits; the reference re-finds splits for every leaf
                # in leaves_need_update (serial_tree_learner.cpp Split ->
                # RecomputeBestSplitForLeaf). Recomputing ALL leaves is
                # equivalent (unchanged bounds reproduce the cached result)
                # and stays one dense scan.
                if use_imono:
                    # children always recompute; other leaves only when
                    # their entry actually tightened (leaves_need_update).
                    # Unchanged leaves keep their cached best split — also
                    # keeps by-node / extra_trees draws stable for them (the
                    # reference's RecomputeBestSplitForLeaf redraws GetByNode
                    # only for recomputed leaves,
                    # serial_tree_learner.cpp:1053)
                    ids2 = jnp.arange(L)
                    child2 = jnp.zeros(L, bool) \
                        .at[old_idx].set(pair_valid, mode="drop") \
                        .at[new_idx].set(pair_valid, mode="drop")
                    valid2 = child2 | imono_changed
                    hist2 = _hist_order(cache)
                else:
                    ids2 = jnp.concatenate([p_old, p_new])
                    valid2 = jnp.concatenate([p_valid, p_valid])
                    # what the cache would read back at ids2: the split leaf
                    # keeps the left child, the new leaf takes the right one
                    sl4 = small_left[:, None, None, None]
                    hist2 = jnp.concatenate([jnp.where(sl4, small, large),
                                             jnp.where(sl4, large, small)])
                rkey = (jax.random.fold_in(key, 2 + st.round_idx)
                        if key is not None else None)
                rows2 = ids2.shape[0]
                cmask2 = node_col_mask(st.col_mask[None, :],
                                       st2.used_feat[ids2] if use_inter
                                       else jnp.zeros((rows2, F), bool),
                                       rkey, rows=rows2)
                with jax.named_scope("find_splits"):
                    if use_rs or use_fp:
                        # shard-local scan on each device's group slice +
                        # tiny best-record all_gather (bit-identical to the
                        # full scan)
                        res = (rs_split if use_rs else fp_split)(
                            hist2, st2.sum_g[ids2], st2.sum_h[ids2],
                            count_h(st2.cnt[ids2]), st.col_mask)
                    else:
                        res = find_splits(
                            hist2, st2.sum_g[ids2], st2.sum_h[ids2],
                            count_h(st2.cnt[ids2]),
                            col_mask=cmask2,
                            adv_bounds=((st2.adv_vmin[ids2],
                                         st2.adv_vmax[ids2])
                                        if use_amono else None),
                            splittable=(st2.adv_split_ok[ids2]
                                        if use_amono else None),
                            out_lo=st2.out_lo[ids2] if use_output else None,
                            out_hi=st2.out_hi[ids2] if use_output else None,
                            slot_depth=st2.depth[ids2] if use_mono else None,
                            parent_out=(st2.leaf_out[ids2] if use_output
                                        else None),
                            extra_key=(jax.random.fold_in(
                                key, 100000 + st.round_idx)
                                       if use_extra else None),
                            cegb_penalty=(cegb_pen(
                                count_h(st2.cnt[ids2]), st2.cegb_used,
                                lazy_unused_counts(
                                    st2.cegb_lazy,
                                    jnp.full(L, -1, i32).at[
                                        jnp.where(valid2, ids2, drop)].set(
                                        jnp.arange(rows2, dtype=i32),
                                        mode="drop")[st2.leaf_id],
                                    rows2) if use_lazy else None)
                                          if use_cegb else None))
                ids2_m = jnp.where(valid2, ids2, drop)
                best = tuple(
                    b.at[ids2_m].set(r, mode="drop") for b, r in zip(
                        best, (res.gain, res.feature, res.threshold,
                               res.dir_flags, res.left_sum_g, res.left_sum_h,
                               res.left_count)))
                # flags refresh only for leaves that actually rescanned
                # (each FindBestThreshold call rewrites is_splittable_,
                # feature_histogram.hpp:196; skipped leaves keep theirs)
                split_ok = (jnp.where(valid2[:, None], res.feat_ok,
                                      st2.adv_split_ok)
                            if use_amono else st2.adv_split_ok)
                return cache, best, split_ok

            best = (st2.best_gain, st2.best_feat, st2.best_thr, st2.best_dir,
                    st2.best_left_g, st2.best_left_h, st2.best_left_c)
            if C:
                steps = (k + (C - 1)) // C
                cache, best = jax.lax.fori_loop(
                    0, steps, lambda i, cb: tail(i * C, *cb)[:2],
                    (st2.hist, best))
                split_ok, scanned = st2.adv_split_ok, steps * C
            else:
                cache, best, split_ok = tail(0, st2.hist, best)
                scanned = S
            return st2._replace(
                hist=cache, best_gain=best[0], best_feat=best[1],
                best_thr=best[2], best_dir=best[3], best_left_g=best[4],
                best_left_h=best[5], best_left_c=best[6],
                adv_split_ok=split_ok,
                num_leaves_cur=cur + k, progressed=k > 0,
                round_idx=st.round_idx + 1, hist_passes=st.hist_passes + 1,
                hist_small_passes=st.hist_small_passes + took_small,
                scan_slots=st.scan_slots + scanned)

        return body

    # forced splits run first, one statically-unrolled round per level
    # (reference: serial_tree_learner.cpp:628 ForceSplits)
    if forced:
        for level in forced:
            state = make_body(max(len(level[0]), 1), forced_level=level)(state)

    # streaming rounds: round r can split at most 2^r leaves, and the
    # fused kernel cost is linear in the slot budget S — run the first
    # log2(S) rounds as specialized small-S bodies, then loop at full S
    if use_stream and S > 64:
        # the 64-slot KERNEL's MXU cost is quantized to 128-column tiles of
        # the (T, 2S) operand, so a kernel budget under 64 buys nothing —
        # what a round of fewer splits saves it saves inside the 64-budget
        # body, by its own split count: the small-slot pass
        # (route_and_hist_live) and a tail of as many chunks as hold its
        # pairs (tail_chunk).
        # Round r can split at most 2^r leaves, so 7 budget-64 rounds cover
        # growth to 128 leaves before the full-S while_loop takes over.
        b64 = make_body(64)
        for _ in range(7):
            state = jax.lax.cond(cond(state), b64, lambda s: s, state)

    # FINAL-SPRINT schedule (stream only): a tree's last round never reads
    # its children's histograms, so once ONE route-only round can finish the
    # remaining splits, exit the hist loop and sprint.  At the bench shapes
    # (255 leaves, budget 64) this turns the 1+9-pass schedule into 1+7 full
    # passes + a nearly-free route pass — the minimum, since leaves at most
    # double per round.  The sprint batches up to 2S splits, the same
    # batched-growth deviation from strict best-first the budget already
    # accepts (quality gates in bench.py verify AUC/NDCG).
    sprint = (use_stream and S >= 64 and not forced
              and params.max_depth <= 0)
    if sprint:
        S_f = min(2 * S, 255, max(L - 1, 1))

        def cond_sprint(st: _GrowState):
            remaining = L - st.num_leaves_cur
            # a single sprint round can split at most one per current leaf,
            # and only leaves with a positive cached gain
            splittable = jnp.sum((st.best_gain > 0).astype(i32))
            can_finish = (remaining <= S_f) & (remaining <= splittable)
            return st.progressed & (remaining > 0) & ~can_finish

        state = jax.lax.while_loop(cond_sprint, make_body(S), state)
        final = jax.lax.cond(
            cond(state), make_body(S_f, with_hist=False), lambda s: s, state)
    else:
        final = jax.lax.while_loop(cond, make_body(S), state)

    if fuse:
        # ---- fused full-data route REPLAY (GOSS+stream fusion) ----
        # one launch re-routes EVERY row through the stored round tables:
        # bins stream from HBM once per tree instead of once per route-only
        # round, and the replay trip count is the tree's actual round count
        # (unused buffer rows are exact no-op steps and never execute)
        with jax.named_scope("route_replay"):
            if mesh is not None:
                from jax.sharding import PartitionSpec as P
                from ..parallel.mesh import shard_map_rows
                _rep = shard_map_rows(
                    lambda bT, tb, nr: route_replay(
                        bT, tb, nr, L, block_rows=T_rows,
                        rounds_buf=R_buf)[None],
                    mesh,
                    (P(None, row_axis), P(None, None), P()),
                    P(None, row_axis))
                replayed = _rep(bins_T, final.tabs_buf,
                                final.round_idx)[0]
            else:
                replayed = route_replay(bins_T, final.tabs_buf,
                                        final.round_idx, L,
                                        block_rows=T_rows, rounds_buf=R_buf)
        final = final._replace(leaf_id=replayed)

    if use_output:
        # constrained/smoothed outputs were fixed at split time (reference:
        # SerialTreeLearner::Split computes them with the leaf's bounds)
        leaf_value = final.leaf_out
        if params.max_delta_step > 0.0:
            leaf_value = jnp.clip(leaf_value, -params.max_delta_step,
                                  params.max_delta_step)
    else:
        leaf_value = leaf_output(final.sum_g, final.sum_h, params.lambda_l1,
                                 params.lambda_l2, params.max_delta_step)
    # single-leaf tree edge case: value 0 (no boost)
    leaf_value = jnp.where(final.num_leaves_cur > 1, leaf_value, 0.0)
    # f32 outputs regardless of the histogram dtype: downstream score updates
    # and model finalization run outside any enable_x64 scope
    tree = TreeArrays(
        split_feature=final.split_feature, threshold_bin=final.threshold_bin,
        dir_flags=final.dir_flags, left_child=final.left_child,
        right_child=final.right_child, split_gain=final.split_gain,
        internal_value=final.internal_value, internal_weight=final.internal_weight,
        internal_count=final.internal_count, cat_bitset=final.cat_bitset,
        leaf_value=leaf_value.astype(f32), leaf_weight=final.sum_h.astype(f32),
        leaf_count=final.cnt if int_counts else final.cnt.astype(f32),
        leaf_parent=final.leaf_parent, num_leaves=final.num_leaves_cur,
        leaf_depth=final.depth,
    )
    out = (tree, final.leaf_id[:N])
    if use_lazy:
        out += (final.cegb_lazy,)
    if with_passes:
        out += (jnp.stack([final.hist_passes, final.hist_small_passes,
                           final.scan_slots]),)
    return out


class _GrowStateK(NamedTuple):
    """Channelized grow state — every per-class array gains a leading K
    axis; the round body updates all K class trees in lockstep."""
    leaf_id: jax.Array          # (K, N_pad) i32
    leaf_id_c: jax.Array        # (K, compact_rows) i32 ((1, 1) dummy when
                                # row compaction is off)
    split_feature: jax.Array    # (K, L) i32 — node arrays
    threshold_bin: jax.Array
    dir_flags: jax.Array
    left_child: jax.Array
    right_child: jax.Array
    split_gain: jax.Array       # (K, L) f32
    internal_value: jax.Array
    internal_weight: jax.Array
    internal_count: jax.Array
    cat_bitset: jax.Array       # (K, L, Bmax) bool
    sum_g: jax.Array            # (K, L) hdt — per-leaf stats
    sum_h: jax.Array
    cnt: jax.Array
    depth: jax.Array            # (K, L) i32
    leaf_parent: jax.Array
    best_gain: jax.Array        # (K, L) hdt — cached best splits
    best_feat: jax.Array
    best_thr: jax.Array
    best_dir: jax.Array
    best_left_g: jax.Array
    best_left_h: jax.Array
    best_left_c: jax.Array
    hist: jax.Array             # (K, L, G, Bmax, 2)
    num_leaves_cur: jax.Array   # (K,) i32
    progressed: jax.Array       # (K,) bool
    hist_passes: jax.Array      # () i32 — as _GrowState.hist_passes: one
                                # lockstep pass serves all K classes
    scan_slots: jax.Array       # () i32 — as _GrowState.scan_slots: every
                                # class's pairs, each round at its budget


def grow_tree_k(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                cnt_w: jax.Array, col_mask: jax.Array,
                layout: FeatureLayout, routing: RoutingLayout,
                params: GrowParams,
                packed=None, gh_scales: Optional[jax.Array] = None,
                mesh=None, row_axis: Optional[str] = None,
                feature_axis: Optional[str] = None,
                compact_rows: int = 0,
                with_passes: bool = False,
                ) -> Tuple[TreeArrays, jax.Array]:
    """Grow K class trees in LOCKSTEP inside one widened XLA program
    (batched multiclass). Returns (TreeArrays with a leading K axis,
    leaf_id (K, N)) — the same stacked layout the per-class lax.scan path
    produces; with_passes=True appends the (3,) i32 counts of
    histogram-building passes, as grow_tree does (no lockstep pass is a
    small-slot one, and every round's tail is as wide as its budget).

    grad/hess: (K, N) class-major gradient channels (bagging mask applied).
    gh_scales: (K, 2) per-class (grad_scale, hess_scale) or None.

    The dominant per-round cost — the class-independent one-hot bin
    construct and its MXU contraction — is built ONCE and contracted
    against the stacked class x slot channel axis: the stream backend runs
    ONE route_and_hist kernel over (K, N) leaf ids with a (m_rows, 2*S*K)
    histogram block (the reference's one-histogram-pass-serves-all-classes
    layout, cuda_histogram_constructor.cu), the segsum/onehot backends go
    through build_histograms_k. Everything per-class (candidate selection,
    split scans, node bookkeeping) is computed batched over the K axis with
    the SAME per-class arithmetic as grow_tree, and classes whose per-class
    loop would have exited are frozen to exact no-ops — so the trees are
    bit-identical to the per-class scan path (exact on the segsum backend
    and on the MXU kernel paths, where each output column's contraction is
    independent of the operand's column count; CPU-interpret/onehot blocked
    contractions can differ in final-ulp accumulation order).

    Only the plain feature set is supported (no monotone/interaction/CEGB/
    forced splits/path smoothing/extra_trees/bynode sampling); the caller
    falls back to the per-class scan otherwise.

    mesh + row_axis + feature_axis: the 2D (rows x feature-groups) mesh —
    the widened (K, S, G, Bmax, 3) block builds shard-locally over the
    feature axis, psum_scatters over the row axis, and the K*2S-slot scan
    runs on each device's G/(D_rows*D_feat) slice (docs/DISTRIBUTED.md
    "2D mesh"); feature_axis without row_axis is not supported here.
    """
    if (params.has_monotone or params.has_interaction or params.has_cegb
            or params.extra_trees or params.bynode_fraction < 1.0
            or params.path_smooth > 0.0):
        raise ValueError("grow_tree_k supports the plain feature set only; "
                         "use the per-class grow_tree scan path")
    K, N = grad.shape
    G = bins.shape[1]
    L = params.num_leaves
    S = min(params.max_splits_per_round, max(L - 1, 1))
    Bmax = layout.valid_mask.shape[1]
    F = layout.gather_idx.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    hdt = jnp.float64 if params.hist_double else jnp.float32
    kI = jnp.arange(K)

    find_splits = functools.partial(
        find_best_splits,
        layout=layout,
        lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
        min_data_in_leaf=max(params.min_data_in_leaf, 1),
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        cat_l2=params.cat_l2, cat_smooth=params.cat_smooth,
        max_cat_threshold=params.max_cat_threshold,
        max_cat_to_onehot=params.max_cat_to_onehot,
        min_data_per_group=params.min_data_per_group,
        enable_categorical=params.has_categorical,
        max_delta_step=params.max_delta_step,
    )

    def ta(a, idx):
        return jnp.take_along_axis(a, idx, axis=1)

    # ---- root ----
    use_stream = params.hist_backend == "stream"
    use_compact = compact_rows > 0
    check_hist_backend(params.hist_backend,
                       mesh=mesh_kind(mesh, row_axis, feature_axis),
                       double=params.hist_double, compact=use_compact)
    Bpad = -(-Bmax // 8) * 8
    # reduce_scatter comms for the widened K-class block: identical design
    # to grow_tree's (see there), scattering over the group axis of the
    # (K, S, G, Bmax, 2) block and scanning K*2S slots shard-locally
    use_rs = (mesh is not None and use_stream
              and params.hist_comms == "reduce_scatter")
    use_fp = mesh is not None and feature_axis is not None
    if use_fp and row_axis is None:
        raise ValueError(
            "grow_tree_k shards the feature axis only as part of the 2D "
            "data x feature mesh with a contraction/segsum backend; use "
            "the per-class grow_tree scan for tree_learner=feature")
    G_h = G
    plan = None
    if use_rs:
        from ..parallel.comms import make_rs_context
        plan, rs_split, rs_bitset = make_rs_context(
            mesh, row_axis, layout, routing, G, Bmax, params)
        G_h = plan.g_pad
    if use_fp:
        # 2D mesh: same ShardPlan machinery as grow_tree's, keyed by the
        # compound (feature, data) axis; the K-class build is the widened
        # variant of make_sharded_hist_2d
        from ..parallel.comms import (make_rs_context, make_sharded_hist_2d,
                                      make_sharded_bin_gather_2d)
        fp_plan, fp_split, fp_bitset = make_rs_context(
            mesh, (feature_axis, row_axis), layout, routing, G, Bmax,
            params)
        if fp_plan.g_pad != G:
            raise ValueError(
                f"2D-mesh bins must arrive group-padded to a multiple of "
                f"the mesh shard count (got {G} groups, need "
                f"{fp_plan.g_pad}); the engine pads at construction")
        d_feat = int(mesh.shape[feature_axis])
        fp_hist_1 = make_sharded_hist_2d(mesh, row_axis, feature_axis,
                                         params.hist_backend, 1, Bmax, hdt,
                                         k_classes=K)
        fp_hist_S = make_sharded_hist_2d(mesh, row_axis, feature_axis,
                                         params.hist_backend, S, Bmax, hdt,
                                         k_classes=K)
        fp_bin = make_sharded_bin_gather_2d(mesh, row_axis, feature_axis,
                                            G // d_feat, batched=True)
    if use_stream:
        from ..pallas.stream_kernel import (build_route_tables, pack_bins_T,
                                            route_and_hist,
                                            stream_tiling)
        T_rows, tile_groups = stream_tiling(
            Bmax, G, params.int_hist, bin_buckets=params.bin_buckets,
            hist_channels=2 * S * K)[:2]
        if tile_groups:
            # the engine sends such a table down the per-class scan
            # (gbdt._use_batched_multiclass: the widened block must fit VMEM)
            raise ValueError(
                f"grow_tree_k runs one M-tile; {G} groups of {Bmax} bins "
                f"with {2 * S * K} histogram columns need {tile_groups}-"
                "group tiles")
        if packed is None:
            with jax.named_scope("pack_bins"):
                bins_T = pack_bins_T(bins, T_rows, max_bins=Bmax).bins_T
        else:
            bins_T = packed.bins_T if hasattr(packed, "bins_T") else packed
        n_pad = bins_T.shape[1]
        use_int = params.int_hist and gh_scales is not None
        if use_int:
            inv = 1.0 / jnp.maximum(gh_scales, 1e-30)        # (K, 2)
            w_grad = grad * inv[:, 0:1]
            w_hess = hess * inv[:, 1:2]
            hscale = gh_scales                               # (K, 2)
        else:
            w_grad, w_hess = grad, hess
        w_rows = 2 * K + 1
        w_pad_rows = -(-w_rows // 8) * 8
        w2 = jnp.stack([w_grad, w_hess], axis=1).reshape(2 * K, N)
        w_T = jnp.zeros((w_pad_rows, n_pad), f32)
        w_T = w_T.at[:2 * K, :N].set(w2).at[2 * K, :N].set(cnt_w)

        # ---- GOSS/bagging row compaction (see grow_tree): one stable
        # partition per iteration serves all K lockstep class trees — the
        # mask row (2K) is shared across classes
        bins_T_h, w_T_h = bins_T, w_T
        if use_compact:
            from .compact import compact_transposed_view
            bins_T_h, w_T_h = compact_transposed_view(
                bins_T, w_T, 2 * K, compact_rows, T_rows,
                mesh=mesh, row_axis=row_axis)
        n_pad_h = bins_T_h.shape[1]

        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ..parallel.comms import reduce_hist_rows
            from ..parallel.mesh import shard_map_rows

            def _rh(bT, lid, wT, tb, bi, num_slots, with_hist=True):
                def _local(bT, lid, wT, tb, bi):
                    nl, h, c = route_and_hist(
                        bT, lid, wT, tb, bi, num_slots, Bmax, G, L,
                        block_rows=T_rows, has_cat=params.has_categorical,
                        two_pass=params.hist_two_pass, int_weights=use_int,
                        with_hist=with_hist, bin_buckets=params.bin_buckets,
                        num_class=K)
                    if with_hist:
                        h = reduce_hist_rows(
                            h, row_axis, 2, plan, params.hist_comms_dtype,
                            params.hist_comms_chunks,
                            params.hist_reduce_limbs)
                    elif use_rs:
                        h = jnp.zeros(h.shape[:2] + (plan.gs,) + h.shape[3:],
                                      h.dtype)
                    with jax.named_scope("slot_count_psum"):
                        return nl, h, jax.lax.psum(c.astype(i32), row_axis)

                hspec = (P(None, None, row_axis, None, None) if use_rs
                         else P(None, None, None, None, None))
                wrapped = shard_map_rows(
                    _local, mesh,
                    (P(None, row_axis), P(None, row_axis),
                     P(None, row_axis), P(None, None), P(None, None)),
                    (P(None, row_axis), hspec, P(None, None)))
                return wrapped(bT, lid, wT, tb, bi)
        else:
            def _rh(bT, lid, wT, tb, bi, num_slots, with_hist=True):
                return route_and_hist(
                    bT, lid, wT, tb, bi, num_slots, Bmax, G, L,
                    block_rows=T_rows, has_cat=params.has_categorical,
                    two_pass=params.hist_two_pass, int_weights=use_int,
                    with_hist=with_hist, bin_buckets=params.bin_buckets,
                    num_class=K)

        zKL = jnp.zeros(K * L, i32)
        tabs0 = build_route_tables(zKL, zKL, zKL, zKL, zKL, zKL, zKL,
                                   zKL.at[kI * L].set(1), routing, K * L)
        bits0 = jnp.zeros((Bpad, K * L), jnp.bfloat16)
        leaf_id = jnp.zeros((K, n_pad), i32)
        leaf_id_c = jnp.zeros((K, n_pad_h) if use_compact else (1, 1), i32)
        _, root_hist, _ = _rh(bins_T_h,
                              leaf_id_c if use_compact else leaf_id,
                              w_T_h, tabs0, bits0, 1)
        if use_int:
            root_hist = root_hist.astype(f32) \
                * hscale[:, None, None, None, :]
    else:
        leaf_id = jnp.zeros((K, N), i32)
        leaf_id_c = jnp.zeros((1, 1), i32)
        if use_compact:
            # see grow_tree: same shared compact_row_views helper; grad/
            # hess are (K, N) here and the helper gathers the last axis
            from .compact import compact_row_views
            bins_c, grad_c, hess_c, cnt_c, c_perm = compact_row_views(
                bins, grad, hess, cnt_w, compact_rows)
            root_hist = build_histograms_k(
                bins_c, jnp.zeros((K, compact_rows), i32), grad_c, hess_c,
                cnt_c, K, 1, Bmax, backend=params.hist_backend,
                acc_dtype=hdt)[..., :2]
        elif use_fp:
            root_hist = fp_hist_1(bins, leaf_id, grad, hess,
                                  cnt_w)[..., :2]
        else:
            root_hist = build_histograms_k(
                bins, leaf_id, grad, hess, cnt_w, K, 1, Bmax,
                backend=params.hist_backend, acc_dtype=hdt)[..., :2]
    root_g = jnp.sum(grad, axis=1, dtype=hdt)                # (K,)
    root_h = jnp.sum(hess, axis=1, dtype=hdt)
    int_counts = _int_counts(use_stream, mesh)
    if int_counts:
        root_n = jnp.broadcast_to(
            jnp.sum(cnt_w.astype(i32), dtype=i32), (K,))
        root_c = root_n.astype(hdt)
    else:
        root_n = root_c = jnp.broadcast_to(jnp.sum(cnt_w, dtype=hdt), (K,))

    def count_h(c):
        """A count of the state's in the histogram dtype (as grow_tree)."""
        return c.astype(hdt) if int_counts else c
    cm_root = jnp.broadcast_to(col_mask[None, :], (K, F))
    if use_rs or use_fp:
        root_split = (rs_split if use_rs else fp_split)(
            root_hist.reshape(K, G_h, Bmax, 2),
            root_g, root_h, root_c, col_mask)
    else:
        root_split = find_splits(root_hist.reshape(K, G_h, Bmax, 2),
                                 root_g, root_h, root_c, col_mask=cm_root)

    hist = jnp.zeros((K, L, G_h, Bmax, 2), hdt).at[:, 0].set(
        root_hist.reshape(K, G_h, Bmax, 2))
    if use_fp:
        # pin the histogram STATE to the compound group sharding for the
        # whole while_loop (see grow_tree's fp pin)
        from jax.sharding import NamedSharding, PartitionSpec as _P
        hist = jax.lax.with_sharding_constraint(
            hist, NamedSharding(
                mesh, _P(None, None, (feature_axis, row_axis), None,
                         None)))
    state = _GrowStateK(
        leaf_id=leaf_id,
        leaf_id_c=leaf_id_c,
        split_feature=jnp.zeros((K, L), i32),
        threshold_bin=jnp.zeros((K, L), i32),
        dir_flags=jnp.zeros((K, L), i32),
        left_child=jnp.zeros((K, L), i32),
        right_child=jnp.zeros((K, L), i32),
        split_gain=jnp.zeros((K, L), f32),
        internal_value=jnp.zeros((K, L), f32),
        internal_weight=jnp.zeros((K, L), f32),
        internal_count=jnp.zeros((K, L), i32 if int_counts else f32),
        cat_bitset=jnp.zeros((K, L, Bmax), bool),
        sum_g=jnp.zeros((K, L), hdt).at[:, 0].set(root_g),
        sum_h=jnp.zeros((K, L), hdt).at[:, 0].set(root_h),
        cnt=jnp.zeros((K, L), i32 if int_counts else hdt)
        .at[:, 0].set(root_n),
        depth=jnp.zeros((K, L), i32),
        leaf_parent=jnp.full((K, L), -1, i32),
        best_gain=jnp.full((K, L), NEG_INF, hdt).at[:, 0].set(
            root_split.gain),
        best_feat=jnp.zeros((K, L), i32).at[:, 0].set(root_split.feature),
        best_thr=jnp.zeros((K, L), i32).at[:, 0].set(root_split.threshold),
        best_dir=jnp.zeros((K, L), i32).at[:, 0].set(root_split.dir_flags),
        best_left_g=jnp.zeros((K, L), hdt).at[:, 0].set(
            root_split.left_sum_g),
        best_left_h=jnp.zeros((K, L), hdt).at[:, 0].set(
            root_split.left_sum_h),
        best_left_c=jnp.zeros((K, L), hdt).at[:, 0].set(
            root_split.left_count),
        hist=hist,
        num_leaves_cur=jnp.ones(K, i32),
        progressed=jnp.ones(K, bool),
        hist_passes=jnp.asarray(1, i32),
        scan_slots=jnp.asarray(0, i32),
    )

    def cond_k(st: _GrowStateK):
        return jnp.any(st.progressed & (st.num_leaves_cur < L))

    sprint = (use_stream and S >= 64 and params.max_depth <= 0)
    S_f = min(2 * S, 255, max(L - 1, 1))

    def can_finish(st: _GrowStateK):
        remaining = L - st.num_leaves_cur
        splittable = jnp.sum((st.best_gain > 0).astype(i32), axis=1)
        return (remaining <= S_f) & (remaining <= splittable)

    def make_body_k(S: int, with_hist: bool = True,
                    freeze_sprint: bool = False):
        """Lockstep round body. A class whose per-class loop would have
        exited (no progress, leaf budget reached, or — with freeze_sprint —
        sprint-ready) takes an exact no-op this round: its split count is
        forced to 0, every update indexes out of bounds with mode="drop",
        and its progressed flag is preserved. Frozen sprint-ready classes
        replay their sprint from untouched state, so per-class results
        match grow_tree's sequential schedule split for split."""
        def body(st: _GrowStateK) -> _GrowStateK:
            cur = st.num_leaves_cur                          # (K,)
            remaining = L - cur
            drop = jnp.asarray(2 ** 30, i32)
            active = st.progressed & (cur < L)
            if freeze_sprint:
                active = active & ~can_finish(st)

            # ---- candidate selection: per-class top-S splittable ----
            depth_ok = (params.max_depth <= 0) | (st.depth < jnp.asarray(
                params.max_depth if params.max_depth > 0 else 2 ** 30, i32))
            cand = jnp.where((st.best_gain > 0) & depth_ok, st.best_gain,
                             NEG_INF)
            order = jnp.argsort(-cand, axis=1)               # (K, L)
            k_budget = jnp.minimum(remaining, S)
            sorted_gain = ta(cand, order)
            chosen_rank = (jnp.arange(L)[None, :] < k_budget[:, None]) \
                & (sorted_gain > 0)
            ksp = jnp.where(active,
                            jnp.sum(chosen_rank, axis=1, dtype=i32), 0)

            sS = jnp.arange(S, dtype=i32)
            pair_valid = sS[None, :] < ksp[:, None]          # (K, S)
            pair_old = jnp.where(pair_valid, order[:, :S].astype(i32), 0)
            pair_new = jnp.where(pair_valid, cur[:, None] + sS[None, :], 0)
            pair_node = jnp.where(pair_valid,
                                  (cur - 1)[:, None] + sS[None, :], 0)
            node_idx = jnp.where(pair_valid, pair_node, drop)
            new_idx = jnp.where(pair_valid, pair_new, drop)
            old_idx = jnp.where(pair_valid, pair_old, drop)

            feat = ta(st.best_feat, pair_old)
            thr = ta(st.best_thr, pair_old)
            dirf = ta(st.best_dir, pair_old)
            gain = ta(st.best_gain, pair_old)
            pg, ph, pn = (ta(st.sum_g, pair_old), ta(st.sum_h, pair_old),
                          ta(st.cnt, pair_old))
            pc = count_h(pn)
            lg, lh, lc = (ta(st.best_left_g, pair_old),
                          ta(st.best_left_h, pair_old),
                          ta(st.best_left_c, pair_old))
            rg, rh, rc = pg - lg, ph - lh, pc - lc

            # ---- categorical bitsets (rows are class x slot) ----
            parent_hist = st.hist[kI[:, None], pair_old]     # (K, S, G, B, 2)
            if params.has_categorical and (use_rs or use_fp):
                bitset = (rs_bitset if use_rs else fp_bitset)(
                    parent_hist.reshape(K * S, G_h, Bmax, 2),
                    feat.reshape(-1), thr.reshape(-1), dirf.reshape(-1),
                    pg.reshape(-1), ph.reshape(-1), pc.reshape(-1)
                ).reshape(K, S, Bmax)
            elif params.has_categorical:
                hf = gather_feature_histograms(
                    parent_hist.reshape(K * S, G, Bmax, 2), layout,
                    pg.reshape(-1), ph.reshape(-1))
                hf_feat = hf[jnp.arange(K * S), feat.reshape(-1)]
                bitset = categorical_left_bitset(
                    hf_feat, thr.reshape(-1), dirf.reshape(-1),
                    layout.valid_mask[feat.reshape(-1)],
                    params.cat_smooth, params.min_data_per_group,
                    (pc / jnp.maximum(ph, EPS_HESS)).reshape(-1)
                ).reshape(K, S, Bmax)
            else:
                bitset = jnp.zeros((K, S, Bmax), bool)

            # ---- node array updates ----
            out = leaf_output(pg, ph, params.lambda_l1, params.lambda_l2,
                              params.max_delta_step)
            k2 = kI[:, None]
            st2 = st._replace(
                split_feature=st.split_feature.at[k2, node_idx].set(
                    feat, mode="drop"),
                threshold_bin=st.threshold_bin.at[k2, node_idx].set(
                    thr, mode="drop"),
                dir_flags=st.dir_flags.at[k2, node_idx].set(
                    dirf, mode="drop"),
                split_gain=st.split_gain.at[k2, node_idx].set(
                    gain.astype(f32), mode="drop"),
                internal_value=st.internal_value.at[k2, node_idx].set(
                    out.astype(f32), mode="drop"),
                internal_weight=st.internal_weight.at[k2, node_idx].set(
                    ph.astype(f32), mode="drop"),
                internal_count=st.internal_count.at[k2, node_idx].set(
                    pn if int_counts else pc.astype(f32), mode="drop"),
                cat_bitset=st.cat_bitset.at[k2, node_idx].set(
                    bitset, mode="drop"),
                left_child=st.left_child.at[k2, node_idx].set(
                    ~pair_old, mode="drop"),
                right_child=st.right_child.at[k2, node_idx].set(
                    ~pair_new, mode="drop"),
            )
            parent_of_old = ta(st.leaf_parent, pair_old)
            was_left = (ta(st2.left_child,
                           jnp.where(parent_of_old >= 0, parent_of_old, 0))
                        == ~pair_old) & (parent_of_old >= 0)
            lp_idx = jnp.where(pair_valid & (parent_of_old >= 0) & was_left,
                               parent_of_old, drop)
            rp_idx = jnp.where(pair_valid & (parent_of_old >= 0) & ~was_left,
                               parent_of_old, drop)
            st2 = st2._replace(
                left_child=st2.left_child.at[k2, lp_idx].set(
                    pair_node, mode="drop"),
                right_child=st2.right_child.at[k2, rp_idx].set(
                    pair_node, mode="drop"),
                leaf_parent=(st2.leaf_parent
                             .at[k2, old_idx].set(pair_node, mode="drop")
                             .at[k2, new_idx].set(pair_node, mode="drop")),
            )

            # ---- route rows of chosen leaves (all classes at once) ----
            leaf_chosen = jnp.zeros((K, L), bool).at[k2, old_idx].set(
                pair_valid, mode="drop")
            leaf_new_id = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                pair_new, mode="drop")
            leaf_feat = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                feat, mode="drop")
            leaf_thr = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                thr, mode="drop")
            leaf_dir = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                dirf, mode="drop")
            smaller_is_left = lc <= rc

            if use_stream:
                si1 = jnp.broadcast_to(sS[None, :] + 1, (K, S))
                sl1 = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                    jnp.where(smaller_is_left, si1, 0), mode="drop")
                sr1 = jnp.zeros((K, L), i32).at[k2, old_idx].set(
                    jnp.where(smaller_is_left, 0, si1), mode="drop")
                bits_l = jnp.zeros((K, L, Bpad), jnp.bfloat16).at[
                    k2, old_idx].set(
                    jnp.pad(bitset, ((0, 0), (0, 0), (0, Bpad - Bmax))
                            ).astype(jnp.bfloat16), mode="drop")
                tabs = build_route_tables(
                    leaf_chosen.reshape(-1).astype(i32),
                    leaf_feat.reshape(-1), leaf_thr.reshape(-1),
                    leaf_dir.reshape(-1), leaf_new_id.reshape(-1),
                    sl1.reshape(-1), sr1.reshape(-1),
                    jnp.zeros(K * L, i32), routing, K * L)
                lid_h = st.leaf_id_c if use_compact else st.leaf_id
                with jax.named_scope("route_and_hist_k"):
                    new_leaf_h, hist_small, slot_cnt = _rh(
                        bins_T_h, lid_h, w_T_h, tabs,
                        bits_l.reshape(K * L, Bpad).T, S,
                        with_hist=with_hist)
                if use_int and with_hist:
                    hist_small = hist_small.astype(f32) \
                        * hscale[:, None, None, None, :]
                if use_compact:
                    # full-data route-only pass (see grow_tree)
                    with jax.named_scope("route_full_k"):
                        new_leaf_id, _, _ = _rh(
                            bins_T, st.leaf_id, w_T, tabs,
                            bits_l.reshape(K * L, Bpad).T, S,
                            with_hist=False)
                    new_leaf_c = new_leaf_h
                else:
                    new_leaf_id = new_leaf_h
                    new_leaf_c = st.leaf_id_c
            else:
                leaf_bits = jnp.zeros((K, L, Bmax), bool).at[
                    k2, old_idx].set(bitset, mode="drop")
                lid = st.leaf_id                             # (K, N)
                r_chosen = ta(leaf_chosen, lid)
                r_feat = ta(leaf_feat, lid)
                r_grp = routing.feat_group[r_feat]           # (K, N)
                if use_fp:
                    # owner-feature-shard column read + feature-axis psum
                    # (the row axis never communicates)
                    gb = fp_bin(bins, r_grp)
                else:
                    gb = jnp.take_along_axis(
                        bins, r_grp.T.astype(jnp.int32), axis=1).T
                fb = feature_local_bin(gb, r_feat, routing)
                r_thr = ta(leaf_thr, lid)
                r_dir = ta(leaf_dir, lid)
                is_cat = (r_dir & 2) != 0
                default_left = (r_dir & 1) != 0
                is_nan = (routing.nan_bin[r_feat] >= 0) \
                    & (fb == routing.nan_bin[r_feat])
                mzb_r = (routing.mzero_bin[r_feat]
                         if routing.mzero_bin is not None
                         else jnp.full_like(r_feat, -1))
                is_miss = is_nan | ((mzb_r >= 0) & (fb == mzb_r))
                go_left_num = jnp.where(is_miss, default_left, fb <= r_thr)
                go_left_cat = leaf_bits.reshape(-1)[
                    (k2 * L + lid) * Bmax + fb]
                go_left = jnp.where(is_cat, go_left_cat, go_left_num)
                new_leaf_id = jnp.where(r_chosen & ~go_left,
                                        ta(leaf_new_id, lid), lid)
                new_leaf_c = st.leaf_id_c

            # ---- histograms for the smaller children + EXACT counts ----
            smaller_id_pre = jnp.where(smaller_is_left, pair_old, pair_new)
            if not use_stream:
                slot_map = jnp.full((K, L), -1, i32).at[
                    k2, jnp.where(pair_valid, smaller_id_pre, drop)].set(
                    jnp.broadcast_to(sS[None, :], (K, S)), mode="drop")
                slot = ta(slot_map, new_leaf_id)             # (K, N)
                if use_compact:
                    hist3 = build_histograms_k(
                        bins_c, jnp.take(slot, c_perm, axis=1), grad_c,
                        hess_c, cnt_c, K, S, Bmax,
                        backend=params.hist_backend, acc_dtype=hdt)
                elif use_fp:
                    hist3 = fp_hist_S(bins, slot, grad, hess, cnt_w)
                else:
                    hist3 = build_histograms_k(
                        bins, slot, grad, hess, cnt_w, K, S, Bmax,
                        backend=params.hist_backend, acc_dtype=hdt)
                hist_small = hist3[..., :2]
                slot_cnt = hist3[:, :, 0, :, 2].sum(axis=-1)
            lc_x = jnp.where(smaller_is_left, slot_cnt, pn - slot_cnt)
            rc_x = pn - lc_x

            # ---- per-leaf stats for the children ----
            st2 = st2._replace(
                leaf_id=new_leaf_id,
                leaf_id_c=new_leaf_c,
                sum_g=st2.sum_g.at[k2, old_idx].set(lg, mode="drop")
                               .at[k2, new_idx].set(rg, mode="drop"),
                sum_h=st2.sum_h.at[k2, old_idx].set(lh, mode="drop")
                               .at[k2, new_idx].set(rh, mode="drop"),
                cnt=st2.cnt.at[k2, old_idx].set(lc_x, mode="drop")
                           .at[k2, new_idx].set(rc_x, mode="drop"),
                depth=st2.depth.at[k2, new_idx].set(
                    ta(st.depth, pair_old) + 1, mode="drop")
                               .at[k2, old_idx].set(
                    ta(st.depth, pair_old) + 1, mode="drop"),
            )

            if not with_hist:
                # sprint round: the trees are complete after these splits
                return st2._replace(
                    num_leaves_cur=cur + ksp,
                    progressed=jnp.where(active, ksp > 0, st.progressed))

            # ---- histogram subtraction for the larger siblings ----
            larger_id = jnp.where(smaller_is_left, pair_new, pair_old)
            hist_large = parent_hist - hist_small
            sm_idx = jnp.where(pair_valid, smaller_id_pre, drop)
            lg_idx = jnp.where(pair_valid, larger_id, drop)
            new_hist = (st2.hist
                        .at[k2, sm_idx].set(hist_small, mode="drop")
                        .at[k2, lg_idx].set(hist_large, mode="drop"))
            st2 = st2._replace(hist=new_hist)

            # ---- best splits for the 2S children of every class ----
            ids2 = jnp.concatenate([pair_old, pair_new], axis=1)  # (K, 2S)
            valid2 = jnp.concatenate([pair_valid, pair_valid], axis=1)
            hist2 = new_hist[k2, ids2]
            cm2 = jnp.broadcast_to(col_mask[None, :], (K * 2 * S, F))
            with jax.named_scope("find_splits_k"):
                if use_rs or use_fp:
                    res = (rs_split if use_rs else fp_split)(
                        hist2.reshape(K * 2 * S, G_h, Bmax, 2),
                        ta(st2.sum_g, ids2).reshape(-1),
                        ta(st2.sum_h, ids2).reshape(-1),
                        count_h(ta(st2.cnt, ids2)).reshape(-1), col_mask)
                else:
                    res = find_splits(hist2.reshape(K * 2 * S, G_h, Bmax, 2),
                                      ta(st2.sum_g, ids2).reshape(-1),
                                      ta(st2.sum_h, ids2).reshape(-1),
                                      count_h(ta(st2.cnt, ids2)).reshape(-1),
                                      col_mask=cm2)
            ids2_m = jnp.where(valid2, ids2, drop)

            def rs(a):
                return a.reshape(K, 2 * S)
            st2 = st2._replace(
                best_gain=st2.best_gain.at[k2, ids2_m].set(
                    rs(res.gain), mode="drop"),
                best_feat=st2.best_feat.at[k2, ids2_m].set(
                    rs(res.feature), mode="drop"),
                best_thr=st2.best_thr.at[k2, ids2_m].set(
                    rs(res.threshold), mode="drop"),
                best_dir=st2.best_dir.at[k2, ids2_m].set(
                    rs(res.dir_flags), mode="drop"),
                best_left_g=st2.best_left_g.at[k2, ids2_m].set(
                    rs(res.left_sum_g), mode="drop"),
                best_left_h=st2.best_left_h.at[k2, ids2_m].set(
                    rs(res.left_sum_h), mode="drop"),
                best_left_c=st2.best_left_c.at[k2, ids2_m].set(
                    rs(res.left_count), mode="drop"),
            )
            return st2._replace(
                num_leaves_cur=cur + ksp,
                progressed=jnp.where(active, ksp > 0, st.progressed),
                hist_passes=st.hist_passes + 1,
                scan_slots=st.scan_slots + K * S)
        return body

    # streaming rounds: same specialized small-S prefix as grow_tree
    if use_stream and S > 64:
        b64 = make_body_k(64)
        for _ in range(7):
            state = jax.lax.cond(cond_k(state), b64, lambda s: s, state)

    if sprint:
        # full rounds while ANY class still needs one; sprint-ready classes
        # FREEZE (exact no-op) so their final route-only sprint replays from
        # the same state the per-class schedule would have sprinted from
        def cond_sprint_k(st: _GrowStateK):
            return jnp.any(st.progressed & (L - st.num_leaves_cur > 0)
                           & ~can_finish(st))
        state = jax.lax.while_loop(
            cond_sprint_k, make_body_k(S, freeze_sprint=True), state)
        final = jax.lax.cond(
            cond_k(state), make_body_k(S_f, with_hist=False),
            lambda s: s, state)
    else:
        final = jax.lax.while_loop(cond_k, make_body_k(S), state)

    leaf_value = leaf_output(final.sum_g, final.sum_h, params.lambda_l1,
                             params.lambda_l2, params.max_delta_step)
    leaf_value = jnp.where(final.num_leaves_cur[:, None] > 1,
                           leaf_value, 0.0)
    tree = TreeArrays(
        split_feature=final.split_feature, threshold_bin=final.threshold_bin,
        dir_flags=final.dir_flags, left_child=final.left_child,
        right_child=final.right_child, split_gain=final.split_gain,
        internal_value=final.internal_value,
        internal_weight=final.internal_weight,
        internal_count=final.internal_count, cat_bitset=final.cat_bitset,
        leaf_value=leaf_value.astype(f32),
        leaf_weight=final.sum_h.astype(f32),
        leaf_count=final.cnt if int_counts else final.cnt.astype(f32),
        leaf_parent=final.leaf_parent, num_leaves=final.num_leaves_cur,
        leaf_depth=final.depth,
    )
    if with_passes:
        return (tree, final.leaf_id[:, :N],
                jnp.stack([final.hist_passes, jnp.zeros((), i32),
                           final.scan_slots]))
    return tree, final.leaf_id[:, :N]

"""Streaming batch-prediction Pallas TPU kernel.

Reference analog: src/boosting/gbdt_prediction.cpp (PredictRaw: per-row loop
over trees, recursive node walk) and src/application/predictor.hpp:237.

TPU re-design: per-row pointer chasing is hostile to both XLA (per-step row
gathers run at ~100M rows/s) and the MXU.  This kernel streams row blocks
through VMEM once; ALL tree node tables live in VMEM simultaneously
(~24 rows x L cols x T trees x 4 B — 6 MB for 500 trees x 255 leaves), and the
walk advances every row through one tree level with a (24, L) @ (L, T)
node-one-hot matmul.  Child pointers and leaf values are 7-bit/bf16-pair
digit-encoded so the bf16 matmuls stay exact.  Trees iterate in a
`lax.fori_loop` with dynamic VMEM slices, so compile time is independent of
the model size.

Categorical splits walk on-device too: each cat node's left-set is a
bitset over the feature's BINS (the value-domain `cat_threshold` words are
re-projected through the bin mapper's category list at table-build time),
stored in a per-tree side table of 7-bit digit rows — five digit rows
reconstruct one exact 32-bit word, and the word for a row's bin is picked
with the same one-hot masked dot as every other per-node field.  NaN /
unseen / negative category values are pre-binned to a sentinel bin one
past the feature's span whose bit is always zero, reproducing the host
walk's "not in bitset -> right" routing.  Zero-as-missing default routing
(MISSING_ZERO) rides two more table rows, mirroring the training stream
kernel.  The host fallback is linear trees only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import pallas_interpret
from ..telemetry.watchdog import watched_jit

ROWS_PER_TREE = 24
(P_WORD_LO, P_WORD_HI, P_SHIFT, P_SPAN, P_DEFBIN, P_BUNDLED, P_HASNAN,
 P_NANBIN, P_NBINS, P_THR, P_DEFLEFT, P_LEFT_LO, P_LEFT_HI, P_RIGHT_LO,
 P_RIGHT_HI, P_LEAF_HI, P_LEAF_LO, P_ISCAT, P_HASMZ, P_MZBIN, P_CATB_LO,
 P_CATB_HI) = range(22)

# digit rows per tree in the categorical side table: one 32-bit bitset
# word = five 7-bit digits (each exact in bf16, reassembled with shifts)
CAT_DIGITS = 5


def _predict_kernel(bins_ref, tabs_ref, cat_ref, out_ref, *, T, L, GW, CW,
                    n_trees, max_depth, has_cat: bool, es_freq: int = 0,
                    es_margin: float = 0.0):
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    words = bins_ref[...]                                    # (GW, T)
    l_iota = jax.lax.broadcasted_iota(i32, (L, T), 0)
    gw_iota = jax.lax.broadcasted_iota(i32, (GW, T), 0)

    def tree_body(t, carry):
        # score-only carry when early stop is off: the active mask and its
        # per-tree select exist only under es_freq > 0
        score, active = carry if es_freq else (carry, None)
        tab = tabs_ref[pl.ds(t * ROWS_PER_TREE, ROWS_PER_TREE), :]  # (24, L)
        tab_bf = tab.astype(bf16)
        if has_cat:
            # this tree's bitset digit rows, (CAT_DIGITS, CW)
            cat_bf = cat_ref[pl.ds(t * CAT_DIGITS, CAT_DIGITS), :].astype(bf16)
        enc = jnp.zeros((1, T), i32)       # node 0; >= L means "at leaf ~"

        def step(_, enc):
            at_leaf = enc >= L
            node = jnp.where(at_leaf, 0, enc)
            node_oh = (l_iota == node).astype(bf16)          # (L, T)
            vals = jax.lax.dot_general(
                tab_bf, node_oh, (((1,), (0,)), ((), ())),
                preferred_element_type=f32)                  # (24, T)
            iv = vals.astype(i32)
            wordi = iv[P_WORD_LO:P_WORD_LO + 1] + (iv[P_WORD_HI:P_WORD_HI + 1] << 7)
            word = jnp.sum(jnp.where(gw_iota == wordi, words, 0), axis=0,
                           keepdims=True)
            gb = jax.lax.shift_right_logical(word, iv[P_SHIFT:P_SHIFT + 1]) & 0xFF
            span = iv[P_SPAN:P_SPAN + 1]
            defbin = iv[P_DEFBIN:P_DEFBIN + 1]
            nbins = iv[P_NBINS:P_NBINS + 1]
            ls = gb - span
            ge_def = jnp.where(ls >= defbin, 1, 0)
            fb_b = jnp.where((ls >= 0) & (ls < nbins - 1), ls + ge_def, defbin)
            fb = jnp.where(iv[P_BUNDLED:P_BUNDLED + 1] > 0, fb_b, gb)
            is_nan_i = (iv[P_HASNAN:P_HASNAN + 1]
                        * jnp.where(fb == iv[P_NANBIN:P_NANBIN + 1], 1, 0))
            # MISSING_ZERO default routing (training stream kernel parity:
            # stream_kernel.py T_HASMZ/T_MZBIN)
            is_mz_i = (iv[P_HASMZ:P_HASMZ + 1]
                       * jnp.where(fb == iv[P_MZBIN:P_MZBIN + 1], 1, 0))
            le_thr = jnp.where(fb <= iv[P_THR:P_THR + 1], 1, 0)
            go_left = jnp.where(is_nan_i + is_mz_i > 0,
                                iv[P_DEFLEFT:P_DEFLEFT + 1], le_thr)
            if has_cat:
                # bitset membership: word index = per-node base + fb >> 5,
                # selected with a one-hot masked dot over the digit rows
                # (exactly one 1.0 * digit product per output — exact);
                # missing flags never apply to categorical nodes (host
                # walk: miss &= ~is_cat)
                catb = (iv[P_CATB_LO:P_CATB_LO + 1]
                        + (iv[P_CATB_HI:P_CATB_HI + 1] << 7))
                wi = catb + jax.lax.shift_right_logical(fb, 5)   # (1, T)
                cw_iota = jax.lax.broadcasted_iota(i32, (CW, T), 0)
                woh = (cw_iota == wi).astype(bf16)               # (CW, T)
                digs = jax.lax.dot_general(
                    cat_bf, woh, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)                  # (5, T)
                dg = digs.astype(i32)
                cword = (dg[0:1] + (dg[1:2] << 7) + (dg[2:3] << 14)
                         + (dg[3:4] << 21) + (dg[4:5] << 28))
                cbit = jax.lax.shift_right_logical(cword, fb & 31) & 1
                go_left = jnp.where(iv[P_ISCAT:P_ISCAT + 1] > 0, cbit,
                                    go_left)
            left = iv[P_LEFT_LO:P_LEFT_LO + 1] + (iv[P_LEFT_HI:P_LEFT_HI + 1] << 7)
            right = (iv[P_RIGHT_LO:P_RIGHT_LO + 1]
                     + (iv[P_RIGHT_HI:P_RIGHT_HI + 1] << 7))
            nxt = jnp.where(go_left > 0, left, right)
            return jnp.where(at_leaf, enc, nxt)

        enc = jax.lax.fori_loop(0, max_depth, step, enc)
        leaf = jnp.maximum(enc - L, 0)
        leaf_oh = (l_iota == leaf).astype(bf16)
        lv = jax.lax.dot_general(
            tab_bf[P_LEAF_HI:P_LEAF_LO + 1], leaf_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                      # (2, T)
        if not es_freq:
            return score + lv[0:1] + lv[1:2]
        # prediction early stopping (reference: prediction_early_stop.cpp
        # CreateBinary): every es_freq trees, rows whose margin 2|score|
        # clears the threshold freeze — the host loop's `active`
        # bookkeeping vectorized per block, applied to the device walk's
        # own (bf16-summed) scores, so rows landing within bf16 error of
        # the margin may freeze one checkpoint apart from the f64 host loop
        score = score + jnp.where(active > 0, lv[0:1] + lv[1:2], 0.0)
        at_check = ((t + 1) % es_freq) == 0
        stopped = (2.0 * jnp.abs(score)) > es_margin
        return score, jnp.where(at_check & stopped, 0, active)

    init = jnp.zeros((1, T), f32)
    if es_freq:
        score, _ = jax.lax.fori_loop(0, n_trees, tree_body,
                                     (init, jnp.ones((1, T), i32)))
    else:
        score = jax.lax.fori_loop(0, n_trees, tree_body, init)
    out_ref[...] = score


@functools.partial(watched_jit, name="predict_stream", warn_after=0,
                   static_argnames=("num_leaves", "n_trees", "max_depth",
                                    "block_rows", "has_cat", "es_freq",
                                    "es_margin"))
def predict_stream(bins_T: jax.Array, tabs: jax.Array, cat_tab: jax.Array,
                   num_leaves: int, n_trees: int, max_depth: int,
                   block_rows: int = 1024, has_cat: bool = False,
                   es_freq: int = 0, es_margin: float = 0.0):
    """Raw-score prediction: (GW, N_pad) packed bins + (n_trees*24, L) tables
    + (n_trees*5, CW) categorical bitset digit rows -> (N_pad,) f32 summed
    leaf values.  es_freq > 0 enables the binary prediction-early-stop
    margin check every es_freq trees."""
    GW, n_pad = bins_T.shape
    T = block_rows
    NB = n_pad // T
    L = num_leaves
    CW = cat_tab.shape[1]

    out = pl.pallas_call(
        functools.partial(_predict_kernel, T=T, L=L, GW=GW, CW=CW,
                          n_trees=n_trees, max_depth=max_depth,
                          has_cat=has_cat, es_freq=es_freq,
                          es_margin=es_margin),
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((GW, T), lambda b: (0, b)),
            pl.BlockSpec((n_trees * ROWS_PER_TREE, L), lambda b: (0, 0)),
            # sized off the actual table: numeric-only models pass a
            # minimal (CAT_DIGITS, 128) dummy the kernel never reads, so
            # no dead (n_trees*5, CW) VMEM block rides along
            pl.BlockSpec((cat_tab.shape[0], CW), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(bins_T, tabs, cat_tab)
    return out[0]


def build_predict_tables(trees, routing_np, num_leaves: int,
                         bin_mappers=None):
    """Host-side: (n_trees * 24, L) f32 node tables + (n_trees * 5, CW)
    f32 categorical bitset digit rows from host Tree objects.

    trees: list of tree.Tree (numeric + categorical splits; linear trees
    stay on the host).
    routing_np: dict of numpy routing arrays (feat_group, span_start,
    default_bin, bundled, nan_bin, num_bins, mzero_bin) indexed by
    ORIGINAL feature id.
    bin_mappers: training BinMappers — numeric thresholds are requantized
    from the REAL threshold (file-loaded trees carry threshold_bin=0; same
    rule as models/gbdt.py _tree_to_device), and categorical value-domain
    bitsets are re-projected onto bin indices (bit b set iff the bin's
    category ``categories[b]`` is in the node's value bitset).  Each cat
    feature's bitset spans ceil((num_bins + 1) / 32) words so the sentinel
    bin ``num_bins`` (NaN / unseen / negative values, pre-binned by the
    caller) always reads a zero bit and routes right like the host walk.
    Child encoding: internal child c >= 0 stays c; leaf child c < 0 becomes
    L + (~c).  Values that can exceed 255 are 7-bit digit-split; leaf values
    are bf16 hi/lo pairs."""
    L = num_leaves
    n_trees = len(trees)
    tabs = np.zeros((n_trees * ROWS_PER_TREE, L), np.float32)
    mzero = routing_np.get("mzero_bin")
    tree_words = []
    for ti, t in enumerate(trees):
        base = ti * ROWS_PER_TREE
        ni = max(t.num_leaves - 1, 0)
        # single-leaf trees (ni == 0) leave all child rows zero: the walk
        # stays on node 0 and the final jnp.maximum(enc - L, 0) resolves to
        # leaf 0, whose value is written below
        feats = np.asarray(t.split_feature[:ni], np.int64)
        grp = routing_np["feat_group"][feats]
        tabs[base + P_WORD_LO, :ni] = (grp >> 2) % 128
        tabs[base + P_WORD_HI, :ni] = (grp >> 2) // 128
        tabs[base + P_SHIFT, :ni] = (grp & 3) * 8
        tabs[base + P_SPAN, :ni] = routing_np["span_start"][feats]
        tabs[base + P_DEFBIN, :ni] = routing_np["default_bin"][feats]
        tabs[base + P_BUNDLED, :ni] = routing_np["bundled"][feats]
        nanb = routing_np["nan_bin"][feats]
        tabs[base + P_HASNAN, :ni] = (nanb >= 0).astype(np.float32)
        tabs[base + P_NANBIN, :ni] = np.maximum(nanb, 0)
        tabs[base + P_NBINS, :ni] = routing_np["num_bins"][feats]
        if mzero is not None and ni:
            mzb = mzero[feats]
            tabs[base + P_HASMZ, :ni] = (mzb >= 0).astype(np.float32)
            tabs[base + P_MZBIN, :ni] = np.maximum(mzb, 0)
        dt = (np.asarray(t.decision_type[:ni], np.uint8).astype(np.int32)
              if ni else np.zeros(0, np.int32))
        is_cat = (dt & 1) > 0
        tabs[base + P_ISCAT, :ni] = is_cat.astype(np.float32)
        if bin_mappers is not None:
            thr_b = np.zeros(ni, np.float32)
            for i in range(ni):
                if is_cat[i]:
                    continue   # cat nodes never compare against P_THR
                m = bin_mappers[int(feats[i])]
                thr_b[i] = np.searchsorted(m.upper_bounds,
                                           t.threshold[i], side="left")
            tabs[base + P_THR, :ni] = thr_b
        else:
            tabs[base + P_THR, :ni] = np.asarray(t.threshold_bin[:ni])
        tabs[base + P_DEFLEFT, :ni] = (np.asarray(t.decision_type[:ni]) & 2) > 0

        # categorical side table: per cat node, project the value-domain
        # bitset onto this feature's bins and record the node's word base
        words_t: list = []
        for i in np.nonzero(is_cat)[0]:
            f = int(feats[i])
            nb = int(routing_np["num_bins"][f])
            nw = (nb + 1 + 31) // 32     # +1: the sentinel bin past span
            base_w = len(words_t)
            tabs[base + P_CATB_LO, i] = base_w % 128
            tabs[base + P_CATB_HI, i] = base_w // 128
            k = int(t.threshold_bin[i])
            s, e = int(t.cat_boundaries[k]), int(t.cat_boundaries[k + 1])
            wv = np.asarray(t.cat_threshold[s:e], np.uint32)
            words = np.zeros(nw, np.uint32)
            cats = (bin_mappers[f].categories if bin_mappers is not None
                    else np.zeros(0, np.int64))
            for b in range(min(len(cats), nb)):
                c = int(cats[b])
                if c >= 0 and c // 32 < len(wv) \
                        and (int(wv[c // 32]) >> (c % 32)) & 1:
                    words[b // 32] |= np.uint32(1 << (b % 32))
            words_t.extend(int(w) for w in words)
        tree_words.append(words_t)

        def enc_child(c):
            c = np.asarray(c, np.int64)
            return np.where(c >= 0, c, L + ~c).astype(np.float64)

        lc = enc_child(t.left_child[:ni])
        rc = enc_child(t.right_child[:ni])
        tabs[base + P_LEFT_LO, :ni] = lc % 128
        tabs[base + P_LEFT_HI, :ni] = lc // 128
        tabs[base + P_RIGHT_LO, :ni] = rc % 128
        tabs[base + P_RIGHT_HI, :ni] = rc // 128

        lv = np.zeros(L, np.float32)
        lv[:t.num_leaves] = np.asarray(t.leaf_value[:t.num_leaves], np.float32)
        hi = _to_bf16_f32(lv)
        tabs[base + P_LEAF_HI, :] = hi
        tabs[base + P_LEAF_LO, :] = _to_bf16_f32(lv - hi)

    # digit-encode the per-tree word lists into the (n_trees*5, CW) table
    # (CW lanes padded to a multiple of 128 for VMEM tiling)
    cwt = max(max((len(w) for w in tree_words), default=0), 1)
    cwt = -(-cwt // 128) * 128
    cat_tab = np.zeros((max(n_trees, 1) * CAT_DIGITS, cwt), np.float32)
    for ti, words_t in enumerate(tree_words):
        for wj, w in enumerate(words_t):
            for d in range(CAT_DIGITS):
                cat_tab[ti * CAT_DIGITS + d, wj] = (w >> (7 * d)) & 127
    return tabs, cat_tab


def tree_max_depth(t) -> int:
    """Exact max depth of a host Tree via iterative traversal (leaf-wise trees
    can be up to num_leaves-1 deep)."""
    ni = max(t.num_leaves - 1, 0)
    if ni == 0:
        return 1
    depth = 1
    stack = [(0, 1)]
    lc = np.asarray(t.left_child)
    rc = np.asarray(t.right_child)
    while stack:
        node, d = stack.pop()
        depth = max(depth, d)
        for c in (int(lc[node]), int(rc[node])):
            if c >= 0:
                stack.append((c, d + 1))
    return depth


def _to_bf16_f32(x: np.ndarray) -> np.ndarray:
    """Round f32 -> bf16 (round-to-nearest-even) -> back to f32, in numpy."""
    u = np.asarray(x, np.float32).view(np.uint32)
    rounded = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return rounded.view(np.float32)

"""Fused streaming route+histogram Pallas TPU kernel — the v2 hot path.

Reference analog: src/io/dense_bin.hpp:99-170 (ConstructHistogramInner),
src/treelearner/data_partition.hpp (leaf row partition) and
src/treelearner/cuda/cuda_data_partition.cu + cuda_histogram_constructor.cu
(the CUDA backend splits these into separate scatter/atomic kernels).

TPU re-design rationale: measured on a v5e, XLA's random row gather runs at
~100M rows/s and scatter at ~11M rows/s, while sequential streaming runs at
HBM bandwidth (hundreds of GB/s).  The round-1 design (sort rows by histogram
slot, gather them into single-slot blocks, then contract) was therefore
latency-bound: ~10 full-data sort+gather+route passes per tree.  This kernel
removes ALL data movement: rows stream through in natural order ONCE per
round, and one fused pass both
  (1) routes each row through this round's chosen splits (per-leaf split
      tables applied via a one-hot matmul on the MXU), and
  (2) accumulates histograms for the S "smaller children" of the round, with
      the histogram-slot one-hot FOLDED into the contraction weights:

        hist[(g,b), (c,s)] += sum_t 1[bin_g[t]=b] * w[c,t] * 1[slot[t]=s]

      i.e. per group one (B, T) x (T, 3S) matmul; the (3S, T) right operand
      A[(c,s),t] = w[c,t]*slot_oh[s,t] is built once per block on the VPU.

Per-leaf split tables (threshold, feature word/shift, EFB span, NaN bin,
categorical bitset, child ids, slot ids) are tiny (L rows) and live in VMEM;
per-row values are fetched with a (24, L) @ (L, T) one-hot matmul.  Table
values are 7-bit digit-encoded where they can exceed 256 so the bf16 matmul
stays exact.

The histogram output uses a constant-index BlockSpec, so it stays resident in
VMEM across the whole grid and is written back to HBM once.  f32 weights are
split into two bf16 parts (hi + lo) and contracted twice so gradient sums
accumulate with f32 accuracy.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.watchdog import watched_jit
from ..binning import bucket_group_pad, bucket_run_rows
from ..runtime import on_tpu, pallas_interpret
from ..utils.log import LightGBMError

_BYTES = 0x01010101   # one per byte of a word
_TOPS = -0x7F7F7F80   # 0x80 in every byte, as an int32
NUM_TAB = 24          # per-leaf table rows (padded to a sublane multiple)
# a u8 block of more groups than this is not widened to int32 whole for the
# route's group select (a (G_pad, T) int32 temporary): the select goes this
# many groups at a time instead.  Every table narrow enough for the kernel
# before it tiled its M-axis (G <= 512) is widened whole, as it was.
WIDE_ROUTE_GROUPS = 512
MAX_SLOTS = 255       # slot table rows are single bf16 digits (exact <= 256)

import os as _os
# Perf-ablation probes (dev only): additive variants that double one kernel
# phase so its cost can be measured through the real bench. Several modes
# deliberately CORRUPT results — never set this for real training.
_ABLATE = _os.environ.get("LGBTPU_KABLATE", "")
_KNOWN_ABLATE = ("", "nohist", "constoh", "dblcon", "dblroute", "dblA",
                 "dbldot", "dbldot_i8", "noA")
if _ABLATE not in _KNOWN_ABLATE:
    raise ValueError(f"unknown LGBTPU_KABLATE={_ABLATE!r}; one of "
                     f"{_KNOWN_ABLATE[1:]}")
if _ABLATE:
    import sys as _sys
    print(f"WARNING: LGBTPU_KABLATE={_ABLATE} perf probe active — training "
          "results may be intentionally wrong", file=_sys.stderr)

# table row indices
(T_CHOSEN, T_NEWID_LO, T_NEWID_HI, T_WORD_LO, T_WORD_HI, T_SHIFT, T_SPAN,
 T_DEFBIN, T_BUNDLED, T_HASNAN, T_NANBIN, T_NBINS, T_THR, T_DEFLEFT, T_ISCAT,
 T_SLOT_L, T_SLOT_R, T_SLOT_KEEP, T_HASMZ, T_MZBIN) = range(20)


def _digits(v):
    """Split a non-negative int array into (lo7, hi) digits exact in bf16."""
    v = v.astype(jnp.int32)
    return (v & 127).astype(jnp.float32), (v >> 7).astype(jnp.float32)


def _route_step(iv, bins_ref, bins32, GW, T, u8_layout):
    """Shared single-table routing math: decode one (NUM_TAB, T) block of
    gathered table values into each row's routing decision.

    Used by BOTH the per-round fused kernel (_route_hist_kernel) and the
    fused route-replay kernel (_route_replay_kernel), so the two can never
    drift — the replay's bit-identity to the per-round route-only passes
    rests on this sharing.

    Returns (chosen_i, newid, fb, go_left_i, slot_l1, slot_r1, slot_k1)
    with go_left_i the NUMERIC decision (threshold + NaN/missing-zero
    default direction); the caller overlays the categorical bit where it
    has the bitset operand."""
    i32 = jnp.int32
    chosen_i = iv[T_CHOSEN:T_CHOSEN + 1, :]
    newid = iv[T_NEWID_LO:T_NEWID_LO + 1, :] + (iv[T_NEWID_HI:T_NEWID_HI + 1, :] << 7)
    wordi = iv[T_WORD_LO:T_WORD_LO + 1, :] + (iv[T_WORD_HI:T_WORD_HI + 1, :] << 7)
    shift = iv[T_SHIFT:T_SHIFT + 1, :]
    span = iv[T_SPAN:T_SPAN + 1, :]
    defbin = iv[T_DEFBIN:T_DEFBIN + 1, :]
    bundled_i = iv[T_BUNDLED:T_BUNDLED + 1, :]
    has_nan_i = iv[T_HASNAN:T_HASNAN + 1, :]
    nanbin = iv[T_NANBIN:T_NANBIN + 1, :]
    nbins = iv[T_NBINS:T_NBINS + 1, :]
    thr = iv[T_THR:T_THR + 1, :]
    defleft_i = iv[T_DEFLEFT:T_DEFLEFT + 1, :]

    # select the split feature's group-local bin for every row
    if u8_layout:
        # unpacked (G_pad, T) int8 storage: same HBM bytes as the packed
        # 4-per-word form (28 B/row either way at G=28) but no per-group
        # shift/mask unpack work in the kernel
        grpi = wordi * 4 + jax.lax.shift_right_logical(shift, 3)
        if bins32 is not None:
            gp_iota = jax.lax.broadcasted_iota(i32, bins32.shape, 0)
            gb = jnp.sum(jnp.where(gp_iota == grpi, bins32, 0), axis=0,
                         keepdims=True)                  # (1, T)
        else:
            # a table too wide to widen whole (WIDE_ROUTE_GROUPS): the same
            # select, one run of groups at a time
            gb = jnp.zeros((1, T), i32)
            g_pad = bins_ref.shape[0]
            for g0 in range(0, g_pad, WIDE_ROUTE_GROUPS):
                g1 = min(g0 + WIDE_ROUTE_GROUPS, g_pad)   # the last run ragged
                part = bins_ref[g0:g1, :].astype(i32)
                gp_iota = jax.lax.broadcasted_iota(i32, part.shape, 0)
                gb = gb + jnp.sum(jnp.where(gp_iota == grpi - g0, part, 0),
                                  axis=0, keepdims=True)
    else:
        # packed: select the split feature's group word, then its byte
        words = bins_ref[...]                            # (GW, T) i32
        gw_iota = jax.lax.broadcasted_iota(i32, (GW, T), 0)
        word = jnp.sum(jnp.where(gw_iota == wordi, words, 0), axis=0,
                       keepdims=True)                    # (1, T)
        gb = jax.lax.shift_right_logical(word, shift) & 0xFF

    # feature-local bin for EFB bundles (ops/grow.py feature_local_bin)
    ls = gb - span
    ge_def = jnp.where(ls >= defbin, 1, 0)
    fb_b = jnp.where((ls >= 0) & (ls < nbins - 1), ls + ge_def, defbin)
    fb = jnp.where(bundled_i > 0, fb_b, gb)

    has_mz_i = iv[T_HASMZ:T_HASMZ + 1, :]
    mzbin = iv[T_MZBIN:T_MZBIN + 1, :]
    is_nan_i = has_nan_i * jnp.where(fb == nanbin, 1, 0)
    is_mz_i = has_mz_i * jnp.where(fb == mzbin, 1, 0)
    le_thr = jnp.where(fb <= thr, 1, 0)
    go_left_i = jnp.where(is_nan_i + is_mz_i > 0, defleft_i, le_thr)
    return (chosen_i, newid, fb, go_left_i,
            iv[T_SLOT_L:T_SLOT_L + 1, :], iv[T_SLOT_R:T_SLOT_R + 1, :],
            iv[T_SLOT_KEEP:T_SLOT_KEEP + 1, :])


def _route_rows(bins_ref, leaf_ref, tabs_ref, bits_ref, newleaf_ref, *,
                T, B, L, GW, has_cat, f32_dots, u8_layout, K):
    """Route one (K, T) block of rows through this round's split tables:
    writes every row's new leaf id to newleaf_ref and returns (each class's
    (1, T) histogram slot, -1 for none; the block's bins widened to int32 in
    the u8 layout, None otherwise or where the table is too wide to widen
    whole).  `bins_ref` holds EVERY group's bins of the block: a split may
    test any of them."""
    i32, f32 = jnp.int32, jnp.float32
    # interpret mode on CPU: XLA:CPU's Eigen DotThunk rejects bf16 at some
    # shapes; f32 operands carry the identical (bf16-rounded) values, so the
    # contraction results match the TPU MXU's bf16 x bf16 -> f32 exactly
    bf16 = f32 if f32_dots else jnp.bfloat16

    # ---------------- route (per class; the bin one-hot below is shared) ---
    # K > 1 is the BATCHED MULTICLASS path: K class trees grow in lockstep,
    # so the kernel routes each row through K per-class split tables and
    # accumulates one widened (m_rows, 2*S*K) histogram block — the
    # class-independent bin one-hot is built ONCE and contracted against
    # the class x slot channel axis (vs K separate kernel launches each
    # rebuilding the one-hot).
    l_iota = jax.lax.broadcasted_iota(i32, (L, T), 0)
    bins32 = (bins_ref[...].astype(i32)                      # (G_pad, T)
              if u8_layout and bins_ref.shape[0] <= WIDE_ROUTE_GROUPS
              else None)
    # FOLDED multiclass route gather (docs/PERF.md lever): the K per-class
    # (NUM_TAB, L) @ (L, T) table dots merge into ONE block-diagonal
    # (K*NUM_TAB, K*L) @ (K*L, T) dot — class k's leaf one-hot occupies
    # rows [k*L, (k+1)*L) and the LHS zero-masks tabs outside its column
    # band, so every output element still sums exactly one 1.0 * value
    # product (bit-exact; zero products add exact zeros).  Gated on the
    # operands fitting VMEM; the per-class loop remains the fallback.
    fold_routes = (K > 1 and K * L * T * 2 <= 8 * 2 ** 20
                   and NUM_TAB * K * K * L * 4 <= 4 * 2 ** 20)
    if fold_routes:
        kl_iota = jax.lax.broadcasted_iota(i32, (K * L, T), 0)
        lid_all = jnp.concatenate(
            [jnp.broadcast_to(leaf_ref[k:k + 1, :] + k * L, (L, T))
             for k in range(K)], axis=0)
        oh_all = (kl_iota == lid_all).astype(bf16)           # (K*L, T)
        col_iota = jax.lax.broadcasted_iota(i32, (NUM_TAB, K * L), 1)
        bd = jnp.concatenate(
            [jnp.where((col_iota >= k * L) & (col_iota < (k + 1) * L),
                       tabs_ref[...], 0.0) for k in range(K)], axis=0)
        vals_all = jax.lax.dot_general(
            bd, oh_all, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                      # (K*NUM_TAB, T)
    slots = []                                               # per-class (1,T)
    for k in range(K):  # static unroll
        lid = leaf_ref[k:k + 1, :]                           # (1, T) i32
        if fold_routes:
            # NUM_TAB row slices stay sublane-aligned (24 = 3 x 8); the
            # categorical-bits dot below rebuilds its per-class one-hot
            # instead of slicing oh_all at the unaligned k*L offset
            leaf_oh = None
            vals = vals_all[k * NUM_TAB:(k + 1) * NUM_TAB, :]
        else:
            leaf_oh = (l_iota == lid).astype(bf16)           # (L, T)
            vals = jax.lax.dot_general(
                tabs_ref[:, k * L:(k + 1) * L], leaf_oh,
                (((1,), (0,)), ((), ())),
                preferred_element_type=f32)                  # (NUM_TAB, T)
        # flags stay i32 (0/1) throughout — Mosaic cannot handle i1 vectors
        # as select OPERANDS (i8<->i1 truncation); predicates are fresh
        # comparisons.  The per-table routing math is the shared
        # _route_step (also the replay kernel's step — never drifts).
        iv = vals.astype(i32)
        (chosen_i, newid, fb, go_left_i,
         slot_l1, slot_r1, slot_k1) = _route_step(iv, bins_ref, bins32,
                                                  GW, T, u8_layout)
        is_cat_i = iv[T_ISCAT:T_ISCAT + 1, :]
        if has_cat:
            # per-row categorical bit: (Bmax, L) @ (L, T) one-hot, pick fb
            if leaf_oh is None:
                leaf_oh = (l_iota == lid).astype(bf16)       # (L, T)
            br = jax.lax.dot_general(
                bits_ref[:, k * L:(k + 1) * L].astype(bf16), leaf_oh,
                (((1,), (0,)), ((), ())),
                preferred_element_type=f32)                  # (B, T)
            b_iota_c = jax.lax.broadcasted_iota(i32, (B, T), 0)
            cat_bit = jnp.sum(jnp.where(b_iota_c == fb, br, 0.0), axis=0,
                              keepdims=True)
            go_left_cat = jnp.where(cat_bit > 0.5, 1, 0)
            go_left_i = jnp.where(is_cat_i > 0, go_left_cat, go_left_i)

        new_lid = jnp.where(chosen_i * (1 - go_left_i) > 0, newid, lid)
        slot1 = jnp.where(chosen_i > 0,
                          jnp.where(go_left_i > 0, slot_l1, slot_r1), slot_k1)
        if _ABLATE == "dblroute":    # perf probe: one extra route gather
            leaf_oh2 = (l_iota == lid + L).astype(bf16)
            vals2 = jax.lax.dot_general(
                tabs_ref[:, k * L:(k + 1) * L], leaf_oh2,
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            new_lid = new_lid + vals2[0:1, :].astype(i32)
        newleaf_ref[k:k + 1, :] = new_lid
        slots.append(slot1 - 1)
    return slots, bins32


def _count_slots(cnt_ref, w_ref, slots, *, T, S, K, f32_dots):
    """cnt_ref += this block's exact per-slot data counts.  Returns the slot
    iota and one-hots, which the histogram contraction shares."""
    i32, f32 = jnp.int32, jnp.float32
    bf16 = f32 if f32_dots else jnp.bfloat16
    s_iota = jax.lax.broadcasted_iota(i32, (S, T), 0)
    slot_ohs = [(s_iota == slot).astype(bf16) for slot in slots]  # (S, T) ea
    slot_oh = (jnp.concatenate(slot_ohs, axis=0) if K > 1
               else slot_ohs[0])                             # (S*K, T)
    # EXACT per-slot data counts (one tiny (1,T)x(T,S*K) dot) — needed by
    # every variant including route-only rounds: they become the model's
    # leaf_count values (DataPartition::leaf_count,
    # serial_tree_learner.cpp:798)
    cnt_row = w_ref[2 * K:2 * K + 1, :]
    cnt_ref[0:1, :] += jax.lax.dot_general(
        cnt_row.astype(bf16), slot_oh, (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
    return s_iota, slot_ohs


def _wsplit(w):
    """Split f32 weights into (hi, lo) bf16 parts: w ~= hi + lo exactly enough."""
    hi = w.astype(jnp.bfloat16)
    lo = (w - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def onehot_build_kind(bins_dtype, int_weights: bool, bin_buckets=None) -> str:
    """How the 64-slot and tiled passes of such a program build their bin
    one-hot: "words" — int8, four rows a 32-bit word, byte-parallel
    (_onehot_words) — where the bins are in the u8 layout, the weights are
    integer-valued and the M-axis is uniform; "compare" (an int32 key
    against an iota, then a convert: _onehot_compare) otherwise: the
    bucketed axis (its runs are not word-aligned in the table), float
    weights, the packed-word layout."""
    return ("words" if int_weights and bins_dtype == jnp.int8
            and bin_buckets is None else "compare")


def onehot_rows(num_groups: int, B: int, words: bool) -> int:
    """Rows of the uniform M-axis over `num_groups` groups of B bins: the
    word form's cover whole words of four groups."""
    return (-(-num_groups // 4) * 4 if words else num_groups) * B


def onehot_word_major(axis_groups: int) -> bool:
    """The order _onehot_words gives the rows of an M-axis over
    `axis_groups` groups (onehot_rows' whole words of four: a table's, or a
    tile's): word-major unless they fill whole 32-group word arrays, where
    they keep the compare-built b-major order."""
    return axis_groups % 32 != 0


def _onehot_compare(bins_ref, bins32, *, T, G, B, u8_layout, bin_buckets,
                    m_rows):
    """The (m_rows, T) boolean bin one-hot of the block's first G groups,
    B-MAJOR — row r = b * G + g — via key = bin * G + g tiled B times
    against a flat 2-D iota (~40% of kernel time used to go into the
    (G, B, T) 3-D broadcast-compare layout this replaced)."""
    i32 = jnp.int32
    if u8_layout:
        if bins32 is None:
            bins32 = bins_ref[...].astype(i32)
        bins_G = bins32[:G, :]                               # (G, T) no unpack
    else:
        # unpack the 4-per-word packed group bins
        rows = []
        for g in range(G):  # static unroll
            word_g = bins_ref[g // 4:g // 4 + 1, :]
            rows.append(jax.lax.shift_right_logical(word_g, (g % 4) * 8) & 0xFF)
        bins_G = jnp.concatenate(rows, axis=0)               # (G, T)
    # (tried and left: a per-bin compare-block construct — B int8 compares
    # of (G, T) concatenated — measured 14% SLOWER than this key form on the
    # earlier rig: the 64-block concat relayout costs more than the
    # (B*G, T) key/iota compare)
    if bin_buckets is None:
        g_iota = jax.lax.broadcasted_iota(i32, (G, T), 0)
        key = bins_G * G + g_iota                            # (G, T)
        key_t = jnp.concatenate([key] * B, axis=0)           # (B*G, T) tiled
        r_iota = jax.lax.broadcasted_iota(i32, (B * G, T), 0)
        oh_match = key_t == r_iota        # (B*G, T) bool, row r = b * G + g
        if _ABLATE == "dblcon":  # additive probe: one extra (never-hit) construct
            key_t2 = jnp.concatenate([key + B * G] * B, axis=0)
            oh_match = oh_match | (key_t2 == r_iota)
        return oh_match
    # BUCKETED M-axis: groups are laid out in runs of equal bin-bucket
    # size (binning.device_group_order), and each run contributes
    # Bk * Gk8 one-hot rows — M = sum of rounded per-group bin counts
    # instead of G * Bmax, which is where low-cardinality features'
    # histogram cost actually goes (the reference's scatter never paid
    # per-bin; this is the matmul formulation's equivalent).  Row
    # r = roff_k + b * Gk8 + g_local; the key trick is per run.  Gk
    # pads to a sublane multiple (8) with never-matching keys so the
    # Bk tiled concat pieces stay aligned.
    parts = []
    goff = roff = 0
    for Bk, Gk in bin_buckets:
        Gk8 = bucket_group_pad(Gk)
        sub = bins_G[goff:goff + Gk, :]                      # (Gk, T)
        # real keys first, then pad rows pinned to -1 (below every
        # r_iota value). Padding the BIN value instead (1 << 24) only
        # worked while (1 << 24) * Gk8 stayed inside int32 — at
        # Gk8 >= 128 that product wraps and a pad row could alias a
        # real histogram row.
        gi_k = jax.lax.broadcasted_iota(i32, (Gk, T), 0)
        key_k = sub * Gk8 + gi_k + roff
        if Gk8 > Gk:
            key_k = jnp.concatenate(
                [key_k, jnp.full((Gk8 - Gk, T), -1, i32)], axis=0)
        parts.extend([key_k] * Bk)
        goff += Gk
        roff += Bk * Gk8
    if m_rows > roff:
        parts.append(jnp.full((m_rows - roff, T), -1, i32))
    key_t = jnp.concatenate(parts, axis=0)                   # (m_rows, T)
    r_iota = jax.lax.broadcasted_iota(i32, (m_rows, T), 0)
    return key_t == r_iota


def _onehot_words(bins_ref, *, T, G, B):
    """The int8 bin one-hot of the block's first G groups, built four rows
    at a time in the 32-bit words the u8 layout stores the bins in (word r
    of a 32-group block holds groups 4r..4r+3 of one row, as
    _factored_accumulate reads them): no int32 key, no (M, T) int32 compare
    and no int32 -> int8 pack.  Bins are under 128 in this layout, so is a
    byte's XOR with a bin, and 0x80 - x has its top bit set in exactly the
    bytes of x that are 0 with no borrow between bytes: four word operations
    for 32 rows.  Returned as pieces of about 256 rows, a dot each, every
    piece a whole number of (8, T) word arrays (nothing is concatenated at a
    7- or 17-row pitch), in the order onehot_word_major() says:

    - WORD-MAJOR, r = (g // 4) * 4B + 4b + g % 4: one word row broadcast
      over 8 sublanes against eight bins a word array, a (4B, T) piece a
      word row of four groups (the last word's padding groups included:
      4 * ceil(G / 4) * B rows);
    - where the axis' groups fill whole 32-group word arrays (the M-tiles)
      B-MAJOR, r = b * G + g, the compare-built order: every word array
      against one bin in all its bytes, so the caller's unflatten of the
      tiles' large block is the one it always was (the word-major transpose
      of six (16, 8192, 128) blocks a tree measured 3.7 ms a tree dearer
      in XLA, PERF.md section 6, PR 36)."""
    i32 = jnp.int32

    def match(x):
        """0x01 in every byte of x (bytes under 128) that is 0, else 0x00;
        under the dblcon probe one extra construct besides (never hit while
        B <= 64: no bin has bit 6 set)."""
        oh = jax.lax.shift_right_logical(_TOPS - x, 7) & _BYTES
        if _ABLATE == "dblcon":
            oh = oh | (jax.lax.shift_right_logical(
                _TOPS - (x ^ (0x40 * _BYTES)), 7) & _BYTES)
        return oh

    W = -(-G // 4)
    blocks = [pltpu.bitcast(bins_ref[a * 32:(a + 1) * 32, :], i32)  # (8, T)
              for a in range(-(-W // 8))]   # static unroll: 32 groups each
    if not onehot_word_major(4 * W):
        per = max(1, 64 // W)                                # bins a piece
        return [pltpu.bitcast(jnp.concatenate(
            [match(words ^ (b * _BYTES))
             for b in range(p * per, (p + 1) * per) for words in blocks],
            axis=0), jnp.int8) for p in range(B // per)]     # (per * 4W, T)
    sub = jax.lax.broadcasted_iota(i32, (8, T), 0)
    bins_b = [(sub + 8 * j) * _BYTES for j in range(B // 8)]  # bins 8j..8j+7
    pieces = []
    for w in range(W):
        row = jnp.broadcast_to(blocks[w // 8][w % 8:w % 8 + 1, :], (8, T))
        pieces.append(pltpu.bitcast(
            jnp.concatenate([match(row ^ b) for b in bins_b], axis=0),
            jnp.int8))                                       # (4B, T)
    return pieces


def _accumulate_hist(hist_ref, bins_ref, bins32, w_ref, slots, s_iota,
                     slot_ohs, *, T, G, B, S, two_pass, int_weights, f32_dots,
                     u8_layout, bin_buckets, m_rows, K):
    """hist_ref += the one-hot contraction of this block's G groups (the
    first G rows of `bins_ref`; `bins32` their widening in the u8 layout
    where a caller has it) against the rows' slot-folded grad/hess."""
    i32, f32 = jnp.int32, jnp.float32
    bf16 = f32 if f32_dots else jnp.bfloat16
    w2 = w_ref[0:2 * K, :]                                   # (2K, T) f32
    w_hi, w_lo = _wsplit(w2)

    # the bin-match one-hot: the int path's own word form where
    # onehot_build_kind() has it, the boolean shared by the int and float
    # contraction paths otherwise
    words = onehot_build_kind(bins_ref.dtype, int_weights,
                              bin_buckets) == "words"
    if not words:
        oh_match = _onehot_compare(bins_ref, bins32, T=T, G=G, B=B,
                                   u8_layout=u8_layout,
                                   bin_buckets=bin_buckets, m_rows=m_rows)

    if int_weights:
        # Quantized-gradient histograms (reference: gradient_discretizer.cpp
        # + the int8/int16 ConstructHistogram variants, dense_bin.hpp): the
        # grow layer passes integer-valued grad/hess rows, the contraction
        # runs on the int8 MXU (~25% faster than bf16 at these shapes), and
        # int32 accumulation makes the histogram sums EXACT.
        # build A in i32 (Mosaic cannot legalize i8*i8 multiplies), then
        # convert the (2*S*K, T) operand to int8 once; class-major rows
        # j = k*2S + c*S + s match the caller's unflatten
        slot_ohs_i = [(s_iota == slot).astype(i32) for slot in slots]
        w_i = jnp.round(w2).astype(i32)                      # int-valued rows
        A_i = jnp.concatenate(
            [w_i[2 * k + c:2 * k + c + 1, :] * slot_ohs_i[k]
             for k in range(K) for c in range(2)], axis=0)
        if _ABLATE == "nohist":      # int-path probe: no one-hot, no dot
            hist_ref[...] += jnp.sum(A_i, axis=1)[None, :]
            return

        def dot_i(oh_i, A_8):
            if f32_dots:
                # CPU interpret: f32 products of |v| <= 127 ints are exact
                # and per-block sums stay below 2^24, so rounding back is
                # lossless
                return jax.lax.dot_general(
                    oh_i.astype(f32), A_i.astype(f32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=f32).astype(i32)
            return jax.lax.dot_general(
                oh_i, A_8, (((1,), (1,)), ((), ())),
                preferred_element_type=i32)

        def A_int8():
            if _ABLATE == "noA":         # int-path probe: constant A operand
                return jnp.full((2 * S, T), 1, jnp.int8)
            return A_i.astype(jnp.int8)

        if words:
            # a piece of about 256 rows and its dot at a time: one piece's
            # word arithmetic runs under another's dot (measured: a second
            # construct a piece adds nothing to a pass, PERF.md section 6,
            # PR 36), and no (M, T) operand is held
            A_8 = A_int8()
            if _ABLATE == "constoh":     # int-path probe: constant operands
                # (one value a piece, or the compiler keeps one dot of all)
                pieces = [jnp.full((4 * B, T), w + 1, jnp.int8)
                          for w in range(m_rows // (4 * B))]
            else:
                pieces = _onehot_words(bins_ref, T=T, G=G, B=B)
            off = 0
            for oh_i in pieces:
                hist_ref[off:off + oh_i.shape[0], :] += dot_i(oh_i, A_8)
                off += oh_i.shape[0]
            return
        if _ABLATE == "constoh":         # int-path probe: constant operand
            oh_i = jnp.full((B * G, T), 1, jnp.int8)
        else:
            oh_i = oh_match if f32_dots else oh_match.astype(jnp.int8)
        A_8 = A_int8()
        hist_ref[...] += dot_i(oh_i, A_8)
        if _ABLATE == "dbldot_i8":       # additive probe: one extra int8 dot
            d2 = jax.lax.dot_general(
                oh_i, jnp.flip(A_8, 1), (((1,), (1,)), ((), ())),
                preferred_element_type=i32)
            # |d2| < 2^30 so this adds exactly 0, but the compiler
            # cannot prove it — the extra dot survives DCE
            hist_ref[...] += jnp.abs(d2) // jnp.int32(2 ** 30)
        return

    # (histograms carry only grad/hess — per-bin counts are estimated from
    # hessians at split-find time like the reference; exact per-slot counts
    # came from the hoisted cnt dot above)
    def build_A(w):
        # (1, T) x (S, T) broadcast-multiplies + sublane concat; the 3-D
        # broadcast form lowers to a much slower relayout. Class-major rows
        # j = k*2S + c*S + s (matches the caller's unflatten).
        return jnp.concatenate(
            [w[2 * k + c:2 * k + c + 1, :].astype(bf16) * slot_ohs[k]
             for k in range(K) for c in range(2)],
            axis=0)                                          # (2*S*K, T)

    A_hi = build_A(w_hi)
    if _ABLATE == "dblA":        # perf probe: one extra A-operand build
        A_hi = A_hi + build_A(w_lo) * bf16(0.0)
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
    # ONE (G*B, T) @ (T, 3S) contraction per block: per-group (B, T) dots
    # have M=B=64 — half an MXU tile — so merging groups into a single
    # one-hot doubles MXU utilisation (the dominant cost of training).
    oh = oh_match.astype(bf16)
    if _ABLATE == "nohist":      # fixed costs only (route + A + writes)
        hist_ref[...] += jnp.sum(A_hi, axis=1)[None, :]
        return
    if _ABLATE == "constoh":     # dot with a constant operand (no one-hot)
        oh = jnp.full((G * B, T), 0.5, bf16)
    if _ABLATE == "dbldot":      # perf probe: one extra bf16 dot
        hist_ref[...] += dot(oh, build_A(w_lo)) * 1e-30
    if _ABLATE == "dbldot_i8":   # perf probe: one extra int8 dot
        oh_i8 = oh_match.astype(jnp.int8)
        a_i8 = build_A(w_lo).astype(jnp.int8)
        d2 = jax.lax.dot_general(oh_i8, a_i8,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.int32)
        hist_ref[...] += d2.astype(f32) * 1e-30
    if two_pass:
        A_lo = build_A(w_lo)
        hist_ref[...] += dot(oh, A_hi) + dot(oh, A_lo)
    else:
        # single-precision weights (the reference's GPU default,
        # gpu_use_dp=false): one bf16 pass, f32 accumulation
        hist_ref[...] += dot(oh, A_hi)


def _split_live(refs, live_rows: bool):
    """A one-tile pass kernel's refs -> (the scalar-prefetched count of live
    row blocks, or None where the pass streams every block; the rest)."""
    return (refs[0], refs[1:]) if live_rows else (None, refs)


def _pass_step(rb_ref, zero, work, route=lambda: ()):
    """One grid step of a one-tile pass: the accumulators zeroed at step 0
    (`zero`), the block routed (`route`, where the pass routes) and `work`
    done on what the route returns.  Where the pass streams every block
    (rb_ref None) the route comes first; over a compact view's live blocks
    the zeroing stands outside the guard that skips the blocks past them."""
    if rb_ref is None:
        routed = route()
        zero()
        work(*routed)
        return
    zero()
    pl.when(pl.program_id(0) < rb_ref[0])(lambda: work(*route()))


def _route_hist_kernel(*refs, T, G, B, S, L, GW,
                       has_cat: bool, two_pass: bool = True,
                       int_weights: bool = False, f32_dots: bool = False,
                       u8_layout: bool = False, with_hist: bool = True,
                       bin_buckets=None, m_rows: int = 0, K: int = 1,
                       slot_out: bool = False, live_rows: bool = False):
    """The pass over a table of one M-tile: route, count, contract, one grid
    axis over the row blocks.  Without with_hist it only routes and counts;
    with slot_out it then also writes each row's histogram slot (-1: none)
    to a last (K, T) output, for _hist_tiles_kernel's sweeps.  With
    live_rows (a compact view's pass) only the first `rb_ref[0]` blocks are
    routed, counted and contracted."""
    rb_ref, refs = _split_live(refs, live_rows)
    bins_ref, leaf_ref, w_ref, tabs_ref, bits_ref, newleaf_ref, *outs = refs
    if slot_out:
        *outs, slot_ref = outs
    if with_hist:
        hist_ref, cnt_ref = outs
    else:
        # route-only variant: no histogram output ref exists at all, so the
        # (G*B, 2*S*K) VMEM-resident block is never allocated
        hist_ref, (cnt_ref,) = None, outs
    b = pl.program_id(0)

    def route():
        return _route_rows(
            bins_ref, leaf_ref, tabs_ref, bits_ref, newleaf_ref, T=T, B=B,
            L=L, GW=GW, has_cat=has_cat, f32_dots=f32_dots,
            u8_layout=u8_layout, K=K)

    # ---------------- histogram ----------------
    def zero():
        @pl.when(b == 0)
        def _():
            if with_hist:
                hist_ref[...] = jnp.zeros_like(hist_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def count_and_contract(slots, bins32):
        s_iota, slot_ohs = _count_slots(cnt_ref, w_ref, slots, T=T, S=S, K=K,
                                        f32_dots=f32_dots)
        if slot_out:
            for k in range(K):
                slot_ref[k:k + 1, :] = slots[k]
        if not with_hist:
            # route-only round (a tree's LAST split round: the children's
            # histograms would never be scanned, so the dominant one-hot
            # contraction — and the whole VMEM-resident histogram block —
            # is dropped)
            return
        _accumulate_hist(hist_ref, bins_ref, bins32, w_ref, slots, s_iota,
                         slot_ohs, T=T, G=G, B=B, S=S, two_pass=two_pass,
                         int_weights=int_weights, f32_dots=f32_dots,
                         u8_layout=u8_layout, bin_buckets=bin_buckets,
                         m_rows=m_rows, K=K)

    _pass_step(rb_ref, zero, count_and_contract, route)


def _hist_tiles_kernel(bins_ref, slot_ref, w_ref, hist_ref, *, T, Gt, B, S,
                       two_pass, int_weights, f32_dots, u8_layout, K):
    """The contraction of a table of several M-tiles: grid (tile j, row
    block b), rows innermost, so tile j's (Gt*B, 2*S*K) histogram block
    stays in VMEM over its whole sweep of the rows.  `bins_ref` holds tile
    j's groups only; every sweep contracts against the same slots, which the
    route-only pass before it wrote."""
    i32, f32 = jnp.int32, jnp.float32
    bf16 = f32 if f32_dots else jnp.bfloat16

    @pl.when(pl.program_id(1) == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    slots = [slot_ref[k:k + 1, :] for k in range(K)]
    s_iota = jax.lax.broadcasted_iota(i32, (S, T), 0)
    slot_ohs = [(s_iota == slot).astype(bf16) for slot in slots]
    _accumulate_hist(hist_ref, bins_ref, None, w_ref, slots, s_iota,
                     slot_ohs, T=T, G=Gt, B=B, S=S, two_pass=two_pass,
                     int_weights=int_weights, f32_dots=f32_dots,
                     u8_layout=u8_layout, bin_buckets=None, m_rows=Gt * B,
                     K=K)


# The ROOT pass has one slot: every row is in leaf 0 and nothing is routed.
# The one-hot formulation above would contract its (M, T) bin one-hot against
# a 2-column operand that the MXU pads to a 128-column tile — a full pass's
# M*128 MACs a row for 2/128 of a full pass's result.  The factored form
# splits the bin, b = hi * 8 + lo, and for a group of 16 features contracts
# over the rows t
#
#   LHS[(c, hi, f), t] = w_int[c, t] * 1[bin_f[t] >> 3 == hi]   2*H*16 rows
#   RHS[(lo, f'),  t] = 1[bin_f'[t] & 7 == lo]                   128 rows
#
# on the int8 MXU into an int32 (2*H*16, 128) block that stays in VMEM over
# the grid; the blocks on the diagonal f == f' are the root histogram, the
# others are the price of a dense unit and are dropped outside the kernel.
# That is G*B*32 MACs a row against G*B*128.
#
# Both operands are built four int8 rows at a time, in the 32-bit words the
# u8 layout already stores them in (word r of a block holds the bins of
# groups 4r..4r+3 of one row; pltpu.bitcast reads and writes that packing
# for free): digit tests are byte-parallel arithmetic on words, there is no
# int32 one-hot and no int32 -> int8 pack.  Measured on the v5e the kernel
# then runs at the MXU's pace (PERF.md section 6, PR 28).
# features a group: 4 words x 4 bytes, half of a word array's 8 sublanes (the
# kernel's sublane arithmetic is written for it), and 16 x 8 low digits make
# the RHS one 128-row MXU tile
ROOT_GF = 16


def root_pass_kind(bins_dtype, int_weights: bool, num_class: int = 1) -> str:
    """Which formulation the root histogram pass of such a program takes:
    "factored" when the weights are integer-valued (exact int32 sums, so the
    result is bit-identical whatever the formulation), the bins are in the
    u8 layout and one tree grows at a time; "onehot" (the S = 1 call of the
    64-slot kernel) otherwise."""
    return ("factored" if int_weights and bins_dtype == jnp.int8
            and num_class == 1 else "onehot")


def _weight_bytes(w_ref, slot=None, S: int = 1):
    """The (1, T) int32 rows the factored LHS's digit tests are multiplied
    by: grad, then hess, as the int8 byte a word's matching lanes take.  With
    `slot` (each row's histogram slot, -1 for none) a row a (c, s): the byte
    where the row sits in slot s, 0 elsewhere — the slot mask on the weighted
    operand."""
    wb = jnp.round(w_ref[0:2, :]).astype(jnp.int32) & 0xFF   # (2, T)
    if slot is None:
        return [wb[c:c + 1, :] for c in range(2)]
    return [jnp.where(slot == s, wb[c:c + 1, :], 0)
            for c in range(2) for s in range(S)]


def _factored_accumulate(hist_ref, bins_ref, w_rows, *, T, NG, HP, f32_dots):
    """hist_ref += the factored contraction of the block's NG 16-feature
    groups: group g's rows [g * R, (g + 1) * R), R = len(w_rows) * HP * 16,
    are (w row, hi, f) against the columns (lo, f')."""
    i32 = jnp.int32

    def is_zero(x):
        """0x01 in every byte of x (bytes 0..15) that is 0, else 0x00."""
        return jax.lax.shift_right_logical(0x10101010 - x, 4) & _BYTES

    R = len(w_rows) * HP * ROOT_GF
    top = jax.lax.broadcasted_iota(i32, (8, T), 0) < 4       # sublanes 0..3
    # a word array (8, T) holds TWO digits of one group's 16 features: the
    # even digit in sublanes 0..3, the odd one in 4..7; pair[p] is the digit
    # pair (2p, 2p + 1) in every byte, to test such an array against
    odd = jnp.where(top, 0, _BYTES)
    pair = [odd + 2 * p * _BYTES for p in range(max(HP // 2, 4))]
    for a in range(-(-NG // 2)):  # static unroll: 32 features a word array
        words = pltpu.bitcast(bins_ref[a * 32:(a + 1) * 32, :], i32)  # (8, T)
        hi = jax.lax.shift_right_logical(words, 3) & 0x0F0F0F0F
        lo = words & 0x07070707
        hi_sw, lo_sw = pltpu.roll(hi, 4, 0), pltpu.roll(lo, 4, 0)
        for half in range(min(2, NG - 2 * a)):
            g = 2 * a + half
            # this group's 4 words in both sublane halves
            mine = top if half == 0 else ~top
            hi_g = jnp.where(mine, hi, hi_sw)
            lo_g = jnp.where(mine, lo, lo_sw)
            # the digit tests are built once and shared by every w row
            hi_oh = [is_zero(hi_g ^ pair[p]) for p in range(HP // 2)]
            lhs = jnp.concatenate([oh * w for w in w_rows for oh in hi_oh],
                                  axis=0)                    # (R/4, T) words
            rhs = jnp.concatenate([is_zero(lo_g ^ pair[q]) for q in range(4)],
                                  axis=0)                    # (32, T) words
            lhs8 = pltpu.bitcast(lhs, jnp.int8)              # (R, T)
            rhs8 = pltpu.bitcast(rhs, jnp.int8)              # (128, T)
            if f32_dots:
                # CPU interpret: as the 64-slot kernel's int path — exact
                d = jax.lax.dot_general(
                    lhs8.astype(jnp.float32), rhs8.astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(i32)
            else:
                d = jax.lax.dot_general(
                    lhs8, rhs8, (((1,), (1,)), ((), ())),
                    preferred_element_type=i32)
            hist_ref[g * R:(g + 1) * R, :] += d


def _root_hist_kernel(*refs, T, NG, HP, f32_dots, row_axis=0,
                      live_rows: bool = False):
    rb_ref, (bins_ref, w_ref, hist_ref) = _split_live(refs, live_rows)

    def zero():
        @pl.when(pl.program_id(row_axis) == 0)
        def _():
            hist_ref[...] = jnp.zeros_like(hist_ref)

    def contract():
        _factored_accumulate(hist_ref, bins_ref, _weight_bytes(w_ref), T=T,
                             NG=NG, HP=HP, f32_dots=f32_dots)

    _pass_step(rb_ref, zero, contract)


# A round that splits one or two leaves has one or two live slots, and the
# 64-slot pass would again pay a full 128-column tile for 2 or 4 columns.
# The SMALL-SLOT pass is the root's contraction with a slot mask on the
# weighted operand, LHS rows (c, s, hi, f) = 2*S*H*16 a group of 16 features:
# S*G*B*32 MACs a row, a quarter and a half of a full pass.  Unlike the root
# its rows are routed first.  At S = 4 the form no longer wins.
SMALL_PASS_SLOTS = 2


def small_pass_index(live_slots, bins_dtype, int_weights: bool,
                     num_slots: int, num_class: int = 1):
    """Which pass a histogram round of `live_slots` (traced: the leaves it
    splits; live slots are always 0..live_slots-1) takes, as the index of
    route_and_hist_live's switch: s in 1..min(SMALL_PASS_SLOTS, num_slots)
    is the small-slot pass of s slots, 0 the `num_slots` one-hot pass.  None
    where the program has no small-slot pass: where the root would not be
    factored either (root_pass_kind)."""
    if (live_slots is None
            or root_pass_kind(bins_dtype, int_weights, num_class)
            != "factored"):
        return None
    return jnp.where(
        (live_slots >= 1) & (live_slots <= min(SMALL_PASS_SLOTS, num_slots)),
        live_slots, 0).astype(jnp.int32)


def _route_small_hist_kernel(*refs, T, B, S, L, NG, HP, has_cat, f32_dots,
                             live_rows: bool = False):
    """The small-slot pass over a table of one M-tile: route, count, and the
    factored contraction of the rows' slot-masked grad/hess, the bins block
    read once and no slot leaving the kernel.  live_rows as
    _route_hist_kernel's."""
    rb_ref, refs = _split_live(refs, live_rows)
    (bins_ref, leaf_ref, w_ref, tabs_ref, bits_ref, hist_ref, newleaf_ref,
     cnt_ref) = refs
    b = pl.program_id(0)

    def route():
        (slot,), _ = _route_rows(
            bins_ref, leaf_ref, tabs_ref, bits_ref, newleaf_ref, T=T, B=B,
            L=L, GW=bins_ref.shape[0], has_cat=has_cat, f32_dots=f32_dots,
            u8_layout=True, K=1)
        return (slot,)

    def zero():
        @pl.when(b == 0)
        def _():
            hist_ref[...] = jnp.zeros_like(hist_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def count_and_contract(slot):
        _count_slots(cnt_ref, w_ref, [slot], T=T, S=S, K=1,
                     f32_dots=f32_dots)
        _factored_accumulate(hist_ref, bins_ref,
                             _weight_bytes(w_ref, slot, S), T=T, NG=NG,
                             HP=HP, f32_dots=f32_dots)

    _pass_step(rb_ref, zero, count_and_contract, route)


def _small_hist_tiles_kernel(bins_ref, slot_ref, w_ref, hist_ref, *, T, S,
                             NG, HP, f32_dots):
    """The small-slot contraction of a table of several M-tiles, grid (tile,
    row block) as _hist_tiles_kernel's: of rows to which the route-only pass
    has given slots."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    _factored_accumulate(hist_ref, bins_ref,
                         _weight_bytes(w_ref, slot_ref[0:1, :], S), T=T,
                         NG=NG, HP=HP, f32_dots=f32_dots)


def _one_tile_call(kernel, operands, *, T, ins, outs, out_shape,
                   row_blocks=None, leaf=None):
    """The pallas_call of a pass over a table of one M-tile, grid (row
    block,).  `ins` / `outs` give each block as (shape, walks): a block that
    walks the row axis is block (0, b) at step b, any other the whole array.

    `row_blocks` ((1,) int32: how many row blocks of a compact view hold
    in-bag rows, scalar-prefetched) stops a walking block at the last of
    them: a step past it fetches nothing new, and `kernel` (called with
    live_rows=True) does no work there, so the pass costs the in-bag rows
    and not the view's capacity.  `leaf` = (operand, result) indices of the
    leaf ids, aliased, so the columns past the live blocks keep the ids
    they came in with.  Without row_blocks the call is the plain grid of
    every block."""
    n_pad = operands[0].shape[1]
    if row_blocks is None:
        def at(walks):
            return (lambda b: (0, b)) if walks else (lambda b: (0, 0))
    else:
        def at(walks):
            return ((lambda b, rb: (0, jnp.minimum(b, rb[0] - 1))) if walks
                    else (lambda b, rb: (0, 0)))
    in_specs = [pl.BlockSpec(shape, at(walks)) for shape, walks in ins]
    out_specs = ([pl.BlockSpec(shape, at(walks)) for shape, walks in outs]
                 if isinstance(outs, list) else pl.BlockSpec(outs[0],
                                                             at(outs[1])))
    compiler_params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    if row_blocks is None:
        return pl.pallas_call(
            kernel, grid=(n_pad // T,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=compiler_params, interpret=pallas_interpret(),
        )(*operands)
    return pl.pallas_call(
        functools.partial(kernel, live_rows=True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_pad // T,), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases={} if leaf is None else {1 + leaf[0]: leaf[1]},
        compiler_params=compiler_params, interpret=pallas_interpret(),
    )(row_blocks, *operands)


def _factored_digits(bmax: int):
    """High digits of a bin, b = hi * 8 + lo, padded to go two a word
    array."""
    H = -(-bmax // 8)
    return H + (H & 1)


def _factored_unpack(out, NG: int, S: int, HP: int, G: int, bmax: int):
    """A factored call's int32 rows (group, c, s, hi, f) x columns (lo, f')
    -> the (S, G, bmax, 2) histogram: keep f == f'."""
    GF = ROOT_GF
    same = jnp.eye(GF, dtype=bool)[:, None, :]
    diag = jnp.sum(jnp.where(same, out.reshape(NG, 2, S, HP, GF, 8, GF), 0),
                   axis=6)                              # (NG, 2, S, HP, GF, 8)
    hist = diag.transpose(2, 0, 4, 3, 5, 1).reshape(S, NG * GF, HP * 8, 2)
    return hist[:, :G, :bmax, :]


def _root_hist_factored(bins_T, w_T, bmax: int, num_groups: int,
                        block_rows: int, tile_groups: int = 0,
                        row_blocks=None):
    """(1, G, bmax, 2) int32 root histogram of integer-valued grad/hess rows
    over u8-layout bins: what route_and_hist(..., num_slots=1) returns for
    rows that all sit in one leaf, by the factored contraction.  Rows
    past the data carry zero weights and add nothing, as in every pass.
    With tile_groups the groups go `tile_groups` a sweep of the rows (grid
    (tile, row block)), each sweep with its own resident block."""
    GW, n_pad = bins_T.shape
    T, G, GF = block_rows, num_groups, ROOT_GF
    HP = _factored_digits(bmax)
    R = 2 * HP * GF
    # one tile: grid (row block,), the whole table's groups a block.  Tiled:
    # grid (tile, row block), a tile's groups and its own result rows
    tiles = GW // tile_groups if tile_groups else 1
    NGt = (tile_groups or -(-G // GF) * GF) // GF       # 16-groups a tile
    NG = tiles * NGt
    kernel = functools.partial(_root_hist_kernel, T=T, NG=NGt, HP=HP,
                               f32_dots=pallas_interpret(),
                               row_axis=1 if tile_groups else 0)
    out_shape = jax.ShapeDtypeStruct((NG * R, 8 * GF), jnp.int32)
    if tile_groups:
        out = pl.pallas_call(
            kernel,
            grid=(tiles, n_pad // T),
            in_specs=[
                pl.BlockSpec((tile_groups, T), lambda j, b: (j, b)),
                pl.BlockSpec((w_T.shape[0], T), lambda j, b: (0, b)),
            ],
            out_specs=pl.BlockSpec((NGt * R, 8 * GF), lambda j, b: (j, 0)),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 2),
            interpret=pallas_interpret(),
        )(bins_T, w_T)
    else:
        out = _one_tile_call(
            kernel, (bins_T, w_T), T=T,
            ins=[((GW, T), True), ((w_T.shape[0], T), True)],
            outs=((NGt * R, 8 * GF), False), out_shape=out_shape,
            row_blocks=row_blocks)
    return _factored_unpack(out, NG, 1, HP, G, bmax)


def _route_small_hist(bins_T, leaf_id, w_T, tabs, bits, S: int, bmax: int,
                      num_groups: int, num_leaves: int, block_rows: int,
                      has_cat: bool, tile_groups: int = 0, row_blocks=None):
    """The small-slot pass (S <= SMALL_PASS_SLOTS live slots, integer-valued
    grad/hess, u8-layout bins): (new leaf ids (1, N_pad), (S, G, bmax, 2)
    int32 histograms of the routed rows' slots, (S,) exact slot counts), the
    S-slot one-hot pass's results.  One tile: one fused call.  Tiled: the
    route-only pass writes the slots, then one factored call over grid
    (tile, row block) reads them.  The calls' result types are their own:
    no reader of the 64-slot passes' or the root's matches them."""
    GW, n_pad = bins_T.shape
    T, G, L, GF = block_rows, num_groups, num_leaves, ROOT_GF
    B = -(-bmax // 8) * 8
    HP = _factored_digits(bmax)
    R = 2 * S * HP * GF
    if tile_groups:
        # the counts a sublane wide whatever S: a 2-column second result
        # is the one-hot root's to the trace's readers
        new_leaf, cnt, slot = _route_hist_call(
            bins_T, leaf_id, w_T, tabs, bits, S=8, G=G, B=B, L=L, T=T, K=1,
            has_cat=has_cat, two_pass=True, int_weights=True, with_hist=False,
            bin_buckets=None, m_rows=0, slot_out=True)
        cnt = cnt[:, :S]
        tiles, NGt = GW // tile_groups, tile_groups // GF
        NG = tiles * NGt
        # a leading unit axis keeps the result from reading as the sweeps'
        # (tiles, rows, 128) block or the root's (rows, 128)
        out = pl.pallas_call(
            functools.partial(_small_hist_tiles_kernel, T=T, S=S, NG=NGt,
                              HP=HP, f32_dots=pallas_interpret()),
            grid=(tiles, n_pad // T),
            in_specs=[
                pl.BlockSpec((tile_groups, T), lambda j, b: (j, b)),
                pl.BlockSpec((1, T), lambda j, b: (0, b)),
                pl.BlockSpec((w_T.shape[0], T), lambda j, b: (0, b)),
            ],
            out_specs=pl.BlockSpec((None, None, NGt * R, 8 * GF),
                                   lambda j, b: (j, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((tiles, 1, NGt * R, 8 * GF),
                                           jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 2),
            interpret=pallas_interpret(),
        )(bins_T, slot, w_T)
    else:
        NG = -(-G // GF)
        # the histogram first: a result tuple that opens with the leaf ids
        # is the 64-slot pass's to the trace's readers
        out, new_leaf, cnt = _one_tile_call(
            functools.partial(_route_small_hist_kernel, T=T, B=B, S=S, L=L,
                              NG=NG, HP=HP, has_cat=has_cat,
                              f32_dots=pallas_interpret()),
            (bins_T, leaf_id, w_T, tabs, bits), T=T,
            ins=[((GW, T), True), ((1, T), True), ((w_T.shape[0], T), True),
                 ((NUM_TAB, L), False), ((B, L), False)],
            outs=[((NG * R, 8 * GF), False), ((1, T), True),
                  ((1, S), False)],
            out_shape=[
                jax.ShapeDtypeStruct((NG * R, 8 * GF), jnp.int32),
                jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                jax.ShapeDtypeStruct((1, S), jnp.float32),
            ],
            row_blocks=row_blocks, leaf=(1, 1))
    return (new_leaf, _factored_unpack(out, NG, S, HP, G, bmax),
            cnt.reshape(-1))


# Mosaic's scoped-VMEM limit for one kernel on this compiler (jax 0.9.0,
# libtpu 0.0.34, TPU v5e): kernel-internal temporaries past 16 MiB fail to
# compile with "Scoped allocation with size ... and limit 16.00M".  The
# one-tile kernels do not raise it (no vmem_limit_bytes), so this is the limit
SCOPED_VMEM_LIMIT = 16 * 2 ** 20
# ... but _hist_tiles_kernel's: its histogram block changes with the tile, so
# the pipeline holds two of it (the one-tile kernel's never moves and is held
# once), 4 MiB more than the same tile costs there: "size 16.50M" for 128
# groups of 64 bins at T = 1024 by the same AOT compile.  A quarter of a
# v5e core's 128 MiB.
TILES_VMEM_LIMIT = 32 * 2 ** 20


def stream_vmem_estimate(m_rows: int, block_rows: int, int_hist: bool,
                         hist_channels: int = 0) -> int:
    """Estimate of route_and_hist's scoped VMEM at one block size: the
    materialised (m_rows, T) one-hot, one (m_rows, C) f32/i32 accumulate
    temporary, ~640 B per block row of route/slot one-hots and A operands,
    and 256 KiB (C = 2*S*K; the binary path's default S=64 block when not
    given).  Calibrated against the compiler's own "Scoped allocation with
    size ..." figures by AOT-compiling for v5e from the CPU box (MiB,
    compiler -> estimate; bf16 unless noted):

      G=28  T=2048 S=64    8.59 ->  9.4    G=136 T=512  S=64   12.80 -> 13.3
      G=28  T=4096 S=64   16.40 -> 17.6 x  G=136 T=512  S=128  17.36 -> 17.6 x
      G=136 T=256  S=128  12.65 -> 13.2    G=136 T=1024 S=64   21.84 -> 22.1 x
      int8, compare-built (the bucketed axis, packed words):
            G=28 T=4096    4.09 -> 10.6          G=136 T=1024   9.19 -> 13.6
      int8, word-built (onehot_build_kind; PR 36: the largest allocation a
      compile under a lowered limit names):
            G=28 T=4096    1.31 -> 10.6          G=67  T=2048   0.95 -> 12.1
            G=136 T=1024   0.45 -> 13.6   (the 128-group tile's 4.13 is the
                                           pipeline's second histogram block)

    (x = over the limit, on both sides).  No false accept over G in
    {28, 136} x T in 256..4096 x S in {64, 128} x int8/bf16 and K in
    {1, 3, 10}; conservative for int8, where Mosaic tiles the compare-built
    one-hot instead of materialising it, and by an order of magnitude for
    the word-built one, which is never held whole (a (4B, T) piece a dot).
    The block sizes were chosen by measurement under this estimate and stay
    as they were; what the word form's room is worth is not measured."""
    oh_bytes = 1 if int_hist else 2
    return (m_rows * block_rows * oh_bytes
            + m_rows * (hist_channels or 128) * 4
            + 640 * block_rows + 2 ** 18)


class StreamTiling(NamedTuple):
    """How route_and_hist cuts a table: `block_rows` rows a grid step, and
    the one-hot M-axis in `num_tiles` tiles of `tile_groups` whole groups
    (`tile_m_rows` one-hot rows each), one sweep of the rows a tile.
    tile_groups == 0 says the whole table is one tile."""
    block_rows: int
    tile_groups: int
    num_tiles: int
    tile_m_rows: int


def _tile_fits(m_rows: int, T: int, int_hist: bool, hist_channels: int):
    """Whether a (m_rows, T) one-hot with its (m_rows, C) histogram block is
    a tile the kernel takes: inside the one-hot budget, and the estimate of
    its scoped VMEM under the compiler's limit."""
    # int8 one-hots get a 9 MB budget: at MSLR shapes (G=136, B=64) that
    # admits T=1024 (8.9 MB one-hot + 4.45 MB hist block still compiles),
    # measured 3% faster end-to-end than the T=512 the 8 MB budget forces.
    budget = (9 if int_hist else 8) * 2 ** 20
    if hist_channels:
        # the (m_rows, C) histogram block stays VMEM-resident across the
        # whole grid; the binary path's C=2S block was small enough to
        # ignore, the K-widened block is not
        budget -= max(0, m_rows * hist_channels * 4 - 2 * 2 ** 20)
    return (m_rows * T * (1 if int_hist else 2) <= budget
            and stream_vmem_estimate(m_rows, T, int_hist, hist_channels)
            <= SCOPED_VMEM_LIMIT)


def stream_tiling(bmax: int, num_groups: int = 28, int_hist: bool = False,
                  bin_buckets=None, hist_channels: int = 0) -> StreamTiling:
    """Rows per kernel block and groups per M-tile.

    A table whose whole (G*B, T) one-hot fits at some block size is ONE
    tile, at the largest such size: int8 one-hots (quantized-gradient path)
    take 4096-row blocks (measured ~3% faster than 2048 end to end), bf16
    one-hots 2048 (4096 at bf16 REGRESSES 5x — VMEM pressure kills the
    pipeline, and small bucketed m_rows would otherwise re-admit it), wider
    layouts step down to 256, the last resort of a table over the one-hot
    budget.  A table over the compiler's limit even there is cut
    into tiles of whole groups (a multiple of 32, the int8 sublane tiling;
    as even as that allows), at the first block size of 1024, 512, 256 at
    which 32 groups fit: 128 groups of 64 bins at T = 1024 for int8
    one-hots, the shape the 136-group table runs at in one tile.  Tiles
    take the uniform (G*B) axis: `bin_buckets` counts only while its
    bucketed sum makes the table one tile.  No tile is taken whose
    stream_vmem_estimate exceeds SCOPED_VMEM_LIMIT.

    hist_channels: column count of the VMEM-resident histogram block
    (2*S*K on the batched multiclass path). When > 0 its T-independent
    footprint is charged against the one-hot budget, so the widened
    K-channel program steps the block size down instead of blowing VMEM.

    Off the chip (interpreted kernels) the block is 1024 rows, to keep the
    dots narrow for XLA:CPU; the tiles are the chip's.

    LGBTPU_BLOCK_ROWS overrides the block size; a value the kernel cannot
    run (not a lane multiple, or over the VMEM limit on the chip) is an
    error here, before the compiler says it less clearly."""
    B = -(-bmax // 8) * 8
    if bin_buckets is not None:
        m_rows = -(-sum(bucket_run_rows(bk, gk)
                        for bk, gk in bin_buckets) // 128) * 128
    else:
        m_rows = onehot_rows(num_groups, B, onehot_build_kind(
            _bins_dtype(bmax), int_hist) == "words")
    tiers = (4096, 2048, 1024, 512, 256) if int_hist \
        else (2048, 1024, 512, 256)
    whole = next((T for T in tiers
                  if _tile_fits(m_rows, T, int_hist, hist_channels)), None)
    if whole is None and stream_vmem_estimate(
            m_rows, 256, int_hist, hist_channels) <= SCOPED_VMEM_LIMIT:
        whole = 256     # over the one-hot budget, under the compiler's limit
    env = _os.environ.get("LGBTPU_BLOCK_ROWS")
    if env and (not env.isdigit() or int(env) <= 0 or int(env) % 128):
        raise LightGBMError(
            f"LGBTPU_BLOCK_ROWS={env!r} must be a positive multiple of "
            "128 (the TPU lane width)")
    if whole is not None:
        if env:
            T = int(env)
            est = stream_vmem_estimate(m_rows, T, int_hist, hist_channels)
            if on_tpu() and est > SCOPED_VMEM_LIMIT:
                raise LightGBMError(
                    f"LGBTPU_BLOCK_ROWS={T} needs about "
                    f"{est / 2 ** 20:.1f} MiB of scoped VMEM for a "
                    f"({m_rows}, {T}) {'int8' if int_hist else 'bf16'} "
                    f"one-hot; the limit is "
                    f"{SCOPED_VMEM_LIMIT // 2 ** 20} MiB — use a smaller "
                    "block")
            return StreamTiling(T, 0, 1, m_rows)
        # CPU interpret mode: keep dots narrow for XLA:CPU
        return StreamTiling(whole if on_tpu() else 1024, 0, 1, m_rows)
    for T in ((int(env),) if env else (1024, 512, 256)):
        most = max((g for g in range(32, num_groups + 32, 32)
                    if _tile_fits(g * B, T, int_hist, hist_channels)),
                   default=0)
        if most:
            tiles = -(-num_groups // most)
            groups = -(-(-(-num_groups // tiles)) // 32) * 32
            return StreamTiling(T, groups, tiles, groups * B)
    raise LightGBMError(
        f"no 32 groups of {B} bins fit the stream kernel's VMEM at "
        f"{'LGBTPU_BLOCK_ROWS=' + env if env else 'any block size'} "
        f"(histogram block of {hist_channels or 128} columns); use "
        "hist_backend=segsum or onehot")


def stream_block_rows(bmax: int, num_groups: int = 28,
                      int_hist: bool = False,
                      bin_buckets=None, hist_channels: int = 0) -> int:
    """Rows per kernel block: stream_tiling()'s, for the callers that run
    one tile (a table wider than that takes the tiling as a whole)."""
    return stream_tiling(bmax, num_groups, int_hist, bin_buckets,
                         hist_channels).block_rows


class StreamLayout(NamedTuple):
    """Static transposed-packed data for the streaming kernel (built once per
    training run): bins packed 4 groups/int32, transposed to (GW, N_pad)."""
    bins_T: jax.Array        # (GW_pad, N_pad) i32
    n_pad: int
    num_groups: int


def _bins_dtype(max_bins: int):
    """The layout pack_bins_T gives a table: the u8 layout (int8, one group
    a row) where the bins fit int8, packed words (int32) otherwise."""
    return jnp.int8 if max_bins <= 127 else jnp.int32


def pack_bins_T(bins: jax.Array, block_rows: int = 1024,
                max_bins: int = 256, tile_groups: int = 0) -> StreamLayout:
    """(N, G) uint8 -> transposed (GW_pad, N_pad) i32 packed layout, or the
    (G_pad, N_pad) i8 unpacked layout when bins fit int8 (max_bins <= 127:
    identical HBM bytes, and the kernel, which dispatches on the dtype,
    skips all shift/mask unpack work).  With tile_groups (stream_tiling's, a
    multiple of 32) the groups pad to whole tiles; padded groups hold bin 0
    and their histogram rows are dropped.  A NumPy table is packed in NumPy
    and comes back as NumPy (predict's batches: the device is then handed
    the words and nothing else); a device table is packed on the device."""
    xp = np if isinstance(bins, np.ndarray) else jnp
    n, g = bins.shape
    n_pad = -(-n // block_rows) * block_rows
    per = tile_groups or 32            # i8 tiling: 32-sublane multiples
    if _bins_dtype(max_bins) == jnp.int8:
        g_pad = -(-g // per) * per
        w = xp.pad(bins, ((0, n_pad - n), (0, g_pad - g))).astype(xp.int8)
        return StreamLayout(bins_T=w.T, n_pad=n_pad, num_groups=g)
    gw_pad = -(-g // per) * per // 4   # 4 groups a word, 8-sublane multiples
    w = xp.pad(bins, ((0, n_pad - n), (0, gw_pad * 4 - g))).astype(xp.int32)
    w = w.reshape(n_pad, gw_pad, 4)
    packed = (w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24))
    return StreamLayout(bins_T=packed.T, n_pad=n_pad, num_groups=g)


def _unflatten_hist(hist, G: int, B: int, S: int, K: int, words: bool):
    """The kernels' (tiles, m_rows, 2*S*K) histogram blocks over G groups a
    tile of B bins -> (K, S, tiles * G, B, 2), by the order the builder
    gave a block's rows: word-major r = (g // 4) * 4B + 4b + g % 4
    (_onehot_words where onehot_word_major(G) says so; G a whole number of
    words, the groups that fill the last included) or b-major r = b * G + g
    (_onehot_compare, and _onehot_words over whole word arrays)."""
    tiles = hist.shape[0]
    if words and onehot_word_major(G):
        hist7 = hist.reshape(tiles, G // 4, B, 4, K, 2, S)
        return hist7.transpose(4, 6, 0, 1, 3, 2, 5).reshape(
            K, S, tiles * G, B, 2)
    return hist.reshape(tiles, B, G, K, 2, S).transpose(
        3, 5, 0, 2, 1, 4).reshape(K, S, tiles * G, B, 2)


def _route_and_hist_tiled(bins_T, slot, w_T, num_slots, bmax, num_groups,
                          block_rows, two_pass, int_weights, num_class,
                          tile_groups):
    """The histogram half of route_and_hist over several M-tiles, of rows to
    which the route-only pass has given slots (`slot`)."""
    S, G, K, T, Gt = num_slots, num_groups, num_class, block_rows, tile_groups
    if _ABLATE:
        raise ValueError("LGBTPU_KABLATE probes require one M-tile")
    u8_layout = bins_T.dtype == jnp.int8
    per_row = 1 if u8_layout else 4       # groups a row of bins_T
    GW, n_pad = bins_T.shape
    tiles = GW * per_row // Gt
    if Gt % 32 or tiles * Gt != GW * per_row or tiles * Gt < G:
        raise ValueError(f"bins_T of {GW * per_row} groups is not packed to "
                         f"tiles of {Gt} groups (pack_bins_T(tile_groups=))")
    B = -(-bmax // 8) * 8
    hist_dtype = jnp.int32 if int_weights else jnp.float32
    hist = pl.pallas_call(
        functools.partial(_hist_tiles_kernel, T=T, Gt=Gt, B=B, S=S,
                          two_pass=two_pass, int_weights=int_weights,
                          f32_dots=pallas_interpret(), u8_layout=u8_layout,
                          K=K),
        grid=(tiles, n_pad // T),
        in_specs=[
            pl.BlockSpec((Gt // per_row, T), lambda j, b: (j, b)),
            pl.BlockSpec((K, T), lambda j, b: (0, b)),
            pl.BlockSpec((w_T.shape[0], T), lambda j, b: (0, b)),
        ],
        out_specs=pl.BlockSpec((None, Gt * B, 2 * S * K),
                               lambda j, b: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, Gt * B, 2 * S * K),
                                       hist_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=TILES_VMEM_LIMIT),
        interpret=pallas_interpret(),
    )(bins_T, slot, w_T)
    # a tile's rows -> (K, S, G, Bmax, 2); int histograms are unscaled by
    # the caller
    hist4 = _unflatten_hist(
        hist, Gt, B, S, K,
        onehot_build_kind(bins_T.dtype, int_weights) == "words"
    )[:, :, :G, :bmax, :]
    return hist4[0] if K == 1 else hist4


def _route_hist_call(bins_T, leaf_id, w_T, tabs, bits, *, S, G, B, L, T, K,
                     has_cat, two_pass, int_weights, with_hist, bin_buckets,
                     m_rows, slot_out, row_blocks=None):
    """The one pallas_call of _route_hist_kernel: (new leaf ids, [histogram
    block with with_hist], slot counts, [slots with slot_out])."""
    GW, n_pad = bins_T.shape
    hist_dtype = jnp.int32 if int_weights else jnp.float32
    outs = [((K, T), True), ((m_rows, 2 * S * K), False), ((1, S * K), False)]
    out_shape = [
        jax.ShapeDtypeStruct((K, n_pad), jnp.int32),
        jax.ShapeDtypeStruct((m_rows, 2 * S * K), hist_dtype),
        jax.ShapeDtypeStruct((1, S * K), jnp.float32),
    ]
    if not with_hist:
        del outs[1], out_shape[1]
    if slot_out:
        outs.append(outs[0])
        out_shape.append(out_shape[0])
    return _one_tile_call(
        functools.partial(_route_hist_kernel, T=T, G=G, B=B, S=S, L=L, GW=GW,
                          has_cat=has_cat, two_pass=two_pass,
                          int_weights=int_weights, f32_dots=pallas_interpret(),
                          u8_layout=bins_T.dtype == jnp.int8,
                          with_hist=with_hist, bin_buckets=bin_buckets,
                          m_rows=m_rows, K=K, slot_out=slot_out),
        (bins_T, leaf_id, w_T, tabs, bits), T=T,
        ins=[((GW, T), True), ((K, T), True), ((w_T.shape[0], T), True),
             ((NUM_TAB, K * L), False), ((B, K * L), False)],
        outs=outs, out_shape=out_shape, row_blocks=row_blocks, leaf=(1, 0))


@functools.partial(watched_jit, name="route_and_hist", warn_after=0,
                   static_argnames=("num_slots", "bmax", "num_groups",
                                    "num_leaves", "block_rows", "has_cat",
                                    "two_pass", "int_weights", "with_hist",
                                    "bin_buckets", "num_class", "root",
                                    "tile_groups", "small_slots"))
def route_and_hist(bins_T: jax.Array, leaf_id: jax.Array, w_T: jax.Array,
                   tabs: jax.Array, bits: jax.Array, num_slots: int, bmax: int,
                   num_groups: int, num_leaves: int, block_rows: int = 1024,
                   has_cat: bool = True, two_pass: bool = True,
                   int_weights: bool = False, with_hist: bool = True,
                   bin_buckets=None, num_class: int = 1, root: bool = False,
                   tile_groups: int = 0, small_slots: int = 0,
                   row_blocks=None):
    """One fused streaming pass: route rows through this round's splits and
    build grad/hess histograms and exact data counts of the rows' NEW slots.

    bins_T: (GW_pad, N_pad) i32 from pack_bins_T.
    leaf_id: (K, N_pad) i32 current leaf per row (per class; K = num_class).
    w_T: (Wpad, N_pad) f32, rows 2k/2k+1 = class k's grad/hess (bagging mask
    applied) and row 2K = cnt; K=1 keeps the legacy 0..2 = grad, hess, cnt.
    tabs: (NUM_TAB, K*L) f32 per-leaf split tables (see build_route_tables).
    bits: (Bpad, K*L) bf16 categorical left bitsets (dummy when !has_cat).
    Returns (new_leaf_id (K, N_pad) i32, hist (S, G, Bmax, 2) f32 grad/hess
    — (K, S, G, Bmax, 2) when num_class > 1 — and slot_cnt (S,) / (K, S)
    f32 exact per-slot data counts).

    num_class > 1 is the BATCHED MULTICLASS path: all K class trees route
    and accumulate inside ONE widened program whose bin one-hot (the
    dominant construct) is built once per block and contracted against the
    stacked class x slot channel axis.

    root=True is the caller's statement that every row sits in ONE leaf and
    `tabs` splits nothing (the grower's root pass, num_slots == 1): leaf ids
    come back as they went in, and where root_pass_kind() says so the
    histogram is built by the factored contraction instead.

    small_slots = s > 0 is the caller's statement that `tabs` gives rows the
    slots 0..s-1 and no other: the small-slot pass builds those — the same
    exact int32 sums — and leaves zeros in the others (route_and_hist_live
    chooses it by a round's own count, where small_pass_index() allows).

    tile_groups (stream_tiling's, with bins_T packed to it) cuts the one-hot
    M-axis into tiles of that many groups where the whole does not fit VMEM:
    the route-only pass (which has no M-axis) routes the rows once and
    writes their slots, then one pallas_call whose grid is (tile, row block)
    contracts: a tile's histogram block stays in VMEM over its sweep of the
    rows, reads only its own groups' rows of bins_T, and every sweep sees the
    same slots.  0 is one tile: the one fused kernel.

    row_blocks ((1,) int32, traced) is the caller's statement that the
    columns from row_blocks * block_rows on hold no in-bag row (a compact
    view, ops/compact.py): the pass streams the first row_blocks blocks
    alone, and the leaf ids past them come back as they went in.  A tiled
    table streams every block (its view's tail holds out-of-bag rows under
    zero weights, which add nothing).
    """
    if tile_groups:
        row_blocks = None
    if (root and num_slots == 1 and with_hist and root_pass_kind(
            bins_T.dtype, int_weights, num_class) == "factored"):
        hist = _root_hist_factored(bins_T, w_T, bmax, num_groups, block_rows,
                                   tile_groups, row_blocks)
        # the slot's count is the caller's own row count; no grower reads it
        return leaf_id, hist, jnp.sum(w_T[2]).reshape(1)
    if tile_groups and bin_buckets is not None:
        raise ValueError("M-tiles take the uniform one-hot axis: no "
                         "bin_buckets with tile_groups")
    if small_slots:
        S = small_slots
        if (not with_hist or root or not 0 < S <= min(SMALL_PASS_SLOTS,
                                                      num_slots)
                or root_pass_kind(bins_T.dtype, int_weights, num_class)
                != "factored"):
            raise ValueError(f"no small-slot pass of {S} slots in this "
                             "program (small_pass_index)")
        new_leaf, hist, cnt = _route_small_hist(
            bins_T, leaf_id, w_T, tabs, bits, S, bmax, num_groups,
            num_leaves, block_rows, has_cat, tile_groups, row_blocks)
        return (new_leaf,
                jnp.pad(hist, ((0, num_slots - S),) + ((0, 0),) * 3),
                jnp.pad(cnt, (0, num_slots - S)))
    tiled = with_hist and tile_groups > 0
    if tiled:
        with_hist = False      # this call routes and counts; the tiles follow
    T = block_rows
    S, G, L, K = num_slots, num_groups, num_leaves, num_class
    if S > MAX_SLOTS:
        raise ValueError(f"stream kernel supports at most {MAX_SLOTS} "
                         f"histogram slots per round, got {S}")
    if K > 1 and _ABLATE:
        raise ValueError("LGBTPU_KABLATE probes require num_class == 1")
    B = -(-bmax // 8) * 8
    words = onehot_build_kind(bins_T.dtype, int_weights,
                              bin_buckets) == "words"
    if bin_buckets is not None:
        if _ABLATE:
            raise ValueError("LGBTPU_KABLATE probes require the uniform "
                             "(non-bucketed) one-hot layout")
        if sum(gk for _, gk in bin_buckets) != G:
            raise ValueError(f"bin_buckets {bin_buckets} do not cover "
                             f"{G} groups")
        m_tot = sum(bucket_run_rows(bk, gk) for bk, gk in bin_buckets)
        m_rows = -(-m_tot // 128) * 128
    else:
        m_rows = onehot_rows(G, B, words)

    hist_dtype = jnp.int32 if int_weights else jnp.float32
    outs = _route_hist_call(
        bins_T, leaf_id, w_T, tabs, bits, S=S, G=G, B=B, L=L, T=T, K=K,
        has_cat=has_cat, two_pass=two_pass, int_weights=int_weights,
        with_hist=with_hist, bin_buckets=bin_buckets, m_rows=m_rows,
        slot_out=tiled, row_blocks=row_blocks)

    def _cnt_out(cnt):
        return cnt.reshape(-1) if K == 1 else cnt.reshape(K, S)

    if tiled:
        new_leaf, cnt, slot = outs
        hist4 = _route_and_hist_tiled(
            bins_T, slot, w_T, S, bmax, G, T, two_pass, int_weights, K,
            tile_groups)
        return new_leaf, hist4, _cnt_out(cnt)
    if not with_hist:
        new_leaf, cnt = outs
        shape4 = (S, G, bmax, 2) if K == 1 else (K, S, G, bmax, 2)
        return new_leaf, jnp.zeros(shape4, hist_dtype), _cnt_out(cnt)
    new_leaf, hist, cnt = outs
    if bin_buckets is not None:
        # per-run unpack: rows [roff, roff + Bk*Gk) -> (K, S, Gk, Bk, 2),
        # bins padded up to Bmax, runs concatenated in layout group order
        parts4 = []
        roff = 0
        for Bk, Gk in bin_buckets:
            Gk8 = bucket_group_pad(Gk)
            blk = hist[roff:roff + Bk * Gk8]
            h4 = blk.reshape(Bk, Gk8, K, 2, S)[:, :Gk].transpose(2, 4, 1, 0, 3)
            if Bk < bmax:
                h4 = jnp.pad(h4, ((0, 0), (0, 0), (0, 0),
                                  (0, bmax - Bk), (0, 0)))
            parts4.append(h4[:, :, :, :bmax, :])
            roff += Bk * Gk8
        hist4 = jnp.concatenate(parts4, axis=2)
        if K == 1:
            hist4 = hist4[0]
        return new_leaf, hist4, _cnt_out(cnt)
    # (m_rows, 2*S*K) rows -> (K, S, G, Bmax, 2); int histograms are
    # unscaled by the caller
    hist4 = _unflatten_hist(hist[None], m_rows // B, B, S, K,
                            words)[:, :, :G, :bmax, :]
    if K == 1:
        hist4 = hist4[0]
    return new_leaf, hist4, _cnt_out(cnt)


def route_and_hist_live(live_slots, bins_T, leaf_id, w_T, tabs, bits,
                        num_slots: int, bmax: int, num_groups: int,
                        num_leaves: int, *, int_weights: bool = False,
                        with_hist: bool = True, row_blocks=None, **static):
    """route_and_hist for a round that knows how many leaves it splits
    (`live_slots`, traced; `tabs` gives rows the slots 0..live_slots-1 and no
    other): a round of one or two takes the small-slot pass, every other
    count the num_slots pass, where small_pass_index() has one to take.  The
    switch stands OUTSIDE the jitted route_and_hist, one call of it a branch:
    a kernel called inside a branch of its own would lose the jitted name the
    device trace knows it by."""
    call = functools.partial(
        route_and_hist, num_slots=num_slots, bmax=bmax,
        num_groups=num_groups, num_leaves=num_leaves,
        int_weights=int_weights, with_hist=with_hist, row_blocks=row_blocks,
        **static)
    which = small_pass_index(live_slots if with_hist else None, bins_T.dtype,
                             int_weights, num_slots,
                             static.get("num_class", 1))
    if which is None:
        return call(bins_T, leaf_id, w_T, tabs, bits)
    return jax.lax.switch(
        which, [functools.partial(call, small_slots=S)
                for S in range(min(SMALL_PASS_SLOTS, num_slots) + 1)],
        bins_T, leaf_id, w_T, tabs, bits)


def _route_replay_kernel(nr_ref, bins_ref, tabs_ref, newleaf_ref, *,
                         T: int, L: int, GW: int, u8_layout: bool,
                         f32_dots: bool):
    """Fused full-data route REPLAY (GOSS+stream fusion, docs/PERF.md):
    starting from leaf 0, apply every stored round table in sequence to
    this row block in ONE kernel launch — bins stream from HBM ONCE per
    tree instead of once per round.  The trip count is the tree's ACTUAL
    round count (scalar-prefetched), so replay compute matches the sum of
    the per-round route-only passes it replaces; the table buffer's unused
    zero rows are exact no-op steps (chosen=0 keeps every lid) and are
    never executed.  Routing math is the shared _route_step — bit-identical
    to the per-round passes by construction."""
    i32, f32 = jnp.int32, jnp.float32
    bf16 = f32 if f32_dots else jnp.bfloat16
    l_iota = jax.lax.broadcasted_iota(i32, (L, T), 0)
    bins32 = (bins_ref[...].astype(i32)       # as _route_rows widens them
              if u8_layout and bins_ref.shape[0] <= WIDE_ROUTE_GROUPS
              else None)
    n_rounds = nr_ref[0]

    def step(r, lid):
        tab = tabs_ref[pl.ds(r * NUM_TAB, NUM_TAB), :]       # (NUM_TAB, L)
        leaf_oh = (l_iota == lid).astype(bf16)
        vals = jax.lax.dot_general(
            tab, leaf_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                      # (NUM_TAB, T)
        iv = vals.astype(i32)
        chosen_i, newid, _, go_left_i, _, _, _ = _route_step(
            iv, bins_ref, bins32, GW, T, u8_layout)
        return jnp.where(chosen_i * (1 - go_left_i) > 0, newid, lid)

    lid0 = jnp.zeros((1, T), i32)
    newleaf_ref[0:1, :] = jax.lax.fori_loop(0, n_rounds, step, lid0)


@functools.partial(watched_jit, name="route_replay", warn_after=0,
                   static_argnames=("num_leaves", "block_rows",
                                    "rounds_buf"))
def route_replay(bins_T: jax.Array, tabs_buf: jax.Array, n_rounds: jax.Array,
                 num_leaves: int, block_rows: int = 1024,
                 rounds_buf: int = 0) -> jax.Array:
    """Replay the stored per-round route tables over ALL rows.

    bins_T: (GW_pad, N_pad) i32 / (G_pad, N_pad) i8 from pack_bins_T.
    tabs_buf: (rounds_buf * NUM_TAB, L) f32 — round r's build_route_tables
    block at rows [r*NUM_TAB, (r+1)*NUM_TAB); untouched rounds are zeros.
    n_rounds: () i32 — dynamic replay trip count (the grown tree's actual
    round count; scalar-prefetched into the kernel's fori_loop bound).

    Returns the final (N_pad,) i32 leaf id of every row — bit-identical to
    the chain of per-round route-only route_and_hist passes it fuses
    (categorical splits are not supported; the grow layer gates fusion off
    when the tree may contain one)."""
    GW, n_pad = bins_T.shape
    T = block_rows
    NB = n_pad // T
    L = num_leaves
    if rounds_buf <= 0:
        rounds_buf = tabs_buf.shape[0] // NUM_TAB
    u8_layout = bins_T.dtype == jnp.int8
    out = pl.pallas_call(
        functools.partial(_route_replay_kernel, T=T, L=L, GW=GW,
                          u8_layout=u8_layout, f32_dots=pallas_interpret()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((GW, T), lambda b, nr: (0, b)),
                pl.BlockSpec((rounds_buf * NUM_TAB, L),
                             lambda b, nr: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, T), lambda b, nr: (0, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(jnp.asarray(n_rounds, jnp.int32).reshape(1), bins_T, tabs_buf)
    return out.reshape(-1)


def _leaf_gather_kernel(lid_ref, val_ref, out_ref, *, T, L):
    i32, f32 = jnp.int32, jnp.float32
    lid = lid_ref[0:1, :]
    l_iota = jax.lax.broadcasted_iota(i32, (L, T), 0)
    oh = (l_iota == lid).astype(f32)                         # (L, T)
    # exactly one nonzero (1.0 * v) term per output column, so the f32 dot
    # is BIT-EXACT — and at M=1 it is far off the critical path
    out_ref[0:1, :] = jax.lax.dot_general(
        val_ref[0:1, :], oh, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)


@functools.partial(watched_jit, name="leaf_gather", warn_after=0,
                   static_argnames=("block_rows",))
def leaf_gather(leaf_id: jax.Array, values: jax.Array,
                block_rows: int = 1024) -> jax.Array:
    """values[leaf_id] as a streaming one-hot contraction (bit-exact).

    XLA lowers small-table gathers over millions of rows to its generic
    (slow, ~100M rows/s) gather; a (1, L) @ (L, T) one-hot dot runs at
    streaming bandwidth instead.  Each output picks exactly one 1.0*value
    product, so the f32 contraction reproduces values[leaf_id] exactly.
    Reference analog: ScoreUpdater::AddScore (score_updater.hpp)."""
    N = leaf_id.shape[0]
    L = values.shape[0]
    T = block_rows
    n_pad = -(-N // T) * T
    lid = jnp.pad(leaf_id.astype(jnp.int32), (0, n_pad - N)).reshape(1, -1)
    out = pl.pallas_call(
        functools.partial(_leaf_gather_kernel, T=T, L=L),
        grid=(n_pad // T,),
        in_specs=[
            pl.BlockSpec((1, T), lambda b: (0, b)),
            pl.BlockSpec((1, L), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(lid, values.reshape(1, L).astype(jnp.float32))
    return out.reshape(-1)[:N]


def build_route_tables(leaf_chosen, leaf_feat, leaf_thr, leaf_dir, leaf_newid,
                       slot_left1, slot_right1, slot_keep1, routing,
                       num_leaves: int):
    """Assemble the (NUM_TAB, L) f32 per-leaf split tables from this round's
    chosen splits; all inputs are (L,) arrays except `routing` (RoutingLayout).

    slot_*1 are histogram-slot indices +1 (0 means "no histogram")."""
    L = num_leaves
    f32 = jnp.float32
    feat = leaf_feat.astype(jnp.int32)
    grp = routing.feat_group[feat]
    word = grp >> 2
    shift = (grp & 3) << 3
    nan_bin = routing.nan_bin[feat]
    newid_lo, newid_hi = _digits(leaf_newid)
    word_lo, word_hi = _digits(word)
    rows = jnp.zeros((NUM_TAB, L), f32)
    rows = rows.at[T_CHOSEN].set(leaf_chosen.astype(f32))
    rows = rows.at[T_NEWID_LO].set(newid_lo).at[T_NEWID_HI].set(newid_hi)
    rows = rows.at[T_WORD_LO].set(word_lo).at[T_WORD_HI].set(word_hi)
    rows = rows.at[T_SHIFT].set(shift.astype(f32))
    rows = rows.at[T_SPAN].set(routing.span_start[feat].astype(f32))
    rows = rows.at[T_DEFBIN].set(routing.default_bin[feat].astype(f32))
    rows = rows.at[T_BUNDLED].set(routing.bundled[feat].astype(f32))
    rows = rows.at[T_HASNAN].set((nan_bin >= 0).astype(f32))
    rows = rows.at[T_NANBIN].set(jnp.maximum(nan_bin, 0).astype(f32))
    rows = rows.at[T_NBINS].set(routing.num_bins[feat].astype(f32))
    rows = rows.at[T_THR].set(leaf_thr.astype(f32))
    rows = rows.at[T_DEFLEFT].set(((leaf_dir & 1) != 0).astype(f32))
    rows = rows.at[T_ISCAT].set(((leaf_dir & 2) != 0).astype(f32))
    rows = rows.at[T_SLOT_L].set(slot_left1.astype(f32))
    rows = rows.at[T_SLOT_R].set(slot_right1.astype(f32))
    rows = rows.at[T_SLOT_KEEP].set(slot_keep1.astype(f32))
    mzb = (routing.mzero_bin[feat] if routing.mzero_bin is not None
           else jnp.full_like(feat, -1))
    rows = rows.at[T_HASMZ].set((mzb >= 0).astype(f32))
    rows = rows.at[T_MZBIN].set(jnp.maximum(mzb, 0).astype(f32))
    return rows

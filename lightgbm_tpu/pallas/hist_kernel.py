"""Fused Pallas TPU histogram kernels — the framework's hot op.

Reference analog: src/io/dense_bin.hpp:99-170 (ConstructHistogramInner — per-row
scatter-add into an L1-resident histogram) and src/treelearner/cuda/
cuda_histogram_constructor.cu (shared-memory atomic adds). TPUs have neither fast
scatter nor atomics, so the histogram is expressed as a one-hot contraction on the
MXU over slot-sorted row blocks (ops/compact.py): each fixed-size block of rows
belongs to exactly one histogram slot, so the kernel accumulates into a single
VMEM-resident accumulator per slot and writes it back once per slot.

XLA's row gather runs at ~1.6G elements/s on TPU, which makes materialising the
sorted (N, G) uint8 bin matrix the dominant cost. The kernels therefore take bins
PACKED 4-per-int32 (G//4 words per row — 4x fewer gathered elements) and unpack
with shifts on the VPU inside the kernel.

Two kernels, chosen by the padded per-group bin count Bmax:

  * direct (Bmax <= 128): per block ONE wide contraction
        acc[g*B+b, c] += sum_t 1[bin_g[t] == b] * w[c, t]
    i.e. (G*B, T) one-hot  @  (T, 8) weights. The one-hot lives only in VMEM; the
    MXU cost is streaming-bound (G*B*T operand values), ~3*B flops per row-group.

  * nibble (Bmax > 128): bin = 16*hi + lo, so per group
        hist[16h+l, c] = (A_g B_g^T)[c*HI+h, l]
    with A_g[c*HI+h, t] = w[c, t]*onehot(hi)[h, t] and B_g[l, t] = onehot(lo)[l, t],
    keeping one-hot build cost at G*(3*HI + LO) sublanes per block instead of G*Bmax.

The one-hot operand is exact in bfloat16; the weight operand is split into
high/low bfloat16 parts (two MXU passes) so the f32 weights accumulate without
the default bf16 rounding — cheaper than Precision.HIGHEST's 3x3 decomposition.

Both kernels use Pallas grid pipelining (BlockSpec index maps) for the block inputs
— no manual DMA — and scalar-prefetched (slot, first, last) per-block metadata.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.compact import num_blocks, plan_blocks, plan_single_slot
from ..runtime import pallas_interpret
from ..telemetry.watchdog import watched_jit

LO = 16  # nibble kernel low-digit width; HI = ceil(Bmax / LO)


def pack_bins(bins: jax.Array) -> jax.Array:
    """(N, G) uint8 -> (N, ceil(G/4)) int32, 4 bins per word (little-endian)."""
    n, g = bins.shape
    gw = -(-g // 4) * 4
    if gw != g:
        bins = jnp.pad(bins, ((0, 0), (0, gw - g)))
    w = bins.reshape(n, gw // 4, 4).astype(jnp.int32)
    return (w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24))


def _unpack_group(words, g):
    """Extract group g's bin column from packed words (GW, T) i32 -> (1, T) i32."""
    word = words[g // 4:g // 4 + 1, :]
    shift = (g % 4) * 8
    return jax.lax.shift_right_logical(word, shift) & 0xFF


def _wsplit(w):
    """Split f32 weights into (hi, lo) bf16 parts: w ~= hi + lo exactly enough."""
    hi = w.astype(jnp.bfloat16)
    lo = (w - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _direct_kernel(scalar_ref, bins_ref, w_ref, out_ref, oh_ref, acc_ref,
                   *, T: int, G: int, B: int):
    b = pl.program_id(0)
    slot = scalar_ref[b, 0]
    first = scalar_ref[b, 1]
    last = scalar_ref[b, 2]

    @pl.when(slot >= 0)
    def _():
        @pl.when(first == 1)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        biota = jax.lax.broadcasted_iota(jnp.int32, (B, T), 0)
        for g in range(G):  # static unroll
            bg = _unpack_group(bins_ref[...], g)                 # (1, T)
            oh_ref[g * B:(g + 1) * B, :] = (biota == bg).astype(jnp.bfloat16)
        # (G*B, T) @ (8, T)^T -> (G*B, 8); contraction over the lane (T) dim.
        # Two bf16 passes reconstruct f32-accurate weight sums.
        w_hi, w_lo = _wsplit(w_ref[...])
        oh = oh_ref[...]
        dot = functools.partial(jax.lax.dot_general,
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        acc_ref[...] += dot(oh, w_hi) + dot(oh, w_lo)

        @pl.when(last == 1)
        def _():
            out_ref[0] = acc_ref[...].T                          # (8, G*B)


def _nibble_kernel(scalar_ref, bins_ref, w_ref, out_ref, acc_ref,
                   *, T: int, G: int, HI: int):
    b = pl.program_id(0)
    slot = scalar_ref[b, 0]
    first = scalar_ref[b, 1]
    last = scalar_ref[b, 2]

    @pl.when(slot >= 0)
    def _():
        @pl.when(first == 1)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w_hi, w_lo = _wsplit(w_ref[0:3, :])                      # (3, T) each
        hi_iota = jax.lax.broadcasted_iota(jnp.int32, (HI, T), 0)
        lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LO, T), 0)
        dot = functools.partial(jax.lax.dot_general,
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        for g in range(G):  # static unroll
            bg = _unpack_group(bins_ref[...], g)                 # (1, T)
            hi = bg // LO
            lo = bg - hi * LO
            oh_hi = (hi_iota == hi).astype(jnp.bfloat16)         # (HI, T)
            oh_lo = (lo_iota == lo).astype(jnp.bfloat16)         # (LO, T)
            # A[c*HI+h, t] = w[c, t] * oh_hi[h, t] (sublane-merging reshape)
            A = ((w_hi[:, None, :] * oh_hi[None, :, :]).reshape(3 * HI, T),
                 (w_lo[:, None, :] * oh_hi[None, :, :]).reshape(3 * HI, T))
            bh = dot(A[0], oh_lo) + dot(A[1], oh_lo)             # (3HI, LO)
            acc_ref[:, g * LO:(g + 1) * LO] += bh

        @pl.when(last == 1)
        def _():
            out_ref[0] = acc_ref[...]


@functools.partial(watched_jit, name="pallas_hist_direct", warn_after=0,
                   static_argnames=("num_slots", "bmax", "num_groups",
                                    "block_rows"))
def _hist_direct(bins_T, w_T, scalars, counts, num_slots, bmax, num_groups,
                 block_rows):
    GW, n_tot = bins_T.shape
    S, T, G = num_slots, block_rows, num_groups
    B = -(-bmax // 8) * 8                                        # sublane-pad bins
    NB = scalars.shape[0]

    out = pl.pallas_call(
        functools.partial(_direct_kernel, T=T, G=G, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((GW, T), lambda b, sref: (0, b)),
                pl.BlockSpec((8, T), lambda b, sref: (0, b)),
            ],
            out_specs=pl.BlockSpec(
                (1, 8, G * B), lambda b, sref: (jnp.maximum(sref[b, 0], 0), 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G * B, T), jnp.bfloat16),
                pltpu.VMEM((G * B, 8), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 8, G * B), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(scalars, bins_T, w_T)

    hist = out.reshape(S, 8, G, B)[:, :3, :, :bmax]              # (S, 3, G, Bmax)
    hist = jnp.transpose(hist, (0, 2, 3, 1))                     # (S, G, Bmax, 3)
    return jnp.where(counts[:, None, None, None] > 0, hist, 0.0)


@functools.partial(watched_jit, name="pallas_hist_nibble", warn_after=0,
                   static_argnames=("num_slots", "bmax", "num_groups",
                                    "block_rows"))
def _hist_nibble(bins_T, w_T, scalars, counts, num_slots, bmax, num_groups,
                 block_rows):
    GW, n_tot = bins_T.shape
    S, T, G = num_slots, block_rows, num_groups
    HI = -(-bmax // LO)
    NB = scalars.shape[0]

    out = pl.pallas_call(
        functools.partial(_nibble_kernel, T=T, G=G, HI=HI),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((GW, T), lambda b, sref: (0, b)),
                pl.BlockSpec((8, T), lambda b, sref: (0, b)),
            ],
            out_specs=pl.BlockSpec(
                (1, 3 * HI, G * LO),
                lambda b, sref: (jnp.maximum(sref[b, 0], 0), 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((3 * HI, G * LO), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 3 * HI, G * LO), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(scalars, bins_T, w_T)

    # (S, 3, HI, G, LO) -> (S, G, HI*LO, 3), trimmed to Bmax; zero empty slots
    hist = out.reshape(S, 3, HI, G, LO).transpose(0, 3, 2, 4, 1)
    hist = hist.reshape(S, G, HI * LO, 3)[:, :, :bmax, :]
    return jnp.where(counts[:, None, None, None] > 0, hist, 0.0)


def _wide_kernel(bins_ref, slot_ref, w_ref, out_ref, *, T: int, G: int,
                 B: int, S: int, K: int, f32_dots: bool):
    """K-channel natural-order accumulate path (batched multiclass): rows
    stream through in natural order, the class-independent bin one-hot is
    built ONCE per block, and the contraction runs against the stacked
    (3*S*K, T) class x slot weight operand. The sorted direct/nibble
    kernels cannot serve this case — each row belongs to K DIFFERENT slots
    (one per class tree), so no single sort order exists."""
    b = pl.program_id(0)
    i32, f32 = jnp.int32, jnp.float32
    bf16 = f32 if f32_dots else jnp.bfloat16

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # unpack the 4-per-word packed group bins -> (G, T)
    rows = []
    for g in range(G):  # static unroll
        word_g = bins_ref[g // 4:g // 4 + 1, :]
        rows.append(jax.lax.shift_right_logical(word_g, (g % 4) * 8) & 0xFF)
    bins_G = jnp.concatenate(rows, axis=0)
    # B-major one-hot rows r = b * G + g via the key/iota compare (the
    # stream kernel's measured-fastest construct)
    g_iota = jax.lax.broadcasted_iota(i32, (G, T), 0)
    key = bins_G * G + g_iota
    key_t = jnp.concatenate([key] * B, axis=0)               # (B*G, T)
    r_iota = jax.lax.broadcasted_iota(i32, (B * G, T), 0)
    oh = (key_t == r_iota).astype(bf16)

    s_iota = jax.lax.broadcasted_iota(i32, (S, T), 0)
    sohs = [(s_iota == slot_ref[k:k + 1, :]).astype(bf16)
            for k in range(K)]                               # (S, T) each
    w_hi, w_lo = _wsplit(w_ref[...])                         # (Wpad, T)

    def build_A(w):
        # class-major rows j = k*3S + c*S + s; c in (grad, hess, cnt);
        # cnt is the shared row 2K
        return jnp.concatenate(
            [w[r:r + 1, :] * sohs[k]
             for k in range(K)
             for r in (2 * k, 2 * k + 1, 2 * K)], axis=0)    # (3*S*K, T)

    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
    out_ref[...] += dot(oh, build_A(w_hi)) + dot(oh, build_A(w_lo))


def wide_block_rows(bmax: int, num_groups: int, num_class: int,
                    num_slots: int) -> int:
    """Block size for the wide K-channel kernel: the (G*B, T) bf16 one-hot
    plus the T-independent (G*B, 3*S*K) f32 VMEM-resident histogram block
    must share the ~16 MB/core budget."""
    B = -(-bmax // 8) * 8
    m_rows = num_groups * B
    budget = 12 * 2 ** 20 - m_rows * 3 * num_slots * num_class * 4
    for T in (2048, 1024, 512, 256):
        if m_rows * T * 2 <= budget:
            return T
    return 256


def wide_hist_fits(num_class: int, num_slots: int, bmax: int,
                   num_groups: int) -> bool:
    """True when the widened (G*B, 3*S*K) block leaves room for a useful
    one-hot block; otherwise callers fall back to per-class sorted
    kernels."""
    B = -(-bmax // 8) * 8
    if bmax > 128:
        return False   # the key construct is sized for the direct regime
    hist_bytes = num_groups * B * 3 * num_slots * num_class * 4
    return hist_bytes + num_groups * B * 256 * 2 <= 12 * 2 ** 20


@functools.partial(watched_jit, name="pallas_hist_wide", warn_after=0,
                   static_argnames=("num_slots", "bmax", "num_groups",
                                    "num_class", "block_rows"))
def _hist_wide(bins_T, slot, w_T, num_slots, bmax, num_groups, num_class,
               block_rows):
    GW, n_pad = bins_T.shape
    K, S, T, G = num_class, num_slots, block_rows, num_groups
    B = -(-bmax // 8) * 8
    NB = n_pad // T
    out = pl.pallas_call(
        functools.partial(_wide_kernel, T=T, G=G, B=B, S=S, K=K,
                          f32_dots=pallas_interpret()),
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((GW, T), lambda b: (0, b)),
            pl.BlockSpec((K, T), lambda b: (0, b)),
            pl.BlockSpec((w_T.shape[0], T), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((B * G, 3 * S * K), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * G, 3 * S * K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(bins_T, slot, w_T)
    # (B*G, 3SK) b-major rows -> (K, S, G, Bmax, 3)
    hist = out.reshape(B, G, K, 3, S).transpose(2, 4, 1, 0, 3)
    return hist[:, :, :, :bmax, :]


def build_histograms_wide(bins: jax.Array, slot: jax.Array, grad: jax.Array,
                          hess: jax.Array, cnt: jax.Array, num_slots: int,
                          max_group_bins: int,
                          bins_packed: jax.Array = None) -> jax.Array:
    """K-class histograms from ONE widened kernel pass (batched multiclass).

    slot/grad/hess: (K, N) per-class; cnt: (N,) shared.
    Returns (K, S, G, Bmax, 3) float32.
    """
    K, n = slot.shape
    G = bins.shape[1]
    if bins_packed is None:
        bins_packed = pack_bins(bins)
    gw = bins_packed.shape[1]
    gw_pad = -(-gw // 8) * 8
    T = wide_block_rows(max_group_bins, G, K, num_slots)
    n_pad = -(-n // T) * T
    bins_T = jnp.pad(bins_packed.T.astype(jnp.int32),
                     ((0, gw_pad - gw), (0, n_pad - n)))
    slot_p = jnp.pad(slot.astype(jnp.int32), ((0, 0), (0, n_pad - n)),
                     constant_values=-1)
    w_rows = 2 * K + 1
    w_pad = -(-w_rows // 8) * 8
    w2 = jnp.stack([grad, hess], axis=1).reshape(2 * K, n)   # 2k/2k+1 rows
    w_T = jnp.concatenate([w2.astype(jnp.float32),
                           cnt.reshape(1, n).astype(jnp.float32),
                           jnp.zeros((w_pad - w_rows, n), jnp.float32)],
                          axis=0)
    w_T = jnp.pad(w_T, ((0, 0), (0, n_pad - n)))
    return _hist_wide(bins_T, slot_p, w_T, num_slots, max_group_bins, G, K, T)


def build_histograms_sorted(bins: jax.Array, slot: jax.Array, grad: jax.Array,
                            hess: jax.Array, cnt: jax.Array, num_slots: int,
                            max_group_bins: int, block_rows: int = 1024,
                            bins_packed: jax.Array = None) -> jax.Array:
    """Drop-in replacement for ops.histogram.build_histograms using the slot-sorted
    Pallas path: plan blocks, gather packed block rows (invalid positions hit a
    zero pad row), and run the fused kernel. Returns (S, G, Bmax, 3) float32.

    bins_packed: optional precomputed pack_bins(bins) (N, ceil(G/4)) i32 — pass it
    when bins are static across calls (training) to skip re-packing.
    """
    n, G = bins.shape
    if bins_packed is None:
        bins_packed = pack_bins(bins)
    gw = bins_packed.shape[1]
    gw_pad = -(-gw // 8) * 8                       # int32 sublane tile
    if num_slots == 1:
        plan = plan_single_slot(n, block_rows)
    else:
        plan = plan_blocks(slot, num_slots, block_rows)

    bp_pad = jnp.concatenate([bins_packed,
                              jnp.zeros((1, gw), jnp.int32)], axis=0)
    w = jnp.stack([grad.astype(jnp.float32), hess.astype(jnp.float32),
                   cnt.astype(jnp.float32)], axis=1)             # (N, 3)
    w_pad = jnp.concatenate([w, jnp.zeros((1, 3), jnp.float32)], axis=0)

    bb = jnp.take(bp_pad, plan.gather_idx, axis=0)               # (NB*T, GW)
    wb = jnp.take(w_pad, plan.gather_idx, axis=0)                # (NB*T, 3)
    bins_T = jnp.pad(bb.T, ((0, gw_pad - gw), (0, 0)))           # (GW_pad, NB*T)
    w_T = jnp.pad(wb.T, ((0, 8 - 3), (0, 0)))                    # (8, NB*T)

    if max_group_bins <= 128:
        return _hist_direct(bins_T, w_T, plan.scalars, plan.counts,
                            num_slots, max_group_bins, G, block_rows)
    return _hist_nibble(bins_T, w_T, plan.scalars, plan.counts,
                        num_slots, max_group_bins, G, block_rows)

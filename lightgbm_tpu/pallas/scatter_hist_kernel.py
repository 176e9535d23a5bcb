"""Scatter-add Pallas histogram backend (hist_backend=scatter).

Reference analog: src/treelearner/cuda/cuda_histogram_constructor.cu — the
CUDA constructor never materializes a one-hot operand; each thread block
scatter-adds its rows' (grad, hess) straight into a shared-memory histogram
tile.  This backend is the TPU-side existence proof of that formulation: it
skips the one-hot build entirely and accumulates every row block into ONE
VMEM-resident (S*G, B*Cp) histogram tile with a vectorized functional
segment-add (`acc.at[rows, lanes].add(w)`), so per-block cost is O(T*G*C)
update elements instead of the one-hot contraction's O(G*B*T) MACs — the
win grows with B and tree depth, exactly where the CUDA constructor wins.

Portability note (docs/PERF.md gives the measured verdict): Mosaic's
lowering of a functional scatter into a VMEM tile is the open risk on real
TPU cores — the MXU has no scatter datapath, which is the reason the repo's
default formulations are contractions.  The backend therefore ships gated:
`scatter_hist_fits` bounds the tile to the same ~12 MB VMEM budget as
`wide_hist_fits`, dispatch in ops/histogram.py falls back to the one-hot
path whenever the gate refuses, and off-TPU the kernel runs in interpret
mode (pure jnp scatter-add — exact, and fast enough for the A/B suite).

Layout: out[slot * G + g, bin * Cp + c] with Cp = C channels padded to a
multiple of 4; C = 3 (grad, hess, count) or 3*K for the batched-multiclass
widened variant (class-major channels c = k*3 + ch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import pallas_interpret
from ..telemetry.watchdog import watched_jit


def scatter_hist_fits(num_slots: int, num_groups: int, bmax: int,
                      num_class: int = 1) -> bool:
    """True when the (S*G, B*Cp) f32 scatter tile fits the ~12 MB VMEM
    budget (the `wide_hist_fits` convention) AND the static per-group
    unroll stays small enough to compile; callers fall back to the one-hot
    formulation otherwise."""
    C = 3 * num_class
    cp = -(-C // 4) * 4
    B = -(-bmax // 8) * 8
    if bmax > 128 or num_groups > 64:
        return False
    tile = num_slots * num_groups * B * cp * 4
    return tile <= 12 * 2 ** 20


def scatter_block_rows(num_groups: int, num_class: int = 1) -> int:
    """Row-block size: the block inputs are tiny ((T, G) bins + (C, T)
    weights), so the only pressure is the scatter's temporary index
    vectors — large blocks amortize grid overhead."""
    base = 8192 // max(num_class, 1)
    return max(base, 1024)


def _scatter_kernel(bins_ref, slot_ref, w_ref, out_ref, *, T: int, G: int,
                    B: int, K: int, Cp: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...]
    ch3 = jnp.arange(3, dtype=jnp.int32)[None, :]
    for k in range(K):                     # static unroll over classes
        slot = slot_ref[k, :].astype(jnp.int32)
        valid = slot >= 0
        s = jnp.where(valid, slot, 0)
        # (T, 3) per-class (grad, hess, cnt) updates, invalid rows zeroed
        wv = (w_ref[3 * k:3 * (k + 1), :]
              * valid[None, :].astype(jnp.float32)).T
        for g in range(G):                 # static unroll over groups
            fb = bins_ref[:, g].astype(jnp.int32)
            rows = s * G + g
            lanes = fb * Cp + 3 * k
            acc = acc.at[rows[:, None], lanes[:, None] + ch3].add(wv)
    out_ref[...] = acc


@functools.partial(watched_jit, name="pallas_hist_scatter", warn_after=0,
                   static_argnames=("num_slots", "bmax", "num_groups",
                                    "num_class", "block_rows"))
def _hist_scatter(bins_T, slot, w_T, num_slots, bmax, num_groups, num_class,
                  block_rows):
    T, G = block_rows, num_groups
    K, S = num_class, num_slots
    B = -(-bmax // 8) * 8
    cp = -(-(3 * K) // 4) * 4
    n_pad = bins_T.shape[0]
    NB = n_pad // T
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, T=T, G=G, B=B, K=K, Cp=cp),
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((T, G), lambda b: (b, 0)),
            pl.BlockSpec((K, T), lambda b: (0, b)),
            pl.BlockSpec((3 * K, T), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((S * G, B * cp), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S * G, B * cp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(bins_T, slot, w_T)
    # (S*G, B*Cp) -> (K, S, G, Bmax, 3)
    hist = out.reshape(S, G, B, cp)[:, :, :bmax, :3 * K]
    hist = hist.reshape(S, G, bmax, K, 3)
    return jnp.transpose(hist, (3, 0, 1, 2, 4))


def build_histograms_scatter(bins: jax.Array, slot: jax.Array,
                             grad: jax.Array, hess: jax.Array,
                             cnt: jax.Array, num_slots: int,
                             max_group_bins: int) -> jax.Array:
    """Single-class scatter histograms: (S, G, Bmax, 3) float32.

    Same contract as ops.histogram.build_histograms (slot < 0 skips the
    row); rows are streamed unsorted — no block plan, no one-hot."""
    return build_histograms_scatter_k(
        bins, slot[None], grad[None], hess[None], cnt, 1, num_slots,
        max_group_bins)[0]


def build_histograms_scatter_k(bins: jax.Array, slot: jax.Array,
                               grad: jax.Array, hess: jax.Array,
                               cnt: jax.Array, num_class: int,
                               num_slots: int,
                               max_group_bins: int) -> jax.Array:
    """K-class scatter histograms (batched multiclass): (K, S, G, Bmax, 3).

    slot/grad/hess: (K, N) per-class; cnt: (N,) shared."""
    K, n = slot.shape
    G = bins.shape[1]
    T = scatter_block_rows(G, K)
    n_pad = -(-n // T) * T
    bins_p = jnp.pad(bins.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    slot_p = jnp.pad(slot.astype(jnp.int32), ((0, 0), (0, n_pad - n)),
                     constant_values=-1)
    w3 = jnp.stack([grad.astype(jnp.float32), hess.astype(jnp.float32),
                    jnp.broadcast_to(cnt, grad.shape).astype(jnp.float32)],
                   axis=1).reshape(3 * K, n)        # rows k*3 + (g, h, c)
    w_T = jnp.pad(w3, ((0, 0), (0, n_pad - n)))
    return _hist_scatter(bins_p, slot_p, w_T, num_slots, max_group_bins, G,
                         K, T)

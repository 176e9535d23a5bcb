"""Row compaction as one streaming Pallas TPU kernel — the in-bag rows of a
sampled tree (GOSS / bagging) moved to the front of the stream kernel's
operands by prefix counts, with no sort and no gather.

Reference analog: src/treelearner/cuda/cuda_data_partition.cu (a prefix sum
over the in-bag flags gives every kept row its destination) and
src/boosting/bagging.hpp (the in-bag prefix `bag_data_indices_`).

A stable partition needs no permutation: the destination of in-bag row r is
the number of in-bag rows before it.  XLA counts the in-bag rows of every
128-row chunk (one reduce over the mask) and takes the exclusive prefix of
the counts; the kernel streams the table ONCE, a block of `block_rows` rows a
grid step, and inside a step, a chunk at a time:

  rank   the row's rank among its chunk's in-bag rows — one (chunks, 128) x
         (128, 128) triangular-ones dot a block gives every chunk's;
  dest   `fill + rank`, `fill` the rows already placed (a scalar, from the
         prefix counts), relative to the 128-aligned window that holds it;
  one dot of the stacked operand's BYTES — the G int8 bin rows and the C
         float32 weight rows seen as 4C int8 rows (`pltpu.bitcast`) — with
         the chunk's (window, chunk) destination one-hot.  Every output
         column takes exactly one 1 x byte product, so the int32 result is the
         byte itself, and the bytes put back together are the input's own
         bits (-0.0, subnormals and NaN payloads included): the leaf_gather
         trick turned round, on the int8 MXU;
  OR     of the result, back in bytes, into the window of an accumulator
         that starts as zeros: a column is placed once.

An output block stays in VMEM while consecutive input blocks fill it (its
index comes from the scalar-prefetched prefix) and is written once; what an
input block places past its end waits in the accumulator for the next, and a
last grid step writes what the last block left there.  Output blocks no
input reaches are the aliased zero operand's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import pallas_interpret
from ..telemetry.watchdog import watched_jit
from .stream_kernel import _BYTES, _TOPS   # 0x01 / 0x80 in every byte of a word

CHUNK = 128              # rows a one-hot dot places: one lane tile
WINDOW = 2 * CHUNK       # output columns a chunk can reach from its aligned fill
UNROLL = 8               # chunks a loop body: their dots' latencies overlap
MIN_MASK_ROWS = 8        # the mask block's sublanes (a block of < 1024 rows pads)


def compact_kind(tile_groups: int) -> str:
    """How compact_transposed_view compacts such a table: "stream" (this
    kernel) for a table of one M-tile, "take" (XLA's gather by the sorted
    permutation) for a tiled one — a static fact of the table's shape
    (stream_tiling's `tile_groups`), published in every GBDT::FlagPoll record
    of a compacted tree."""
    return "take" if tile_groups else "stream"


def _byte_rows(x):
    """A (r, n) block as int8 rows: 32-bit rows as their 4r byte rows."""
    return x if x.dtype == jnp.int8 else pltpu.bitcast(x, jnp.int8)


def _compact_kernel(p_ref, cp_ref, m_ref, *refs, T: int, capacity: int,
                    n_ops: int, f32_dots: bool):
    """One input block of T rows: place its in-bag rows behind the rows
    already placed.  refs: the operands, their aliased zero results (never
    touched), the results, then the scratch — `acc` (R / 4, 2T + WINDOW)
    int32, the R byte rows four a word: the current output block in [0, T)
    and what was placed past it behind — and `rank` (chunks, 128) int32."""
    i32, f32, i8 = jnp.int32, jnp.float32, jnp.int8
    x_refs = refs[:n_ops]
    out_refs = refs[2 * n_ops:3 * n_ops]
    acc_ref, rank_ref = refs[3 * n_ops:]
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    nb_out = capacity // T
    # the prefix is clamped to the capacity: a block past it maps to the
    # last output block and places nothing (every dest fails `limit`)
    blk = jnp.minimum(p_ref[i] // T, nb_out - 1)
    blk_next = jnp.minimum(p_ref[i + 1] // T, nb_out - 1)
    off = p_ref[i] - blk * T
    limit = capacity - blk * T

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dot_t, sum_t = (f32, f32) if f32_dots else (i8, i32)
    # every chunk's exclusive in-chunk rank at once: mask @ strict upper ones
    mask = m_ref[0]                                          # (chunks, 128)
    dt = f32 if f32_dots else jnp.bfloat16
    upper = (jax.lax.broadcasted_iota(i32, (CHUNK, CHUNK), 0)
             < jax.lax.broadcasted_iota(i32, (CHUNK, CHUNK), 1)).astype(dt)
    rank_ref[...] = jnp.dot(mask.astype(dt), upper,
                            preferred_element_type=f32).astype(i32)
    # the window's one-hot is built four rows a 32-bit word (int8 row 4s + k
    # is byte k of word row s, as pltpu.bitcast packs them): `unit` has 0x01
    # in the byte of row k = dest % 4 — found against the bytes of a packed
    # 0, 1, 2, 3, so no byte order is assumed — and goes to word row dest // 4
    word_iota = jax.lax.broadcasted_iota(i32, (WINDOW // 4, CHUNK), 0)
    row_in_word = pltpu.bitcast(
        (jax.lax.broadcasted_iota(i32, (32, CHUNK), 0) & 3).astype(i8), i32)

    def chunk(c):
        fill = off + cp_ref[0, 0, c]                         # >= 0
        start = pl.multiple_of((fill >> 7) << 7, CHUNK)
        rank = rank_ref[pl.ds(c, 1), :]                      # (1, 128)
        keep = (m_ref[0, pl.ds(c, 1), :] > 0) & (rank + fill < limit)
        dest = jnp.where(keep, rank + (fill - start), -1)
        differ = row_in_word ^ jnp.broadcast_to((dest & 3) * _BYTES,
                                                (8, CHUNK))
        unit = jax.lax.shift_right_logical(_TOPS - differ, 7) & _BYTES
        onehot = pltpu.bitcast(
            jnp.where(word_iota == (dest >> 2),
                      jnp.concatenate([unit] * (WINDOW // 32), axis=0), 0),
            i8)                                              # (WINDOW, CHUNK)
        lanes = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        x = jnp.concatenate([_byte_rows(r[:, lanes]) for r in x_refs], axis=0)
        # (CPU interpret: one 1.0 x byte product a column is exact in f32)
        placed = jax.lax.dot_general(
            x.astype(dot_t), onehot.astype(dot_t), (((1,), (1,)), ((), ())),
            preferred_element_type=sum_t).astype(i32)        # (R, WINDOW)
        # back to bytes, four rows a word, before the window is touched (a
        # quarter of the stores); a column is placed once, so OR-ing into
        # zeros is exact
        acc_ref[:, pl.ds(start, WINDOW)] |= pltpu.bitcast(
            placed.astype(i8), i32)

    # the step after the last input block has nothing to place: it is there
    # for what the last one placed past its output block's end
    @pl.when(i < last)
    def _():
        chunks = T // CHUNK
        per = math.gcd(chunks, UNROLL)

        def body(k, carry):
            for u in range(per):
                chunk(k * per + u)
            return carry

        jax.lax.fori_loop(0, chunks // per, body, 0)

    @pl.when((blk_next != blk) | (i == last))
    def _():
        # the output block is full (or the table is through): write it once
        row = 0
        for out_ref in out_refs:
            rows = out_ref.shape[0] * out_ref.dtype.itemsize // 4
            out_ref[...] = pltpu.bitcast(acc_ref[row:row + rows, 0:T],
                                         out_ref.dtype)
            row += rows

    @pl.when(blk_next != blk)
    def _():
        # what was placed past the block's end opens the next block
        width = acc_ref.shape[1]
        for j in range(0, T + WINDOW, 512):
            n = min(512, T + WINDOW - j)
            acc_ref[:, j:j + n] = acc_ref[:, T + j:T + j + n]
        acc_ref[:, T + WINDOW:width] = jnp.zeros(
            (acc_ref.shape[0], width - T - WINDOW), i32)


@functools.partial(watched_jit, name="compact_rows", warn_after=0,
                   static_argnames=("mask_row", "capacity", "block_rows"))
def compact_rows(bins_T: jax.Array, w_T: jax.Array, mask_row: int,
                 capacity: int, block_rows: int = 1024):
    """The stream kernel's operands with the in-bag rows first.

    bins_T: (G_pad, N_pad) int8 / (GW_pad, N_pad) int32 from pack_bins_T.
    w_T: (C, N_pad) float32 weight rows; row `mask_row` > 0 says in-bag.
    capacity: columns of the result, a multiple of `block_rows` (as N_pad).

    Returns (bins_T_c, w_T_c), (rows, capacity) each: column j < min(nc,
    capacity) is the j-th in-bag column of the input, bit for bit and in the
    input's order (nc the in-bag count; an in-bag row of rank >= capacity is
    dropped); every column from nc on is zero."""
    T = block_rows
    n_pad = bins_T.shape[1]
    if n_pad % T or capacity % T or T % CHUNK:
        raise ValueError(
            f"compact_rows: {n_pad} rows and capacity {capacity} must be "
            f"multiples of the block ({T}), a multiple of {CHUNK}")
    i32, f32 = jnp.int32, jnp.float32
    nb, chunks = n_pad // T, T // CHUNK
    in_bag = (w_T[mask_row] > 0).reshape(nb, chunks, CHUNK)
    per_chunk = jnp.sum(in_bag, axis=2, dtype=i32)           # (nb, chunks)
    # rows placed before a chunk inside its block, and before a block
    in_block = jnp.cumsum(per_chunk, axis=1) - per_chunk
    placed = jnp.cumsum(jnp.sum(per_chunk, axis=1))
    prefix = jnp.minimum(jnp.concatenate(
        [jnp.zeros(1, i32), placed, placed[-1:]]), capacity).astype(i32)
    mask_rows = max(chunks, MIN_MASK_ROWS)
    mask = jnp.pad(in_bag.astype(f32),
                   ((0, 0), (0, mask_rows - chunks), (0, 0)))
    ops = (bins_T, w_T)
    byte_rows = sum(x.shape[0] * x.dtype.itemsize for x in ops)
    outs = tuple(jax.ShapeDtypeStruct((x.shape[0], capacity), x.dtype)
                 for x in ops)

    def source(b, p):        # the step behind the last block fetches nothing new
        return jnp.minimum(b, nb - 1)

    def target(b, p):        # the output block the rows before block b reach
        return jnp.minimum(p[b] // T, capacity // T - 1)

    return pl.pallas_call(
        functools.partial(_compact_kernel, T=T, capacity=capacity,
                          n_ops=len(ops), f32_dots=pallas_interpret()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb + 1,),
            in_specs=[
                pl.BlockSpec((1, 1, chunks),
                             lambda b, p: (source(b, p), 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, mask_rows, CHUNK),
                             lambda b, p: (source(b, p), 0, 0)),
                *[pl.BlockSpec((x.shape[0], T),
                               lambda b, p: (0, source(b, p))) for x in ops],
                *[pl.BlockSpec(memory_space=pl.ANY) for _ in ops],
            ],
            out_specs=[pl.BlockSpec((x.shape[0], T),
                                    lambda b, p: (0, target(b, p)))
                       for x in ops],
            scratch_shapes=[
                pltpu.VMEM((byte_rows // 4, 2 * T + WINDOW), i32),
                pltpu.VMEM((mask_rows, CHUNK), i32),
            ],
        ),
        out_shape=outs,
        # the results start as zeros: blocks no input reaches stay so
        input_output_aliases={3 + len(ops) + k: k for k in range(len(ops))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
    )(prefix, in_block.reshape(nb, 1, chunks), mask, *ops,
      *[jnp.zeros(o.shape, o.dtype) for o in outs])

"""Row compaction for sampled trees (GOSS / bagging): one stable partition per
tree moves the in-bag rows to the front of a fixed-capacity view, so every
histogram pass of that tree scans the SAMPLED row count.

Reference analog: src/treelearner/data_partition.hpp (LightGBM keeps rows of one leaf
contiguous via a parallel stable partition so per-leaf histograms scan a contiguous
range), src/boosting/bagging.hpp (the in-bag prefix) and
src/treelearner/cuda/cuda_data_partition.cu (prefix-sum compaction on device).

Two routes to the same contiguity.  The stream engine's operands
(`compact_transposed_view`) go through pallas/compact_kernel.py: prefix
counts of the in-bag flags are every kept row's destination, and one kernel
streams the table once — no sort, no gather.  The contraction / segsum
engines (`compact_row_views`), and a table the stream kernel tiles, take a
device-wide stable key sort (`plan_sample_rows`) and XLA's gathers by its
permutation, which those engines reuse for their per-round slot gathers.
Neither makes an (N, S) intermediate.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplePlan(NamedTuple):
    """Row-compaction plan for one sampled tree (GOSS / bagging).

    Reference analog: bagging_.cc / data_partition.hpp keep the in-bag rows
    in a contiguous ``bag_data_indices_`` prefix so every histogram pass
    scans only ``bag_data_cnt_`` rows.  This plan is the SORTED route to
    it: ONE stable key/index sort per tree whose permutation gathers the
    sampled rows to the front of a fixed-capacity view — what the
    contraction / segsum engines and a tiled stream table take.  (The
    stream engine's one-tile tables stopped sorting in PR 38: at 31.4M rows
    on the v5e the sort took 76.8 ms and the two row gathers behind it
    559.8 ms a tree, PERF.md section 6; pallas/compact_kernel.py gives the
    same columns from prefix counts.)  The histogram pass then runs over
    ``capacity`` rows instead of N, so the dominant one-hot MAC cost scales
    with the SAMPLED row count.  Positions past ``nc`` hold out-of-bag rows
    whose grad/hess/count weights are already exactly 0 (the mask
    multiplied them), so no masking is needed.

    Bit-exactness contract (both routes): the stable partition keeps
    sampled rows in original relative order, and truncating the
    all-zero-weight tail changes every f32 histogram accumulation by
    exact-zero terms only — the compacted pass is byte-identical to
    streaming the full layout (tests/test_sample_compact.py proves it
    model-string-equal).
    """
    perm: jax.Array     # (capacity,) i32 — source row per compacted position
    nc: jax.Array       # () i32 — number of sampled rows (caller guarantees
                        # nc <= capacity via the eager capacity bucketing)


def plan_sample_rows(mask: jax.Array, capacity: int) -> SamplePlan:
    """Stable-partition plan: rows with ``mask > 0`` first, original order.

    mask: (N,) f32/bool in-bag weights (0 = out of bag / padding).
    capacity: static compacted row count (a multiple of the kernel block).
    """
    n = mask.shape[0]
    i32 = jnp.int32
    in_bag = mask > 0
    key = jnp.where(in_bag, 0, 1).astype(i32)
    _, perm = jax.lax.sort_key_val(key, jnp.arange(n, dtype=i32))
    return SamplePlan(perm=perm[:capacity],
                      nc=jnp.sum(in_bag.astype(i32)))


def compact_row_views(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                      cnt_w: jax.Array, capacity: int):
    """Compacted natural-order row views for the contraction/segsum
    backends — shared by grow_tree ((N,) grad/hess) and grow_tree_k
    ((K, N), rows last) so the two growth paths cannot drift.  Returns
    (bins_c, grad_c, hess_c, cnt_c, perm); the caller reuses ``perm``
    for its per-round O(capacity) slot gathers.
    """
    perm = plan_sample_rows(cnt_w, capacity).perm

    def rows(a):
        return jnp.take(a, perm, axis=a.ndim - 1)   # rows are the last axis

    return (jnp.take(bins, perm, axis=0), rows(grad), rows(hess),
            jnp.take(cnt_w, perm, axis=0), perm)


def compact_transposed_view(bins_T: jax.Array, w_T: jax.Array,
                            mask_row: int, capacity: int, block: int,
                            mesh=None, row_axis=None, tile_groups: int = 0):
    """Compacted (rows-last) streaming-kernel operands for one sampled tree.

    Shared by grow_tree and grow_tree_k (whose only difference is which
    w_T row holds the count/mask channel: 2 vs 2*K) so the two growth
    paths cannot drift.  Stable-partitions the in-bag rows of ``bins_T``
    (G, N) / ``w_T`` (C, N) to the front and truncates to ``capacity``
    columns; under ``mesh`` every device partitions its OWN row shard
    inside shard_map (no cross-device row movement — the caller sizes
    ``capacity`` to cover the fullest shard).  Returns (bins_T_h, w_T_h,
    placed): the in-bag columns in the table's order, bit for bit, and how
    many there are, min(in-bag count, capacity), as (1,) int32 - a device's
    own count under ``mesh`` ((D,) over the row axis), which the grower
    turns into the row blocks its passes over the view stream.

    A table of one M-tile streams through pallas/compact_kernel.py
    (`compact_kind`: "stream"; columns past the in-bag count are zero); one
    the stream kernel cuts into ``tile_groups``-group tiles keeps the sort
    and XLA's gathers ("take"; those columns hold out-of-bag rows under
    zero weights) — a chunk's dot over its thousands of byte rows was
    neither built nor run, and no deployment measured compacts one.
    """
    from ..pallas.compact_kernel import compact_kind, compact_rows
    if capacity % block:
        raise ValueError(
            f"compact_rows={capacity} must be a multiple of the "
            f"stream kernel block ({block})")

    def _local(bT, wT):
        if compact_kind(tile_groups) == "stream":
            return compact_rows(bT, wT, mask_row=mask_row, capacity=capacity,
                                block_rows=block)
        plan = plan_sample_rows(wT[mask_row], capacity)
        return (jnp.take(bT, plan.perm, axis=1),
                jnp.take(wT, plan.perm, axis=1),
                jnp.minimum(plan.nc, capacity).reshape(1))

    with jax.named_scope("compact_rows"):
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import shard_map_rows
            return shard_map_rows(
                _local, mesh,
                (P(None, row_axis), P(None, row_axis)),
                (P(None, row_axis), P(None, row_axis), P(row_axis)))(
                    bins_T, w_T)
        return _local(bins_T, w_T)


def live_row_blocks(placed: jax.Array, block: int) -> jax.Array:
    """The row blocks a pass over a compact view streams: the blocks that
    hold its ``placed`` in-bag columns, at least one.  The view's capacity
    keeps its headroom for the next tree's count; the passes stop here."""
    return jnp.maximum(-(-placed // block), 1).astype(jnp.int32)

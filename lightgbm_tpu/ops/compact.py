"""Row compaction for sampled trees (GOSS / bagging): one stable partition per
tree moves the in-bag rows to the front of a fixed-capacity view, so every
histogram pass of that tree scans the SAMPLED row count.

Reference analog: src/treelearner/data_partition.hpp (LightGBM keeps rows of one leaf
contiguous via a parallel stable partition so per-leaf histograms scan a contiguous
range), src/boosting/bagging.hpp (the in-bag prefix) and
src/treelearner/cuda/cuda_data_partition.cu (prefix-sum compaction on device). The
TPU re-design reaches the same contiguity with a device-wide key sort.

Everything here is an O(N log N) sort + gathers — no (N, S) intermediates.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplePlan(NamedTuple):
    """Row-compaction plan for one sampled tree (GOSS / bagging).

    Reference analog: bagging_.cc / data_partition.hpp keep the in-bag rows
    in a contiguous ``bag_data_indices_`` prefix so every histogram pass
    scans only ``bag_data_cnt_`` rows.  The TPU equivalent is ONE stable
    key/index sort per tree (76.8 ms at 31.4M rows on the v5e; the two row
    gathers that follow it took 559 ms there: PERF.md section 6, PR 37)
    whose permutation gathers the sampled rows to the front of a fixed-capacity
    view; the streaming kernel then runs ``capacity / T`` grid blocks
    instead of ``N / T``, so the dominant one-hot MAC cost scales with the
    SAMPLED row count.  Positions past ``nc`` hold out-of-bag rows whose
    grad/hess/count weights are already exactly 0 (the mask multiplied
    them), so no in-kernel masking is needed.

    Bit-exactness contract: the stable partition keeps sampled rows in
    original relative order, and truncating the all-zero-weight tail
    changes every f32 histogram accumulation by exact-zero terms only —
    the compacted pass is byte-identical to streaming the full sorted
    layout (tests/test_sample_compact.py proves it model-string-equal).
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
                            mesh=None, row_axis=None):
    """Compacted (rows-last) streaming-kernel operands for one sampled tree.

    Shared by grow_tree and grow_tree_k (whose only difference is which
    w_T row holds the count/mask channel: 2 vs 2*K) so the two growth
    paths cannot drift.  Stable-partitions the in-bag rows of ``bins_T``
    (G, N) / ``w_T`` (C, N) to the front and truncates to ``capacity``
    columns; under ``mesh`` every device partitions its OWN row shard
    inside shard_map (no cross-device row movement — the caller sizes
    ``capacity`` to cover the fullest shard).  Returns (bins_T_h, w_T_h).
    """
    if capacity % block:
        raise ValueError(
            f"compact_rows={capacity} must be a multiple of the "
            f"stream kernel block ({block})")

    def _local(bT, wT):
        plan = plan_sample_rows(wT[mask_row], capacity)
        return (jnp.take(bT, plan.perm, axis=1),
                jnp.take(wT, plan.perm, axis=1))

    with jax.named_scope("compact_rows"):
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import shard_map_rows
            return shard_map_rows(
                _local, mesh,
                (P(None, row_axis), P(None, row_axis)),
                (P(None, row_axis), P(None, row_axis)))(bins_T, w_T)
        return _local(bins_T, w_T)

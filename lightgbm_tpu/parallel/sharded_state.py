"""Permanently device-sharded training state for the fused iteration path.

Reference analog: the reference keeps ``scores_``/``gradients_``/
``bag_data_indices_`` resident in each worker's memory for the whole
training run (gbdt.cpp, data_partition.hpp) — nothing row-indexed ever
round-trips through a coordinator between iterations.

TPU re-design (docs/DISTRIBUTED.md "fused iteration & sharded state"):
every row-indexed array a boosting iteration touches — the score vector,
the last iteration's gradients/hessians, the tree's row->leaf routing,
the in-bag mask — lives in ONE pytree that the fused one-launch step
takes and returns with **explicit out-sharding equal to in-sharding**
(the pjit partition-rule pattern).  XLA therefore never inserts an
implicit re-shard or a host round trip between iterations, and the
engine's host loop only ever touches the tiny scalar tail (finished /
nan-ok flags, in-bag count, compaction-overflow counter) through the
batched once-per-``eval_fetch_freq`` fetch.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax


class ShardedTrainState(NamedTuple):
    """Row-sharded training state threaded through the fused iteration.

    Row-axis arrays (sharded over the mesh's data axis):
      * ``score``   — (N,) or (N, K) f32 training scores
      * ``grad``/``hess`` — like ``score``; the last iteration's RAW
        (unquantized, pre-sampling) gradients, kept for batched
        telemetry/debug fetches.  These are the iteration's own live
        buffers, not fresh allocations — holding them extends two N-row
        arrays' lifetime across the iteration gap (~8 bytes/row; drop
        them from the pytree if that headroom is ever needed)
      * ``leaf_id`` — (N,) or (K, N) i32, the last tree's row routing
      * ``mask``    — (N,) f32 in-bag mask of the last iteration

    Replicated scalar tail (read only by the batched flag fetch):
      * ``key``      — (2,) u32, mirrors the per-iteration RNG stream
        position (keys themselves derive from the iteration counter the
        checkpoint already stores)
      * ``sampled``  — () i32 global in-bag row count of ``mask``
      * ``overflow`` — () i32 iterations whose per-shard in-bag count
        exceeded the static compaction capacity (must stay 0; the poll
        disables compaction and warns when it moves)
      * ``finished`` — () bool, last tree grew no split
      * ``ok``       — () bool, nan_guard all-finite flag
      * ``hist_passes`` — () i32, histogram-building passes over the rows
        grown so far (ops/grow.py counts them per tree; the poll publishes
        the sum as ``telemetry.hist_pass_count()``).  Checkpoints do not
        hold this state — a resumed run rebuilds it and counts from 0
      * ``hist_small_passes`` — () i32, those of ``hist_passes`` that took
        the stream kernel's small-slot pass (rounds that split one or two
        leaves); rides the same fetch onto the poll's record
      * ``scan_slots`` — () i32, the pairs the histogram rounds' split
        scans ran over (ops/grow.py ``tail_chunk``); the same fetch
    """
    score: jax.Array
    grad: jax.Array
    hess: jax.Array
    leaf_id: jax.Array
    mask: jax.Array
    key: jax.Array
    sampled: jax.Array
    overflow: jax.Array
    finished: jax.Array
    ok: jax.Array
    hist_passes: jax.Array
    hist_small_passes: jax.Array
    scan_slots: jax.Array


def state_shardings(mesh, row_axis: Optional[str], num_class: int,
                    replicate_rows: bool = False
                    ) -> Optional[ShardedTrainState]:
    """The explicit sharding pytree for a :class:`ShardedTrainState` —
    used as BOTH the in- and out-sharding of the fused step so row-axis
    arrays stay pinned to their devices across iterations.  ``None``
    without a mesh (single-device runs let jit place everything).

    ``replicate_rows``: the FEATURE-parallel variant (tree_learner=
    feature) — the mesh shards bins' feature-group axis, so every per-row
    state array is pinned fully REPLICATED instead; mixing a replicated
    score with group-sharded bins is exactly the layout the fp grow
    program's shard_maps expect, and an accidental row sharding here
    would silently re-shard every iteration.

    2D mesh variant (tree_learner=data over ``data x feature`` axes,
    docs/DISTRIBUTED.md "2D mesh"): pass the 2D mesh with the row axis
    and ``replicate_rows=False`` — ``P(row_axis)`` on a multi-axis mesh
    shards rows over the data axis and REPLICATES them over the feature
    axis, which is exactly the placement the 2D grow program requires
    for every per-row array (score, grad/hess, leaf routing, bag/GOSS
    mask); only the bins matrix shards over both axes."""
    if mesh is None or (row_axis is None and not replicate_rows):
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    if replicate_rows:
        return ShardedTrainState(*([rep] * len(ShardedTrainState._fields)))
    row = NamedSharding(mesh, P(row_axis))
    if num_class == 1:
        score = grad = hess = row
        leaf = row
    else:
        score = grad = hess = NamedSharding(mesh, P(row_axis, None))
        leaf = NamedSharding(mesh, P(None, row_axis))
    return ShardedTrainState(
        score=score, grad=grad, hess=hess, leaf_id=leaf, mask=row,
        key=rep, sampled=rep, overflow=rep, finished=rep, ok=rep,
        hist_passes=rep, hist_small_passes=rep, scan_slots=rep)

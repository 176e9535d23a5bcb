"""TPU-native leaf-value refit (reference: TreeLearner::FitByExistingTree,
tree_learner.h:28-115; GBDT::RefitTree, gbdt.cpp).

The host-side ``Booster.refit`` walks every tree over every row on the
host — O(N * depth) Python/NumPy work per tree.  This module computes the
SAME leaf values with device passes:

* **Leaf assignment** is ONE route-only replay of the streaming kernel
  per tree (``pallas.stream_kernel.route_replay``): the tree's splits are
  re-encoded as per-round route tables (the exact encoding the grower
  streams during training) and every row is routed through all rounds in
  a single kernel launch.  Binning the refit data with the TRAINING bin
  mappers makes the bin-space comparison ``bin(v) <= thr_bin`` exactly
  equivalent to the host's real-threshold walk ``v <= upper_bound[thr_bin]``
  (searchsorted round-trip), so leaf assignment is bitwise identical.
* **Leaf sums** are float64 ``segment_sum``s on device (bitwise equal to
  the sequential ``np.bincount`` accumulation of the host reference on
  row-ordered updates); the decay blend
  ``decay * old + (1 - decay) * (-sum_g / (sum_h + l2)) * shrinkage``
  mirrors FitByExistingTree.

Trees the replay kernel cannot route (categorical splits) fall back to
the device tree walk used by the score rebuild (``ops.predict``) — still
no host O(N * depth) pass.  Telemetry counts both:
``refit/route_replay_passes`` / ``refit/walk_fallback_passes``.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .tree import Tree
from .utils.log import LightGBMError, log_debug, log_info


def _x64():
    """Scoped float64 (the repo never enables x64 globally)."""
    return jax.enable_x64()


# ---------------------------------------------------------------------------
# replay-schedule reconstruction: finished Tree -> per-round route tables
# ---------------------------------------------------------------------------

def _replay_schedule(tree: Tree, mappers) -> Optional[Tuple[List[List[Tuple[int, int, int, int, int]]], np.ndarray]]:
    """Recover a grow-order replay schedule from a finished tree.

    BFS from the root: replay leaf-id 0 is the root; each split at replay
    id ``l`` keeps ``l`` for its left child and assigns the next fresh id
    to the right child (exactly the grower's id assignment, so the round
    tables' newid encoding is in range).  All splits at BFS depth ``d``
    form replay round ``d`` — sibling splits at one depth touch disjoint
    replay ids, so batching them into one table round routes identically
    to any sequential order.

    Returns ``(rounds, iperm)`` where ``rounds[d]`` is a list of
    ``(replay_lid, feature, thr_bin, dir_flags, newid)`` and
    ``iperm[replay_lid]`` is the tree's true leaf index — or ``None``
    when the tree cannot be replayed (categorical splits: the stream
    kernel does not route them)."""
    L = tree.num_leaves
    if L < 2 or tree.num_cat > 0:
        return None
    iperm = np.zeros(L, np.int64)
    rounds: List[List[Tuple[int, int, int, int, int]]] = []
    next_id = 1
    frontier: List[Tuple[int, int]] = [(0, 0)]       # (node, replay_lid)
    while frontier:
        this_round: List[Tuple[int, int, int, int, int]] = []
        nxt: List[Tuple[int, int]] = []
        for node, lid in frontier:
            f = int(tree.split_feature[node])
            dt = int(tree.decision_type[node])
            if dt & Tree._CAT_MASK:
                return None
            # DIR_DEFAULT_LEFT=1 / DIR_CATEGORICAL=2 (ops.split flags),
            # recovered from the LightGBM decision_type bit layout the
            # same way _tree_to_device does
            dirf = 1 if dt & Tree._DEFAULT_LEFT_MASK else 0
            m = mappers[f]
            thr_bin = int(np.searchsorted(m.upper_bounds,
                                          tree.threshold[node], side="left"))
            newid = next_id
            next_id += 1
            this_round.append((lid, f, thr_bin, dirf, newid))
            for child, clid in ((int(tree.left_child[node]), lid),
                                (int(tree.right_child[node]), newid)):
                if child < 0:
                    iperm[clid] = ~child
                else:
                    nxt.append((child, clid))
        rounds.append(this_round)
        frontier = nxt
    return rounds, iperm


def _tree_depth(tree: Tree) -> int:
    """Max root-to-leaf edge count (bound for the fallback device walk)."""
    if tree.num_leaves < 2:
        return 1
    depth = {0: 1}
    best = 1
    for node in range(len(tree.split_feature)):
        d = depth.get(node, 1)
        best = max(best, d)
        for child in (int(tree.left_child[node]), int(tree.right_child[node])):
            if child >= 0:
                depth[child] = d + 1
    return best


def _build_tabs_buf(rounds, routing, L_pad: int, R_buf: int) -> jax.Array:
    """Stack per-round build_route_tables blocks into the (R_buf*NUM_TAB,
    L_pad) replay buffer; untouched rounds stay zeros (exact no-op steps:
    chosen=0 keeps every row's leaf id)."""
    from .pallas.stream_kernel import NUM_TAB, build_route_tables

    zeros = jnp.zeros(L_pad, jnp.float32)
    blocks = []
    for splits in rounds:
        chosen = np.zeros(L_pad, np.float32)
        feat = np.zeros(L_pad, np.int64)
        thr = np.zeros(L_pad, np.int64)
        dirf = np.zeros(L_pad, np.int64)
        newid = np.zeros(L_pad, np.int64)
        for lid, f, t, d, nid in splits:
            chosen[lid] = 1.0
            feat[lid] = f
            thr[lid] = t
            dirf[lid] = d
            newid[lid] = nid
        blocks.append(build_route_tables(
            jnp.asarray(chosen), jnp.asarray(feat), jnp.asarray(thr),
            jnp.asarray(dirf), jnp.asarray(newid),
            zeros, zeros, zeros,            # route-only: no histogram slots
            routing, L_pad))
    buf = jnp.concatenate(blocks, axis=0) if blocks \
        else jnp.zeros((0, L_pad), jnp.float32)
    pad_rows = R_buf * NUM_TAB - buf.shape[0]
    if pad_rows > 0:
        buf = jnp.pad(buf, ((0, pad_rows), (0, 0)))
    return buf


# ---------------------------------------------------------------------------
# device leaf assignment
# ---------------------------------------------------------------------------

def device_leaf_ids(trees: List[Tree], dataset, mesh=None,
                    row_axis: Optional[str] = None):
    """Leaf index per row for every tree, computed on device.

    Replayable trees share ONE route_replay compile (one leaf budget, one
    rounds buffer, dynamic trip count); categorical trees fall back to
    the score-rebuild walk.  Yields ``(true_leaf_ids_i32_device, kind)``
    per tree, ``kind`` in {"replay", "walk"}."""
    from . import telemetry
    from .pallas.stream_kernel import pack_bins_T, stream_block_rows

    dd = dataset.device_data()
    mappers = dataset.bin_mappers()
    N = dd.num_data
    schedules = [_replay_schedule(t, mappers) for t in trees]
    out: List[Tuple[jax.Array, str]] = []

    L_max = max([t.num_leaves for t in trees] + [2])
    L_pad = max(8, -(-L_max // 8) * 8)
    R_buf = max([len(s[0]) for s in schedules if s is not None] + [1])
    T_rows = stream_block_rows(dd.max_bins, dd.num_groups)
    bins_T = pack_bins_T(dd.bins, T_rows, max_bins=dd.max_bins).bins_T

    def _replay(tabs_buf, n_rounds):
        from .pallas.stream_kernel import route_replay
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from .parallel.mesh import DATA_AXIS, shard_map_rows
            ax = row_axis or DATA_AXIS
            rep = shard_map_rows(
                lambda bT, tb, nr: route_replay(
                    bT, tb, nr, L_pad, block_rows=T_rows,
                    rounds_buf=R_buf)[None],
                mesh, (P(None, ax), P(None, None), P()), P(None, ax))
            return rep(bins_T, tabs_buf, n_rounds)[0]
        return route_replay(bins_T, tabs_buf, n_rounds, L_pad,
                            block_rows=T_rows, rounds_buf=R_buf)

    walk_budget = max(L_max, 2)
    for tree, sched in zip(trees, schedules):
        if tree.num_leaves < 2:
            out.append((jnp.zeros(N, jnp.int32), "trivial"))
            continue
        if sched is not None:
            rounds, iperm = sched
            tabs_buf = _build_tabs_buf(rounds, dd.routing, L_pad, R_buf)
            lids = _replay(tabs_buf, jnp.int32(len(rounds)))[:N]
            true_leaf = jnp.asarray(iperm, jnp.int32)[lids]
            telemetry.inc("refit/route_replay_passes")
            out.append((true_leaf, "replay"))
        else:
            from .models.gbdt import _tree_to_device
            from .ops.predict import _walk_one_tree
            ta = _tree_to_device(tree, walk_budget, dd.max_bins, dataset)
            fields = (ta.split_feature, ta.threshold_bin, ta.dir_flags,
                      ta.left_child, ta.right_child, ta.cat_bitset)
            lids = _walk_one_tree(fields, dd.bins, dd.routing,
                                  _tree_depth(tree))[:N]
            telemetry.inc("refit/walk_fallback_passes")
            out.append((lids.astype(jnp.int32), "walk"))
    return out


# ---------------------------------------------------------------------------
# the refit loop (mirrors model_io.refit_model / FitByExistingTree)
# ---------------------------------------------------------------------------

def refit_leaf_values(booster, dataset, decay_rate: float = 0.9,
                      mesh=None) -> Dict[str, Any]:
    """Refit ``booster``'s leaf values IN PLACE on ``dataset`` (constructed,
    labeled; binned with the training mappers via ``reference=`` for exact
    routing).  Sequential over trees like the reference: tree ``i``'s
    gradients are taken at the score of the already-refitted prefix.

    Returns a report with the per-kind pass counters (the acceptance
    gate's proof that leaf assignment reused the stream kernel)."""
    from .config import Config
    from .model_io import _objective_string
    from .objectives import create_objective
    from .robustness import chaos
    from . import telemetry

    dataset.construct()
    y = dataset.get_label()
    if y is None:
        raise LightGBMError("refit requires labeled data")
    y = np.asarray(y, np.float64)
    w = dataset.get_weight()
    n = dataset.num_data()

    trees = (list(booster.engine.models) if booster._engine is not None
             else list(booster._loaded_trees.trees))
    k = booster.num_model_per_iteration()
    cfg = booster.config if booster._engine is not None else None
    cfg = cfg or Config()
    obj_name = _objective_string(booster).split(" ")[0]
    cfg2 = copy.copy(cfg)
    cfg2.objective = obj_name if obj_name else "regression"
    try:
        obj = create_objective(cfg2)
        obj.init(y, w, n=n)
    except Exception as e:
        log_debug(f"refit: objective unavailable ({e}); leaf values kept")
        obj = None

    report = {"trees": len(trees), "route_replay_passes": 0,
              "walk_fallback_passes": 0, "trivial": 0,
              "decay_rate": float(decay_rate)}
    with telemetry.global_tracer.span("refit/leaf_assignment"):
        leaf_ids = device_leaf_ids(trees, dataset, mesh=mesh)

    score = np.zeros((n, k), np.float64)
    for i, (tree, (leaf_dev, kind)) in enumerate(zip(trees, leaf_ids)):
        report["route_replay_passes" if kind == "replay" else
               "walk_fallback_passes" if kind == "walk" else "trivial"] += 1
        kk = i % k
        leaf = np.asarray(leaf_dev)
        if obj is not None and tree.num_leaves >= 1:
            g, h = obj.get_gradients(
                jnp.asarray(score if k > 1 else score[:, 0], np.float32))
            g = np.asarray(g)
            h = np.asarray(h)
            if k > 1:
                g, h = g[:, kk], h[:, kk]
            # float64 device segment sums: identical accumulation order to
            # the host reference's np.bincount (row-ordered updates)
            with _x64():
                seg = jnp.asarray(leaf_dev, jnp.int32)
                sum_g = np.asarray(jax.ops.segment_sum(
                    jnp.asarray(g, jnp.float64), seg,
                    num_segments=tree.num_leaves))
                sum_h = np.asarray(jax.ops.segment_sum(
                    jnp.asarray(h, jnp.float64), seg,
                    num_segments=tree.num_leaves))
                cnt = np.asarray(jax.ops.segment_sum(
                    jnp.ones(n, jnp.float64), seg,
                    num_segments=tree.num_leaves))
            new_vals = (-sum_g / (sum_h + cfg2.lambda_l2 + 1e-15)
                        * tree.shrinkage)
            has_data = cnt > 0
            new_leaf = np.where(has_data,
                                decay_rate * tree.leaf_value
                                + (1 - decay_rate) * new_vals,
                                tree.leaf_value)
            tree.leaf_value = chaos.inject_nan_refit(new_leaf, i + 1)
        score[:, kk] += tree.leaf_value[leaf]
    booster._fast1_cache = None
    log_info(f"refit: {report['route_replay_passes']} stream-replay + "
             f"{report['walk_fallback_passes']} walk-fallback + "
             f"{report['trivial']} trivial trees "
             f"(decay_rate={decay_rate})")
    return report

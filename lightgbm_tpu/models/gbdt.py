"""GBDT boosting engine.

Reference: src/boosting/gbdt.cpp — Init (:60), Train (:246), TrainOneIter (:353-461),
Boosting/grad compute (:229), UpdateScore (:502), RollbackOneIter (:463); DART
(src/boosting/dart.hpp), RF (src/boosting/rf.hpp).

TPU design: the score vector lives on device; a tree build is one jitted program
(ops/grow.py); the training-score update is a leaf_value gather on the grower's leaf_id
output (no second traversal); validation scores update incrementally with one jitted tree
walk per new tree.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..device_data import DeviceData, to_device
from ..metrics import Metric
from ..objectives import ObjectiveFunction
from ..ops.grow import GrowParams, grow_tree
from ..ops.histogram import (HIST_BACKENDS, check_hist_backend,
                             hist_backend_refusal, resolve_hist_backend)
from ..ops.split import leaf_output
from ..ops.predict import StackedTrees, _walk_one_tree
from ..robustness import chaos as _chaos
from ..runtime import check_device_type, on_tpu, platform_name
from ..robustness.guards import (NanGuard, check_finite_init,
                                 check_model_trees)
from ..telemetry import (costmodel as _tel_cost, device_hbm_bytes,
                         global_registry as _tel_registry,
                         global_tracer as _tel_tracer, memory_snapshot,
                         watched_jit)
from ..tree import Tree, TreeArrays, finalize_tree
from ..utils.log import LightGBMError, log_info, log_warning
from .sample_strategy import create_sample_strategy

# span name -> per-iteration record key for the telemetry phase splits
_PHASE_KEYS = {
    "GBDT::Boosting": "boosting_s",
    "GBDT::TrainTree": "grow_s",
    "GBDT::FusedIter": "fused_iter_s",
    "GBDT::FinalizeTrees": "finalize_s",
    "GBDT::Eval": "eval_s",
}


def quantize_gh(grad, hess, key, num_bins: int, stochastic: bool):
    """Gradient/hessian discretization onto a symmetric integer grid of
    num_bins levels with stochastic rounding (reference:
    src/treelearner/gradient_discretizer.cpp). Returns the grid-valued
    grads/hessians plus the stacked (grad_scale, hess_scale) pair; on the
    stream backend the integer grid feeds an int8 MXU contraction with exact
    int32 histogram accumulation (the reference's int8/int16
    quantized-histogram path, dense_bin.hpp)."""
    half = max(num_bins, 2) / 2.0
    kg, kh = jax.random.split(key)

    def q(x, maxv, kq, lo):
        scale = jnp.maximum(maxv, 1e-10) / half
        u = jax.random.uniform(kq, x.shape) if stochastic else 0.5
        qi = jnp.clip(jnp.floor(x / scale + u), lo, half)
        return qi * scale, scale

    gmax = jnp.max(jnp.abs(grad), axis=0)
    hmax = jnp.max(hess, axis=0)
    gq, gs = q(grad, gmax, kg, -half)
    hq, hs = q(hess, hmax, kh, 0.0)
    return gq, hq, jnp.stack([gs, hs])


class GBDT:
    """The main booster (reference: src/boosting/gbdt.h GBDT class)."""

    boosting_type = "gbdt"
    _average_output = False

    def __init__(self, config: Config, train_data, objective: Optional[ObjectiveFunction],
                 metrics: Sequence[Metric]):
        self.config = config
        check_device_type(config.device_type)
        self.train_data = train_data          # basic.Dataset (constructed)
        self.objective = objective
        self.train_metrics = list(metrics)
        # host trees, iteration-major; device TreeArrays are finalized LAZILY
        # (one batched device_get): a per-tree device->host readback blocks
        # the host on the device queue every iteration (cost on a local
        # chip: not measured) — see the `models` property
        self._models_list: List[Tree] = []
        self._lazy_trees: List[dict] = []
        self._finished_dev = None             # device flag: last iter made no split
        self.iter_ = 0
        self.num_class = config.num_class
        self.num_tree_per_iteration = (objective.num_model_per_iteration
                                       if objective is not None else config.num_class)
        self.valid_sets: List[Any] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[List[Metric]] = []
        self._valid_scores: List[jax.Array] = []
        self.best_iteration = -1

        from ..parallel.mesh import (bins_sharding, create_mesh, data_sharding,
                                     pad_rows_for_mesh)
        self.mesh = create_mesh(config.mesh_shape, config.tree_learner,
                                config.num_machines)
        self._dist_mode = getattr(train_data, "_dist", None) is not None
        # an in-process mesh is handed its table a shard to a device, padded
        # on the host (Dataset.device_data(sharding=)): until then `dd` is
        # the layouts and dimensions alone, which is all the choice of
        # backend and of padding reads
        in_process_mesh = self.mesh is not None and not self._dist_mode
        dd: DeviceData = (train_data.device_view() if in_process_mesh
                          else train_data.device_data())
        self._row_sharding = None
        self._row_axis = None
        self._mesh_stream = False
        # feature-parallel mode (tree_learner=feature under a mesh): bins
        # sharded over its feature-GROUP axis, every per-row array pinned
        # fully replicated (docs/DISTRIBUTED.md "feature-parallel")
        self._feature_mode = False
        self._feature_axis = None
        # 2D mesh (tree_learner=data over data x feature axes): bins sharded
        # over BOTH axes, per-row arrays sharded over rows and replicated
        # over the feature axis (docs/DISTRIBUTED.md "2D mesh")
        self._mesh_2d = False
        self._replicated_sharding = None
        # voting replaces the grow fn with its own shard_map learner, which
        # never reads the packed stream layout — keep stream (and its packed
        # bins copy) off when voting will engage
        self._voting_planned = False
        if config.tree_learner == "voting" and self.mesh is not None:
            from ..parallel.voting import voting_supported
            self._voting_planned = (
                voting_supported(dd.layout, dd.routing)
                and not any(m.bin_type == 1
                            for m in train_data.bin_mappers()))
        if self._dist_mode:
            # multi-process training on a distributed-loaded dataset: each
            # process holds only its binned row shard; assemble ONE global
            # row-sharded array (reference: the per-worker partitions of
            # data_parallel_tree_learner.cpp)
            if self.mesh is None or not self._mesh_shards_rows_only():
                raise LightGBMError(
                    "distributed-loaded datasets train with "
                    "tree_learner=data (row sharding) only")
            self.dd = dd
            from ..parallel.dist_data import make_global_bins
            self._row_sharding = data_sharding(self.mesh)
            self._row_axis = self._row_sharding.spec[0]
            bins = make_global_bins(np.asarray(dd.bins), self.mesh,
                                    self._row_axis)
            dd = dd._replace(bins=bins)
            self._mesh_stream = (self._resolve_hist_backend() == "stream")
            if self.objective is not None:
                # committed single-device arrays cannot enter multi-process
                # computations; numpy rebinds as replicated values (ranking
                # binds LISTS of per-bucket arrays — convert elementwise)
                for a in self.objective.data_bound_attrs():
                    v = getattr(self.objective, a, None)
                    if isinstance(v, (list, tuple)):
                        setattr(self.objective, a,
                                type(v)(np.asarray(x) for x in v))
                    elif v is not None:
                        setattr(self.objective, a, np.asarray(v))
        elif self.mesh is not None:
            # resolve the backend on the pre-shard view: the stream kernel
            # needs rows padded to a whole block per device
            self.dd = dd
            pad_base = 256
            if self._resolve_hist_backend() == "stream":
                from ..pallas.stream_kernel import stream_block_rows
                self._mesh_stream = True
                # int8 and bf16 paths resolve different block sizes (both
                # powers of two), and the bucketed M-axis can raise the
                # tier further; padding to the largest possible block keeps
                # the per-device shard a whole number of kernel blocks for
                # whatever _grow_params later picks
                bb = self._resolved_bin_buckets()
                pad_base = max(
                    stream_block_rows(dd.max_bins, dd.num_groups, False),
                    stream_block_rows(dd.max_bins, dd.num_groups, True),
                    stream_block_rows(dd.max_bins, dd.num_groups, True,
                                      bin_buckets=bb),
                    stream_block_rows(dd.max_bins, dd.num_groups, False,
                                      bin_buckets=bb))
            sh = bins_sharding(self.mesh, config.tree_learner)
            self._mesh_2d = (config.tree_learner == "data"
                             and len(sh.spec) > 1 and sh.spec[1] is not None)
            # feature sharding needs the group axis divisible by the mesh
            # axis; padded groups hold bin 0 for every row and are never
            # gathered by any feature (layout.gather_idx ignores them). On
            # the 2D mesh the feature-local block is further psum_scattered
            # over the row axis at the group dim, so groups pad to a
            # multiple of D_rows * D_feat.
            g_mult = 1
            if len(sh.spec) > 1 and sh.spec[1] is not None:
                g_mult = int(self.mesh.shape[sh.spec[1]])
                if self._mesh_2d:
                    g_mult *= int(self.mesh.shape[sh.spec[0]])
            # straight from the host, a shard to a device: no device ever
            # holds more than its shard, and the pad is never a device copy
            dd = train_data.device_data(
                sharding=sh, view=dd, pad_groups_to=g_mult,
                pad_rows_to=pad_rows_for_mesh(1, self.mesh, base=pad_base))
            if config.tree_learner != "feature":
                # rows are the sharded axis: keep every per-row array (score, grad,
                # hess, bagging mask) on the same sharding so each eager op compiles
                # to ONE consistent SPMD program (mixed placements would race the
                # in-process collectives)
                self._row_sharding = data_sharding(self.mesh)
                self._row_axis = self._row_sharding.spec[0]
                if self._mesh_2d:
                    self._feature_axis = sh.spec[1]
            else:
                # feature sharding: rows stay whole on every device — pin
                # the per-row arrays (score, grad, hess, bagging mask)
                # REPLICATED by construction so eager ops can't compile
                # mixed-placement SPMD programs that race the in-process
                # collectives (_shard_row_array asserts the placement)
                from ..parallel.mesh import replicated
                self._feature_mode = True
                self._feature_axis = sh.spec[1]
                self._replicated_sharding = replicated(self.mesh)
        self.dd = dd
        n = dd.bins.shape[0]                  # padded row count
        self.num_data = train_data.num_data()

        # row-pad mask: padded rows contribute nothing (distributed layouts
        # pad per shard, so the mask is not a prefix — Dataset knows)
        pad_mask = train_data.get_true_row_mask(n)
        # an in-process mesh takes it from the host to its sharding, never
        # whole on device 0 first
        self._pad_mask = self._shard_row_array(
            pad_mask if in_process_mesh else jnp.asarray(pad_mask))

        k = self.num_tree_per_iteration
        self._score_shape = (n,) if k == 1 else (n, k)
        init_scores = self._compute_init_score()
        self.init_scores = init_scores        # python list of floats, len k
        self.score = jnp.zeros(self._score_shape, jnp.float32) + jnp.asarray(
            init_scores if k > 1 else init_scores[0], jnp.float32)
        # user-provided init_score offsets (kept separate from boost_from_average)
        base = train_data.get_init_score_padded(n, k)
        if base is not None:
            # a single non-finite init score would poison every gradient of
            # every iteration — same policy knob as the gradient guard
            base = check_finite_init(base, "init_score", config.nan_guard)
            self.score = self.score + jnp.asarray(base, jnp.float32)
        self.score = self._shard_row_array(self.score)

        self.sample_strategy = create_sample_strategy(
            config, n,
            train_data.get_query_boundaries(),
            train_data.get_label_padded(n))

        self._check_unsupported_params()
        self._grow_params = self._make_grow_params()
        if (self._feature_mode or self._mesh_2d) and (
                not self._grow_params.plain_growth
                or self._parse_forced_splits() is not None
                or config.linear_tree):
            _mode = ("the 2D data x feature mesh" if self._mesh_2d
                     else "tree_learner=feature")
            raise LightGBMError(
                f"{_mode} does not support monotone/"
                "interaction constraints, forced splits, path smoothing, "
                "extra_trees, feature_fraction_bynode, cegb_*, or "
                "linear_tree; remove those parameters or use "
                "a rows-only mesh (tree_learner=data, mesh_shape=data:D)")
        packed = None
        # row-compaction capacity quantum: compacted views must stay whole
        # multiples of the stream kernel block (smaller-tier K-widened
        # blocks are powers of two, so multiples of the pack block divide
        # them too); contraction backends have no block constraint but
        # reuse the same quantum for bounded jit-capacity buckets
        self._pack_block = 256
        # how the stream kernel cuts this table (block rows, M-tiles), and
        # what every flag poll publishes of it: static per compiled program
        self._stream_tiling = None
        self._poll_tiling = {}
        if self._grow_params.hist_backend == "stream":
            from ..pallas.stream_kernel import pack_bins_T, stream_tiling
            tiling = self._stream_tiling = stream_tiling(
                dd.max_bins, dd.num_groups, self._grow_params.int_hist,
                bin_buckets=self._grow_params.bin_buckets)
            self._pack_block = tiling.block_rows
            self._poll_tiling = {
                "hist_tiles": tiling.num_tiles,
                # one-hot rows of the table's own groups: the tiles' rows
                # less the groups that pad the last
                "hist_m_rows": (dd.num_groups * tiling.tile_m_rows
                                // tiling.tile_groups if tiling.tile_groups
                                else tiling.tile_m_rows)}
            if self._mesh_stream:
                # a shard at a time, each on its own device, as one program
                # (its temporaries fuse): rows were padded to a whole
                # kernel block per device, so no shard pads a row
                from jax.sharding import PartitionSpec as P
                from ..parallel.mesh import shard_map_rows
                packed = watched_jit(shard_map_rows(
                    lambda b: pack_bins_T(
                        b, self._pack_block, max_bins=dd.max_bins,
                        tile_groups=tiling.tile_groups).bins_T,
                    self.mesh, (P(self._row_axis),),
                    P(None, self._row_axis)), name="pack_bins_shards",
                    owner=self)(dd.bins)
            else:
                packed = pack_bins_T(
                    dd.bins, self._pack_block, max_bins=dd.max_bins,
                    tile_groups=tiling.tile_groups).bins_T
        # NOTE: `packed` must be a jit ARGUMENT, not a closure capture —
        # captured arrays are embedded in the HLO as constants, and a 10M-row
        # packed bin matrix (hundreds of MB) blows up compilation
        self._packed = packed
        # which formulation the fused iteration's root histogram pass takes
        # (static per compiled program; published at every flag poll)
        self._root_pass = None
        if self._grow_params.hist_backend == "stream":
            from ..pallas.stream_kernel import (onehot_build_kind,
                                                root_pass_kind)
            self._root_pass = root_pass_kind(
                packed.dtype, self._grow_params.int_hist,
                self.num_tree_per_iteration)
            # ... and how its 64-slot passes build their bin one-hot
            self._poll_tiling["onehot_build"] = onehot_build_kind(
                packed.dtype, self._grow_params.int_hist,
                self._grow_params.bin_buckets)
        self._grow_partial = functools.partial(
            grow_tree, layout=dd.layout, routing=dd.routing,
            params=self._grow_params,
            monotone=self._monotone_array(),
            interaction_groups=self._interaction_group_masks(),
            forced=self._parse_forced_splits(),
            cegb_coupled=self._cegb_coupled_array(),
            cegb_lazy_pen=self._cegb_lazy_pen_array(),
            mesh=(self.mesh if (self._mesh_stream or self._feature_mode
                                or self._mesh_2d)
                  else None),
            row_axis=self._row_axis,
            feature_axis=self._feature_axis)
        self._grow_fn = watched_jit(self._grow_partial, name="grow_tree",
                                    owner=self,
                                    static_argnames=("compact_rows",))
        # per-iteration sampled-row telemetry + the compaction capacity the
        # last grow call ran at (0 = dense masking); _compact_cap is the
        # sticky capacity choice (see _row_compaction_capacity)
        self._last_sampled_rows: Optional[int] = None
        self._last_compact_rows = 0
        self._last_sample_mode = "none"
        self._compact_cap = 0
        self._sample_count_cache: Optional[Tuple[int, np.ndarray]] = None
        self._grow_fn_k = None
        self._grow_fn_kb = None
        self._score_add_k_fn = None
        self._mc_batched_last = False
        self._mc_stacked = None
        self._iter_fn = None
        self._cegb_used = (jnp.zeros(dd.num_features, bool)
                           if self._grow_params.has_cegb else None)
        # CEGB per-row feature-acquisition bitset (feature_used_in_data_,
        # cegb hpp:66 — persists across ALL trees of the boosting run)
        self._cegb_lazy = (jnp.zeros((dd.bins.shape[0], dd.num_features),
                                     bool)
                           if self._cegb_lazy_pen_array() is not None
                           else None)
        self._voting = False
        if config.tree_learner == "voting" and self.mesh is not None:
            from ..parallel.voting import (grow_tree_voting,
                                           make_voting_splitter)
            gp = self._grow_params
            if (not gp.plain_growth
                    or self._parse_forced_splits() is not None):
                raise LightGBMError(
                    "tree_learner=voting does not support monotone/"
                    "interaction constraints, forced splits, path "
                    "smoothing, extra_trees, feature_fraction_bynode, or "
                    "cegb_*; remove those parameters or use "
                    "tree_learner=data")
            if config.top_k <= 0:
                raise LightGBMError(
                    f"top_k should be greater than 0, got {config.top_k}")
            S = min(gp.max_splits_per_round, max(gp.num_leaves - 1, 1))
            sp_root = make_voting_splitter(self.mesh, 1, dd.max_bins,
                                           config.top_k, config,
                                           layout=dd.layout)
            sp = make_voting_splitter(self.mesh, 2 * S, dd.max_bins,
                                      config.top_k, config,
                                      layout=dd.layout)
            routing = dd.routing
            vote_mesh, vote_axis = self.mesh, self._row_axis

            def _vote_fn(bins, g, h, mask, colm, key=None, packed=None,
                         cegb_used=None, cegb_lazy=None, gh_scales=None,
                         compact_rows=0, with_passes=False):
                out = grow_tree_voting(bins, g, h, mask, colm,
                                       sp_root, sp, gp, routing,
                                       mesh=vote_mesh, row_axis=vote_axis,
                                       compact_rows=compact_rows)
                # the voting grower keeps no round histogram pass to count
                return (out + (jnp.zeros(3, jnp.int32),) if with_passes
                        else out)

            # the voting fn replaces grow_tree as THE grow partial, so the
            # fused-iteration and per-class-scan paths thread it unchanged
            self._grow_partial = _vote_fn
            self._grow_fn = watched_jit(_vote_fn, name="grow_tree_voting",
                                        owner=self,
                                        static_argnames=("compact_rows",))
            self._voting = True
        self._needs_grow_key = (self._grow_params.bynode_fraction < 1.0
                                or self._grow_params.extra_trees)
        # fused-sharded iteration state (docs/DISTRIBUTED.md "fused
        # iteration & sharded state")
        self._train_state = None
        self._fused_last = False
        self._compact_overflow = False
        self._overflow_seen = 0
        self._hist_passes_seen = 0, 0, 0
        self._poll_iter_seen = 0
        # batched device-flag fetch cadence: eval_fetch_freq, or auto —
        # 16 wherever the fused one-launch path is the default (TPU, any
        # row-sharded stream mesh: each blocking flag read costs a full
        # pipeline stall there), 1 on the eager CPU paths (a sync is
        # free when every op already runs synchronously)
        eff = int(config.eval_fetch_freq or 0)
        if eff > 0:
            self._finished_check_every = eff
        elif on_tpu() or self._can_fuse_iteration():
            self._finished_check_every = 16
        else:
            self._finished_check_every = 1
        # Pallas leaf-value gather: a single TPU device, or the row-sharded
        # stream mesh a shard at a time (_leaf_gather_fn; any other mesh
        # leaves the plain gather to XLA's partitioner). The kernel holds
        # an (L, T) one-hot in VMEM, so bound L like the stream kernel does.
        self._use_leaf_gather_kernel = (
            on_tpu() and (self.mesh is None or self._mesh_stream)
            and max(self.config.num_leaves, 2) <= 2048)
        self._rng = np.random.RandomState(config.feature_fraction_seed)
        self._saved_state: Optional[Tuple] = None
        self._grad_fn = None
        self._score_add_fn = None
        # non-finite gradient guard (docs/ROBUSTNESS.md): a tripped check
        # zeroes the iteration's gradients so it grows an exact no-op tree
        self._nan_guard = NanGuard(config.nan_guard,
                                   objective.name if objective else "none")
        self._nan_check_fn = None
        # telemetry: recent per-iteration wall times + barrier waits
        # (straggler window; the wait column splits a slow link from a
        # slow device in the skew report)
        self._tel_iter_times: List[float] = []
        self._tel_comms_waits: List[float] = []
        self._tel_launches: List[int] = []
        self._tel_syncs: List[int] = []
        from ..telemetry import host_sync_count as _hsc, launch_count as _lc
        self._tel_disp0 = (_lc(), _hsc())
        self._comms_model_cache: Optional[Dict[str, Any]] = None
        cmdl = self._comms_model()
        if cmdl is not None:
            log_info(
                f"mesh comms: mode={cmdl['mode']} "
                f"(dtype={cmdl['dtype']}) over {cmdl['devices']} devices, "
                f"~{cmdl['per_round_bytes'] / 2 ** 20:.3f} MB split payload "
                f"({cmdl.get('hist_block_bytes', 0) / 2 ** 20:.3f} MB "
                "histogram columns) delivered per device per growth round")

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host-side trees; finalizes any pending device trees first (ONE
        batched transfer instead of one readback per boosting iteration)."""
        self._flush_models()
        return self._models_list

    @models.setter
    def models(self, value) -> None:
        self._lazy_trees = []
        self._models_list = list(value)

    def _flush_models(self) -> None:
        if not self._lazy_trees:
            return
        pending = self._lazy_trees
        self._lazy_trees = []
        with _tel_tracer.boundary("GBDT::FinalizeTrees", trees=len(pending)):
            got = jax.device_get([e["arrays"] for e in pending])
        from ..telemetry import note_host_sync
        note_host_sync()
        mappers = self.train_data.bin_mappers()
        for e, arrays in zip(pending, got):
            tree = finalize_tree(arrays, mappers, None, learning_rate=e["rate"])
            if e["bias"]:
                tree.add_bias(e["bias"])
            self._models_list.append(tree)

    # ------------------------------------------------------------------
    def _shard_row_array(self, a):
        """Place a per-row array ((N,) or (N, K)) on the mesh's row
        sharding — or, in feature-parallel mode, pin it fully REPLICATED
        across the mesh (rows are never sharded there) and assert the
        placement so a mixed-placement eager op cannot slip through."""
        if self._replicated_sharding is not None:
            a = jax.device_put(a, self._replicated_sharding)
            assert a.sharding.is_fully_replicated, (
                "feature-parallel per-row arrays must be fully replicated; "
                f"got {a.sharding}")
            return a
        if self._row_sharding is None:
            return a
        if a.ndim == 1:
            return jax.device_put(a, self._row_sharding)
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = self._row_sharding.spec
        return jax.device_put(
            a, NamedSharding(self._row_sharding.mesh, P(spec[0], None)))

    def _leaf_gather_fn(self):
        """values[leaf_id] by the streaming one-hot kernel
        (stream_kernel.leaf_gather).  Under the row-sharded stream mesh
        every device gathers its own rows inside shard_map: GSPMD cannot
        partition a Pallas call, and XLA's own gather of a small table over
        26M rows a chip took 0.26 s of a 0.88 s tree on four v5e chips
        (PR 34's chip runs, ISSUE 35)."""
        from ..pallas.stream_kernel import leaf_gather
        if self.mesh is None:
            return leaf_gather
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import shard_map_rows
        return shard_map_rows(lambda lid, values: leaf_gather(lid, values),
                              self.mesh, (P(self._row_axis), P()),
                              P(self._row_axis))

    # ------------------------------------------------------------------
    def _row_compaction_capacity(self, mask) -> int:
        """Static PER-SHARD row capacity for this iteration's GOSS/bagging
        row compaction (docs/PERF.md "sample-strategy speedups"); 0 keeps
        the legacy dense-mask path.

        The in-bag count is read back eagerly (one device sync — the
        sampled path already runs eagerly) and bucketed to a ~3%-granular
        multiple of the kernel block, so the jitted grower specializes to
        a handful of capacities per run, not one per tree.  Under the
        row-sharded mesh the capacity covers the FULLEST shard (every
        device compacts its own rows to the same static size).
        row_compaction=pad partitions but keeps the full row count — the
        A/B reference the bit-identity suite compares against."""
        if not self.sample_strategy.is_active():
            return 0
        import os as _os
        mode = str(_os.environ.get("LGBTPU_COMPACT", "")
                   or self.config.row_compaction).strip().lower()
        if mode not in ("auto", "off", "pad"):
            # Config validated its own (case-insensitive) value, so this
            # can only be an LGBTPU_COMPACT typo — which must not silently
            # run as "auto"
            from ..utils.log import LightGBMError
            raise LightGBMError(
                f"LGBTPU_COMPACT={mode!r} is not one of 'auto', 'off', "
                "'pad'")
        gp = self._grow_params
        eligible = (mode != "off"
                    and (self.mesh is None or self._mesh_stream
                         or self._voting or self._feature_mode))
        if not eligible and not _tel_tracer.enabled:
            # opted-out / ineligible runs keep the legacy fully-async
            # pipeline: no per-iteration count readback (the sync below
            # exists for the capacity choice and the telemetry field)
            return 0
        n_rows = self.dd.bins.shape[0]
        D = 1
        # per-shard capacity wherever rows are the sharded axis (stream
        # data-parallel AND the voting learner); feature-parallel
        # replicates rows, so its capacity covers the full row count
        if self.mesh is not None and self._row_axis is not None:
            D = int(self.mesh.shape[self._row_axis])
        local = n_rows // D
        # per-mask count cache: bagging reuses one mask for a whole
        # bagging_freq epoch (mask_key = epoch), so the blocking count
        # readback — a full device sync — runs once per DISTINCT mask,
        # not once per iteration (GOSS draws a fresh mask every
        # iteration, so its key never repeats)
        ck = self.sample_strategy.mask_key(self.iter_)
        if self._sample_count_cache is not None \
                and self._sample_count_cache[0] == ck:
            counts = self._sample_count_cache[1]
        else:
            with _tel_tracer.boundary("GBDT::SampleCount"):
                counts = np.asarray(jax.device_get(
                    (mask > 0).reshape(D, local).sum(axis=1)))
            from ..telemetry import note_host_sync
            note_host_sync()
            self._sample_count_cache = (ck, counts)
        self._last_sampled_rows = int(counts.sum())
        if not eligible:
            return 0
        unit = self._pack_block
        q = max(unit, -(-local // (32 * unit)) * unit)
        nc_max = int(counts.max())
        cap_min = max(unit, (-(-nc_max // q)) * q)
        if nc_max * 4 >= local * 3 or cap_min >= local:
            # <25% in-bag row savings (or block quantization ate them): the
            # partition pass + the per-round full-data route-only pass would
            # eat the win — stay dense
            return 0
        if mode == "pad":
            # full row count, rounded UP to the kernel block — the stream
            # operands are padded to whole blocks, so an unaligned dataset
            # row count (anything not a block multiple after the 256-row
            # Dataset pad) must not reach the grower's alignment check
            return -(-local // unit) * unit
        # STICKY capacity with one quantum of headroom: the in-bag count
        # jitters a few sigma between iterations (GOSS's uniform b-sample
        # is binomial), and any crossing of a bucket boundary changes the
        # static compact_rows jit arg — i.e. recompiles the grower
        # MID-RUN.  Reusing the last capacity while it still covers nc
        # (and still saves rows) pins the program to one compile per run;
        # padding rows past nc carry exact-zero weights, so the capacity
        # choice never changes the grown tree (the pad-mode A/B).
        if cap_min <= self._compact_cap < local:
            return self._compact_cap
        cap = cap_min + q if cap_min + q < local else cap_min
        with _tel_tracer.boundary("GBDT::SamplePlan", rows=local,
                                  expected_fraction=nc_max / local,
                                  capacity=cap):
            self._compact_cap = cap
        return cap

    # ------------------------------------------------------------------
    def _comms_model(self) -> Optional[Dict[str, Any]]:
        """Analytic per-round/iteration histogram comms payload for the
        data-parallel mesh path (docs/DISTRIBUTED.md): bytes of reduced
        histogram payload DELIVERED to each device per growth round — the
        full block under hist_comms=psum, the G/D group slice (plus the
        tiny all_gathered best-split records) under reduce_scatter.  The
        per-iteration figure assumes full growth at the round budget
        (rounds = ceil((L-1)/S) + 1 incl. the root pass) and scales with
        trees per iteration; the psum:reduce_scatter RATIO is exact since
        both modes grow identical trees."""
        if self._comms_model_cache is not None:
            return self._comms_model_cache
        if self.mesh is None:
            return None
        gp = self._grow_params
        S2 = 2 * min(gp.max_splits_per_round, max(gp.num_leaves - 1, 1))
        rounds2 = -(-(gp.num_leaves - 1)
                    // max(S2 // 2, 1)) + 1
        k_all = self.num_tree_per_iteration
        if getattr(self, "_voting", False):
            # PV-Tree: vote psum + ONLY the elected top-2k features'
            # histogram columns per slot (O(2k*B), never O(F*B))
            from ..parallel.comms import voting_bytes_per_round
            F = self.dd.num_features
            k2 = min(2 * self.config.top_k, F)
            per_round = voting_bytes_per_round(S2, F, k2, self.dd.max_bins)
            self._comms_model_cache = {
                "mode": "voting", "dtype": "f32",
                "devices": int(np.prod(self.mesh.devices.shape)),
                "per_round_bytes": per_round,
                "hist_block_bytes": S2 * k2 * self.dd.max_bins * 3 * 4,
                "elected_columns": k2,
                "per_iter_bytes": per_round * rounds2 * k_all}
            return self._comms_model_cache
        if self._feature_mode:
            # feature-parallel: ZERO histogram bytes — best-split records
            # (+ owner-shard categorical bitsets) only; routing adds one
            # int32 per row per round (reported separately)
            from ..parallel.comms import feature_bytes_per_round
            d_f = int(self.mesh.shape[self._feature_axis])
            per_round = feature_bytes_per_round(
                S2, d_f, self.dd.max_bins, gp.has_categorical)
            self._comms_model_cache = {
                "mode": "feature", "dtype": "f32", "devices": d_f,
                "per_round_bytes": per_round,
                "hist_block_bytes": 0,
                "route_bytes_per_round": self.dd.bins.shape[0] * 4,
                "per_iter_bytes": per_round * rounds2 * k_all}
            return self._comms_model_cache
        if self._row_sharding is None:
            return None
        if self._mesh_2d:
            # 2D data x feature mesh: the feature axis moves ZERO histogram
            # bytes (shard-local builds); the row axis psum_scatters each
            # device's G/D_feat block down to G/(D_rows*D_feat) groups.
            # Contraction backends only, so the wire is always 4-byte f32
            # (hist_packed_width / bf16_pair ride the int-stream wire,
            # which 2D cannot use — documented in docs/DISTRIBUTED.md).
            from ..parallel.comms import hist_comms_bytes_per_round
            d_r = int(self.mesh.shape[self._row_axis])
            d_f = int(self.mesh.shape[self._feature_axis])
            S = S2 // 2
            kb = k_all if (k_all > 1 and self._use_batched_multiclass()) \
                else 1
            per_round = hist_comms_bytes_per_round(
                S, self.dd.num_groups, self.dd.max_bins, d_r,
                "reduce_scatter", "f32", num_class=kb, packed_width=32,
                d_feat=d_f)
            self._comms_model_cache = {
                "mode": "2d", "dtype": "f32",
                "devices": d_r * d_f, "d_rows": d_r, "d_feat": d_f,
                "per_round_bytes": per_round,
                "packed_width": 32,
                "hist_block_bytes": per_round,
                "per_iter_bytes": per_round * rounds2 * (k_all // kb)}
            return self._comms_model_cache
        # row-sharded data-parallel: stream runs the explicit shard_map
        # psum/reduce_scatter; non-stream backends get the SAME payload
        # via GSPMD's automatic histogram all-reduce, so the analytic
        # psum-convention accounting applies to both
        from ..parallel.comms import hist_comms_bytes_per_round
        # the collective shards over the ROW axis only (comms.build_shard_plan
        # uses mesh.shape[row_axis]); on multi-axis meshes the other axes do
        # not divide the histogram payload
        d = (int(self.mesh.shape[self._row_axis])
             if self._row_axis is not None
             else int(np.prod(self.mesh.devices.shape)))
        S = S2 // 2   # the data reduce moves S smaller-child blocks/round
        # int32 quantized hists stay on the exact psum_scatter wire — the
        # bf16_pair width never applies to them (comms.reduce_hist)
        cdtype = "f32" if gp.int_hist else gp.hist_comms_dtype
        # batched multiclass reduces ONE K-channel block per round; the
        # per-class scan reduces K single-class blocks — same bytes per
        # iteration, different per-round figure
        k = k_all
        kb = k if (k > 1 and self._use_batched_multiclass()) else 1
        # packed wire (hist_packed_width 16/8): the quantized grad/hess
        # pair rides ONE int32/int16 lane — half/quarter bytes; K=1 grow
        # programs only (the batched-multiclass wire stays exact int32;
        # the per-class scan reduces K packed single-class blocks)
        pw = gp.hist_packed_width if gp.int_hist and kb == 1 else 32
        per_pass = functools.partial(
            hist_comms_bytes_per_round, num_groups=self.dd.num_groups,
            bmax=self.dd.max_bins, d=d, mode=gp.hist_comms, dtype=cdtype,
            num_class=kb, packed_width=pw)
        # an exact int32 block that may pass 2^31 in total crosses in two
        # 16-bit limbs (comms.reduce_hist_rows); the packed wire never does
        limbs = gp.hist_reduce_limbs if pw == 32 else 1
        per_round = limbs * per_pass(S)
        self._comms_model_cache = {
            "mode": gp.hist_comms, "dtype": cdtype,
            "devices": d, "per_round_bytes": per_round,
            "packed_width": pw, "limbs": limbs,
            "root_bytes_per_limb": per_pass(1),
            "trees_per_iter": k // kb,
            "hist_block_bytes": per_round,
            "per_iter_bytes": per_round * rounds2 * (k // kb)}
        return self._comms_model_cache

    def _poll_comm_fields(self) -> Dict[str, int]:
        """What the flag poll publishes of the histogram collective, static
        per compiled program: the devices of the mesh, the int32 words an
        entry crosses in, and the payload one device materialises out of
        the root pass's reduce and out of a budget round's, a limb
        (comms.hist_comms_bytes_per_round).  Bytes only where the grower
        issues the collective itself - the stream kernel under a
        row-sharded mesh; 1, 1, 0, 0 on one device."""
        cm = self._comms_model() if self._mesh_stream else None
        if cm is None:
            d = 1 if self.mesh is None else int(self.mesh.devices.size)
            return {"mesh_devices": d, "hist_reduce_limbs": 1,
                    "hist_comm_bytes_root": 0, "hist_comm_bytes_round": 0}
        return {"mesh_devices": cm["devices"],
                "hist_reduce_limbs": cm["limbs"],
                "hist_comm_bytes_root": cm["root_bytes_per_limb"],
                "hist_comm_bytes_round":
                    cm["per_round_bytes"] // cm["limbs"]}

    def _route_only_passes_per_tree(self) -> int:
        """Full-data route-only passes one grown tree costs (telemetry
        counter hist/route_only_passes).  Only the compacted stream path
        routes the full row set separately from its histogram pass;
        GOSS+stream fusion folds ALL of a tree's per-round passes into ONE
        replay launch — the counter's drop is the fusion A/B signal.  The
        predicate mirrors the grower's fusion eligibility gate
        (ops/grow.py); tests/test_hist_backends.py pins the two against
        each other."""
        gp = self._grow_params
        if gp.hist_backend != "stream" or self._last_compact_rows <= 0:
            return 0
        if self._route_replay_fused():
            return 1
        L = gp.num_leaves
        S = min(gp.max_splits_per_round, max(L - 1, 1))
        return -(-(L - 1) // max(S, 1)) + 1

    def _route_replay_fused(self) -> bool:
        """Whether a compacted tree's full-data routing is ONE replay launch
        after growth (the grower's fusion gate, ops/grow.py) and not a
        route-only pass a round."""
        gp = self._grow_params
        L = gp.num_leaves
        S = min(gp.max_splits_per_round, max(L - 1, 1))
        batched_mc = (self.num_tree_per_iteration > 1
                      and self._use_batched_multiclass())
        return bool(gp.route_fusion and S >= 64 and gp.max_depth <= 0
                    and gp.plain_growth and not gp.has_categorical
                    and L <= 256 and not batched_mc
                    and self._parse_forced_splits() is None
                    and self._cegb_lazy is None)

    def _poll_sampling_fields(self, sampled, overflow) -> Dict[str, Any]:
        """What a flag poll publishes of the sampler: host statics of the
        newest iteration, the in-bag count and the overflow count (words the
        poll fetched anyway; None where it fetched none)."""
        compact = self._last_compact_rows
        out = {"sample_mode": self._last_sample_mode,
               "compact_rows": compact,
               "route_only_passes": self._route_only_passes_per_tree()}
        if sampled is not None:
            out["sampled_rows"] = sampled
        if overflow is not None:
            out["compact_overflow"] = overflow
        if self._last_sample_mode == "goss":
            from .sample_strategy import THRESHOLD_PASSES
            # how the top_rate cut is found: an exact select of count
            # passes over the magnitudes' bit patterns (kth_largest)
            out["goss_threshold"] = "select"
            out["threshold_passes"] = THRESHOLD_PASSES
        if compact > 0 and self._grow_params.hist_backend == "stream":
            from ..pallas.compact_kernel import compact_kind
            out["route_replay"] = ("fused" if self._route_replay_fused()
                                   else "per_round")
            # which route ops/compact.py takes to the compact view: static
            # per compiled program, as the tiling it is decided on
            out["compact_kind"] = compact_kind(
                self._stream_tiling.tile_groups)
            if sampled is not None:
                out["pass_rows"] = self._pass_rows(sampled)
        return out

    def _pass_rows(self, sampled: int) -> int:
        """Rows a histogram pass over a compacted tree's view streamed: the
        kernel blocks that hold the in-bag rows, at most the capacity (the
        whole capacity where the view is tiled and streams every block).
        Host arithmetic on the in-bag count; under a row mesh, of a shard
        holding the mean share."""
        compact = self._last_compact_rows
        if self._stream_tiling.tile_groups:
            return compact
        D = 1
        if self.mesh is not None and self._row_axis is not None:
            D = int(self.mesh.shape[self._row_axis])
        T = self._pack_block
        return min(-(-sampled // (D * T)) * T, compact)

    # ------------------------------------------------------------------
    def _mesh_shards_rows_only(self) -> bool:
        """True when the mesh shards bins on the row axis alone — the layout
        the per-device stream kernel + histogram psum path requires."""
        if self.mesh is None:
            return False
        from ..parallel.mesh import bins_sharding
        spec = bins_sharding(self.mesh, self.config.tree_learner).spec
        return len(spec) == 1 or spec[1] is None

    def _mesh_kind(self) -> str:
        """How the bin matrix is sharded (ops.histogram.MESH_KINDS)."""
        if self.mesh is None:
            return "none"
        if self._voting_planned:
            return "voting"
        if self._mesh_shards_rows_only():
            return "rows"
        return ("feature" if self.config.tree_learner == "feature"
                else "rows_x_feature")

    def _resolve_hist_backend(self) -> str:
        """The histogram formulation this job runs: the facts
        ops.histogram.resolve_hist_backend decides from, gathered.  Under a
        row-sharded mesh the stream kernel runs per-device inside shard_map
        with a histogram psum (the reference's per-worker fast path +
        ReduceScatter, data_parallel_tree_learner.cpp:285-299); feature-
        sharded meshes run the contractions, which GSPMD partitions.

        ``LGBTPU_HIST_BACKEND`` overrides the param (A/B runs; read here and
        nowhere else) and passes through the same validation as the param."""
        import os as _os
        tpu = on_tpu()
        return resolve_hist_backend(
            _os.environ.get("LGBTPU_HIST_BACKEND", "")
            or self.config.hist_backend,
            tpu=tpu, mesh=self._mesh_kind(),
            # asked on a TPU only: off it the kernel module stays unimported
            stream_fits=tpu and self._stream_fits())

    def _resolve_hist_precision(self) -> str:
        """Histogram/scan precision. 'double' mirrors the reference's
        arithmetic — float32 gradients accumulated into double histograms
        (hist_t, dense_bin.hpp) with double split scans — so near-tied split
        gains resolve exactly as stock LightGBM's do. auto = double on the
        CPU segsum backend (where f64 is native-speed and golden-oracle
        fidelity matters), single on the TPU kernel backends (f32/int8 MXU
        paths; f64 is emulated and ~10x slower on TPU)."""
        p = self.config.hist_precision
        backend = self._resolve_hist_backend()
        if p == "auto":
            return "double" if platform_name() == "cpu" \
                and hist_backend_refusal(backend, double=True) is None \
                and not self._voting_planned else "single"
        if p == "double":
            check_hist_backend(backend, double=True)
        if p == "double" and self._voting_planned:
            raise LightGBMError(
                "hist_precision=double is not supported with "
                "tree_learner=voting (the PV-Tree shard_map learner runs "
                "f32); use tree_learner=data")
        return p

    def _grow_x64_ctx(self):
        """enable_x64 scope for the grow program under hist_precision=double
        (f64 arrays cannot exist outside it); used at trace AND call time so
        the jit cache stays consistent."""
        if self._grow_params.hist_double:
            return jax.enable_x64()
        import contextlib
        return contextlib.nullcontext()

    def _stream_fits(self) -> bool:
        """Whether the streaming kernel takes this job, at any width: it keeps
        one M-TILE's (tile rows, 2S) histogram block and the (L, T) leaf
        one-hot resident in VMEM, and a table whose whole one-hot does not
        fit is cut into tiles of whole groups (stream_tiling), so the group
        count no longer decides.  What is left is the leaf one-hot, the slot
        ids, and that 32 groups of this bin count make a tile at all (bf16
        one-hots, the wider of the two)."""
        from ..pallas.stream_kernel import stream_tiling
        L = max(self.config.num_leaves, 2)
        cfg_s = self.config.max_splits_per_round
        S = 2 * min(cfg_s if cfg_s > 0 else 64, max(L - 1, 1))
        if L > 2048 or S > 2 * 255:   # slot ids must stay bf16-exact (<= 255)
            return False
        try:
            stream_tiling(self.dd.max_bins, self.dd.num_groups, False)
        except LightGBMError:
            return False
        return True

    def _resolved_max_splits(self) -> int:
        """Per-round split budget. auto (0): 1 on CPU backends — exact
        best-first, byte-faithful to the reference's leaf-wise order — and
        64 on TPU / stream, where batched rounds keep the MXU fed. Batched
        growth deviates from best-first only at the leaf-budget boundary:
        the last round's slots go to current candidates while stock may
        split higher-gain CHILDREN of leaves split moments earlier.
        Intermediate/advanced monotone constraints force 1 regardless (each
        split tightens other leaves' bounds before the next is chosen)."""
        c = self.config
        if self._monotone_intermediate():
            return 1
        if c.max_splits_per_round > 0:
            return c.max_splits_per_round
        if on_tpu() or self._voting_planned \
                or self._resolve_hist_backend() == "stream":
            return 64   # PV-Tree is round-batched by design (top-2k election)
        return 1

    def _resolved_bin_buckets(self):
        """Static (bucket_bins, group_count) runs over the device group
        layout for the stream kernel's bucketed one-hot M-axis.  Groups are
        bucket-sorted at construction (binning.device_group_order); when
        the dataset's groups genuinely vary in bin count (real-world
        low-cardinality/sparse features), M = sum of rounded per-group bin
        counts beats G * Bmax — otherwise (or for legacy unsorted binary
        datasets that fragment into many runs) fall back to uniform."""
        binned = getattr(self.train_data, "binned", None)
        if binned is None or self._resolve_hist_backend() != "stream":
            return None
        from ..binning import bin_bucket_size, bucket_run_rows
        counts = np.asarray(binned.group_bin_counts, np.int64)
        if len(counts) == 0:
            return None
        bpad = -(-int(counts.max()) // 8) * 8
        buckets = []
        for cnt in counts:
            b = bin_bucket_size(int(cnt), bpad)
            if buckets and buckets[-1][0] == b:
                buckets[-1][1] += 1
            else:
                buckets.append([b, 1])
        # cost with the kernel's actual sublane padding — fragmented
        # layouts (one group per bucket) can pad PAST the uniform cost
        m_tot = sum(bucket_run_rows(b, g) for b, g in buckets)
        if len(buckets) > 6 or m_tot >= 0.9 * len(counts) * bpad:
            return None
        buckets = tuple((int(b), int(g)) for b, g in buckets)
        from ..pallas.stream_kernel import stream_tiling
        if stream_tiling(int(counts.max()), len(counts),
                         self._resolved_int_hist(),
                         bin_buckets=buckets).tile_groups:
            # still too wide for one M-tile: tiles take the uniform axis
            return None
        return buckets

    def _int_hist_rows(self):
        """(rows one device's int32 accumulators sum over, rows of the whole
        table): the same but under the row-sharded stream mesh, where a
        device contracts its own shard and the collective sums the rest."""
        n = self.dd.bins.shape[0]
        d = int(self.mesh.shape[self._row_axis]) if self._mesh_stream else 1
        return n // d, n

    def _resolved_int_hist(self) -> bool:
        """Quantized gradients contracted on the int8 MXU into exact int32
        sums: the stream kernel's, where the levels fit int8 and one
        DEVICE's rows of one level cannot overflow int32 (the whole
        table's may, under a mesh: _resolved_reduce_limbs)."""
        c = self.config
        return bool(c.use_quantized_grad
                    and self._resolve_hist_backend() == "stream"
                    and c.num_grad_quant_bins <= 254
                    and c.num_grad_quant_bins % 2 == 0
                    and (c.num_grad_quant_bins / 2)
                    * self._int_hist_rows()[0] < 2 ** 31)

    def _resolved_reduce_limbs(self) -> int:
        """int32 words a histogram entry crosses the mesh in: 1, or 2 (high
        and low 16 bits, summed apart: comms.split_limbs) where a whole
        table's worth of one level would overflow the one - 67M rows at 64
        levels.  The reference widens its accumulators by the leaf's row
        count the same way (gradient_discretizer.cpp, hist_bits)."""
        if not (self._mesh_stream and self._resolved_int_hist()):
            return 1
        whole = (self.config.num_grad_quant_bins / 2) \
            * self._int_hist_rows()[1]
        return 1 if whole < 2 ** 31 else 2

    def _resolved_packed_width(self) -> int:
        """Packed-wire width for the quantized histogram collective
        (hist_packed_width; ``LGBTPU_HIST_PACKED_WIDTH`` A/B override).
        Pass-through to the grower, which engages packing only where it
        changes anything: the int-hist stream path under a mesh."""
        import os as _os
        env = _os.environ.get("LGBTPU_HIST_PACKED_WIDTH", "")
        w = int(env) if env else self.config.hist_packed_width
        if w not in (32, 16, 8):
            raise LightGBMError(
                f"LGBTPU_HIST_PACKED_WIDTH={w!r} is not one of 32, 16, 8")
        return w

    def _resolved_route_fusion(self) -> bool:
        """GOSS+stream fusion switch (route_fusion; ``LGBTPU_ROUTE_FUSION``
        =1/0 A/B override).  auto resolves ON — the replay is bit-identical
        to the per-round route-only passes and the grower gates itself off
        wherever fusion does not apply (no compaction, categorical trees,
        CEGB lazy costs, forced splits, depth limits, leaf budgets past the
        table buffer's VMEM bound)."""
        import os as _os
        env = _os.environ.get("LGBTPU_ROUTE_FUSION", "")
        if env:
            return env not in ("0", "off", "false")
        return str(self.config.route_fusion).lower() in ("auto", "on")

    def _make_grow_params(self) -> GrowParams:
        c = self.config
        gp = GrowParams(
            num_leaves=max(c.num_leaves, 2),
            max_depth=c.max_depth,
            max_splits_per_round=self._resolved_max_splits(),
            lambda_l1=c.lambda_l1, lambda_l2=c.lambda_l2,
            min_data_in_leaf=c.min_data_in_leaf,
            min_sum_hessian_in_leaf=c.min_sum_hessian_in_leaf,
            min_gain_to_split=c.min_gain_to_split,
            max_delta_step=c.max_delta_step,
            cat_l2=c.cat_l2, cat_smooth=c.cat_smooth,
            max_cat_threshold=c.max_cat_threshold,
            max_cat_to_onehot=c.max_cat_to_onehot,
            min_data_per_group=c.min_data_per_group,
            hist_backend=self._resolve_hist_backend(),
            has_categorical=any(m.bin_type == 1
                                for m in self.train_data.bin_mappers()),
            has_monotone=self._monotone_array() is not None,
            monotone_penalty=c.monotone_penalty,
            monotone_intermediate=self._monotone_intermediate(),
            monotone_advanced=(self._monotone_array() is not None
                               and self.config.monotone_constraints_method
                               == "advanced"),
            path_smooth=c.path_smooth,
            has_interaction=self._interaction_group_masks() is not None,
            extra_trees=c.extra_trees,
            bynode_fraction=c.feature_fraction_bynode,
            hist_two_pass=(self._resolve_hist_precision() == "mixed"),
            hist_double=(self._resolve_hist_precision() == "double"),
            # int8 operand range, exact int32 accumulation bounds, and an
            # even level count (odd counts clip to a non-integer +half grid
            # value that the int8 kernel could not represent)
            int_hist=self._resolved_int_hist(),
            hist_reduce_limbs=self._resolved_reduce_limbs(),
            bin_buckets=self._resolved_bin_buckets(),
            has_cegb=(c.cegb_penalty_split > 0.0
                      or (c.cegb_penalty_feature_coupled is not None
                          and len(np.atleast_1d(
                              c.cegb_penalty_feature_coupled)) > 0)
                      or (c.cegb_penalty_feature_lazy is not None
                          and len(np.atleast_1d(
                              c.cegb_penalty_feature_lazy)) > 0)),
            cegb_tradeoff=c.cegb_tradeoff,
            cegb_penalty_split=c.cegb_penalty_split,
            hist_packed_width=self._resolved_packed_width(),
            route_fusion=self._resolved_route_fusion(),
        )
        mode, cdtype = self._resolve_hist_comms(gp)
        # double-buffered scatter (parallel/comms.reduce_hist): bitwise
        # identical at any chunk count, so auto (0) defaults to 2 whenever
        # the exact psum_scatter wire engages — the collective for one
        # slot chunk overlaps the next chunk's packing/copy compute.  The
        # bf16_pair wire pipelines through its all_to_all instead, so the
        # chunk knob resolves to 1 there rather than dangling unused.
        import os as _os
        env = _os.environ.get("LGBTPU_HIST_COMMS_PIPELINE", "")
        pipe = int(env) if env else int(c.hist_comms_pipeline or 0)
        if cdtype == "bf16_pair" and not gp.int_hist \
                and mode == "reduce_scatter":
            pipe = 1
        elif pipe <= 0:
            pipe = 2 if mode == "reduce_scatter" else 1
        return gp._replace(hist_comms=mode, hist_comms_dtype=cdtype,
                           hist_comms_chunks=pipe)

    def _resolve_hist_comms(self, gp: GrowParams) -> Tuple[str, str]:
        """Data-parallel histogram collective (docs/DISTRIBUTED.md).

        ``LGBTPU_HIST_COMMS=psum|reduce_scatter`` overrides the param (A/B
        experiments — trees are bit-identical either way).  reduce_scatter
        engages only on the row-sharded stream path with the plain feature
        set; constraint features / forced splits fall back to psum."""
        import os as _os
        c = self.config
        from ..parallel.comms import HIST_COMMS_DTYPES, HIST_COMMS_MODES
        mode = _os.environ.get("LGBTPU_HIST_COMMS", "") or c.hist_comms
        cdtype = c.hist_comms_dtype
        if mode not in HIST_COMMS_MODES:
            raise LightGBMError(
                f"unknown hist_comms={mode!r}; one of {HIST_COMMS_MODES}")
        if cdtype not in HIST_COMMS_DTYPES:
            raise LightGBMError(
                f"unknown hist_comms_dtype={cdtype!r}; one of "
                f"{HIST_COMMS_DTYPES}")
        if mode == "reduce_scatter":
            if not self._mesh_stream:
                mode = "psum"   # serial / non-stream meshes: GSPMD decides
            elif (not gp.plain_growth
                    or self._parse_forced_splits() is not None):
                log_info(
                    "hist_comms=reduce_scatter supports the plain feature "
                    "set only; falling back to psum (constraint features / "
                    "forced splits active)")
                mode = "psum"
        return mode, cdtype

    def _cegb_lazy_pen_array(self):
        v = self.config.cegb_penalty_feature_lazy
        if v is None or len(np.atleast_1d(v)) == 0:
            return None
        return jnp.asarray(np.atleast_1d(v), jnp.float32)

    def _cegb_coupled_array(self):
        c = self.config
        v = c.cegb_penalty_feature_coupled
        if v is None or len(np.atleast_1d(v)) == 0:
            return None
        return jnp.asarray(np.atleast_1d(v), jnp.float32)

    def _parse_forced_splits(self):
        """forcedsplits_filename JSON -> static per-level split spec
        (reference: serial_tree_learner.cpp:628 ForceSplits; config
        forcedsplits_filename). Numeric splits only."""
        fn = self.config.forcedsplits_filename
        if not fn:
            return None
        import json
        try:
            with open(fn) as fh:
                spec = json.load(fh)
        except FileNotFoundError:
            raise LightGBMError(f"forcedsplits_filename {fn!r} not found")
        except json.JSONDecodeError as e:
            raise LightGBMError(
                f"forcedsplits_filename {fn!r} is not valid JSON: {e}")
        if not spec:
            return None
        mappers = self.train_data.bin_mappers()
        L = max(self.config.num_leaves, 2)
        levels = []
        frontier = [(spec, 0)]
        cur_count = 1
        total = 0
        while frontier:
            start = cur_count
            leaves, feats, thrs, dls = [], [], [], []
            nxt = []
            for idx, (node, leaf) in enumerate(frontier):
                f = int(node["feature"])
                if not 0 <= f < len(mappers):
                    raise LightGBMError(
                        f"forced split feature {f} out of range")
                if mappers[f].bin_type == 1:
                    raise LightGBMError(
                        "categorical forced splits are not supported")
                tb = int(np.searchsorted(mappers[f].upper_bounds,
                                         float(node["threshold"]),
                                         side="left"))
                leaves.append(int(leaf))
                feats.append(f)
                thrs.append(tb)
                dls.append(bool(node.get("default_left", False)))
                right_id = start + idx
                if node.get("left"):
                    nxt.append((node["left"], leaf))
                if node.get("right"):
                    nxt.append((node["right"], right_id))
            cur_count = start + len(frontier)
            total += len(frontier)
            if cur_count > L:
                raise LightGBMError(
                    f"forced splits need {cur_count} leaves but num_leaves="
                    f"{L}")
            levels.append((tuple(leaves), tuple(feats), tuple(thrs),
                           tuple(dls)))
            frontier = nxt
        return tuple(levels)

    def _monotone_array(self) -> Optional[jax.Array]:
        """(F,) i32 in {-1,0,1} or None (reference: config monotone_constraints;
        monotone_constraints.hpp basic method)."""
        mc = self.config.monotone_constraints
        if mc is None or (hasattr(mc, "__len__") and len(mc) == 0):
            return None
        arr = np.asarray(mc, np.int32)
        F = self.dd.num_features
        if arr.shape[0] != F:
            raise LightGBMError(
                f"monotone_constraints has {arr.shape[0]} entries but the dataset "
                f"has {F} features")
        if not np.any(arr):
            return None
        if self.config.monotone_constraints_method not in (
                "basic", "intermediate", "advanced"):
            log_warning(
                f"monotone_constraints_method="
                f"{self.config.monotone_constraints_method!r} is not "
                "implemented; falling back to 'basic'")
        return jnp.asarray(arr)

    def _monotone_intermediate(self) -> bool:
        return (self._monotone_array() is not None
                and self.config.monotone_constraints_method
                in ("intermediate", "advanced"))

    def _interaction_group_masks(self) -> Optional[jax.Array]:
        """(C, F) bool allowed-feature groups or None (reference: col_sampler.hpp;
        config.cpp ParseInteractionConstraints)."""
        ic = self.config.interaction_constraints
        if not ic:
            return None
        if isinstance(ic, str):
            import json
            s = ic.strip()
            if not s.startswith("[["):
                s = "[" + s + "]"    # "[0,1],[2,3]" -> "[[0,1],[2,3]]"
            ic = json.loads(s)
        if ic and not isinstance(ic[0], (list, tuple)):
            ic = [ic]
        F = self.dd.num_features
        masks = np.zeros((len(ic), F), bool)
        for i, group in enumerate(ic):
            for f in group:
                if not 0 <= int(f) < F:
                    raise LightGBMError(
                        f"interaction_constraints feature index {f} out of range")
                masks[i, int(f)] = True
        return jnp.asarray(masks)

    def _check_unsupported_params(self) -> None:
        """Fail loudly on accepted-but-unimplemented parameters instead of
        silently training a different model (reference behavior: config
        validation fatals; VERDICT r1 'silently ignored parameters')."""
        c = self.config
        if c.hist_precision not in ("auto", "single", "mixed", "double"):
            raise LightGBMError(
                f"hist_precision={c.hist_precision!r} is not one of "
                "'auto', 'single', 'mixed', 'double'")
        if c.hist_backend not in HIST_BACKENDS:
            raise LightGBMError(
                f"unknown hist_backend={c.hist_backend!r}; one of "
                f"{HIST_BACKENDS}")
        if c.hist_packed_width not in (32, 16, 8):
            raise LightGBMError(
                f"hist_packed_width={c.hist_packed_width!r} is not one of "
                "32, 16, 8")
        if c.hist_packed_width != 32:
            if not c.use_quantized_grad:
                raise LightGBMError(
                    "hist_packed_width=16/8 packs the QUANTIZED int32 "
                    "grad/hess wire and needs use_quantized_grad=True "
                    "(the f32 histograms have no integer wire to pack)")
            if c.linear_tree:
                raise LightGBMError(
                    "hist_packed_width=16/8 is not supported with "
                    "linear_tree (leaf regressions feed on exact "
                    "histogram sums; the requantized wire is "
                    "documented-ulp, not exact)")
        if str(c.route_fusion).lower() not in ("auto", "on", "off"):
            raise LightGBMError(
                f"route_fusion={c.route_fusion!r} is not one of 'auto', "
                "'on', 'off'")

        def _nonempty(v):
            return v is not None and len(np.atleast_1d(v)) > 0

        if _nonempty(c.cegb_penalty_feature_lazy) and \
                len(np.atleast_1d(c.cegb_penalty_feature_lazy)) != \
                self.dd.num_features:
            raise LightGBMError(
                "cegb_penalty_feature_lazy should be the same size as the "
                "feature count")
        if _nonempty(c.cegb_penalty_feature_coupled) and \
                len(np.atleast_1d(c.cegb_penalty_feature_coupled)) != \
                self.dd.num_features:
            raise LightGBMError(
                "cegb_penalty_feature_coupled should be the same size as the "
                "feature count")
        if c.linear_tree and self.boosting_type in ("dart", "rf"):
            raise LightGBMError(
                f"linear_tree is not supported with boosting="
                f"{self.boosting_type}")
        if c.linear_tree and self.train_data.raw_data is None:
            raise LightGBMError(
                "linear_tree needs the raw feature matrix; construct the "
                "Dataset with free_raw_data=False")

    def _compute_init_score(self) -> List[float]:
        k = self.num_tree_per_iteration
        if self.objective is None or not self.config.boost_from_average:
            return [0.0] * k
        try:
            v = self.objective.boost_from_score()
        except NotImplementedError:
            v = 0.0
        if isinstance(v, (list, tuple, np.ndarray)):
            return [float(x) for x in v]
        return [float(v)] * k

    # ------------------------------------------------------------------
    def add_valid(self, valid_data, name: str, metrics: Sequence[Metric]) -> None:
        if getattr(self, "_dist_mode", False):
            # rank-aligned validation data (reference:
            # LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:307):
            # every process holds its own shard, binned with the TRAINING
            # mappers; scores live on the same row-sharded mesh as training
            if getattr(valid_data, "_dist", None) is None:
                raise LightGBMError(
                    "validation sets for distributed-loaded training must "
                    "be distributed-loaded too (load the valid file with "
                    "the same multi-process loader, reference=train_set)")
        self.valid_sets.append(valid_data)
        self.valid_names.append(name)
        self.valid_metrics.append(list(metrics))
        dd = self._valid_device_data(valid_data)
        n = dd.bins.shape[0]
        k = self.num_tree_per_iteration
        shape = (n,) if k == 1 else (n, k)
        score = self._shard_row_array(jnp.zeros(shape, jnp.float32))
        if self.iter_ == 0:
            # before training the init score is tracked separately; once trees exist
            # it is folded into tree 0 (AddBias), so catch-up sums are complete
            score = score + jnp.asarray(
                self.init_scores if k > 1 else self.init_scores[0], jnp.float32)
        base = valid_data.get_init_score_padded(n, k)
        if base is not None:
            score = score + jnp.asarray(base, jnp.float32)
        # catch up on already-trained trees
        for it in range(self.iter_):
            for kk in range(k):
                t = self.models[it * k + kk]
                score = self._add_tree_to_score(score, t, dd, kk)
        self._valid_scores.append(score)

    # ------------------------------------------------------------------
    def _valid_device_data(self, vset):
        """Device data for a validation set; distributed-loaded shards are
        assembled into one global row-sharded array (cached) exactly like
        the training data."""
        if not getattr(self, "_dist_mode", False):
            return vset.device_data()
        cache = getattr(self, "_valid_dd_cache", None)
        if cache is None:
            cache = self._valid_dd_cache = {}
        key = id(vset)
        if key not in cache:
            from ..parallel.dist_data import make_global_bins
            dd = vset.device_data()
            bins = make_global_bins(np.asarray(dd.bins), self.mesh,
                                    self._row_axis)
            cache[key] = dd._replace(bins=bins)
        return cache[key]

    def _score_to_host(self, score, n) -> np.ndarray:
        """Score vector as host numpy; multi-process global arrays gather
        their per-rank shards (rank-major row order) to every host so
        metrics — and therefore early stopping — agree on all ranks
        (reference: metrics Allreduce their sums, e.g. Network::GlobalSum)."""
        from ..telemetry import note_host_sync
        note_host_sync()
        if not getattr(self, "_dist_mode", False):
            return np.asarray(score[:n])
        from jax.experimental import multihost_utils
        shards = sorted(score.addressable_shards,
                        key=lambda sh: sh.index[0].start or 0)
        local = np.concatenate([np.asarray(sh.data) for sh in shards])
        full = multihost_utils.process_allgather(local)
        return full.reshape((-1,) + tuple(score.shape[1:]))[:n]

    def _feature_mask(self) -> jax.Array:
        f = self.dd.num_features
        frac = self.config.feature_fraction
        mask = np.ones(f, bool)
        if frac < 1.0:
            kcnt = max(1, int(round(frac * f)))
            keep = self._rng.choice(f, size=kcnt, replace=False)
            mask = np.zeros(f, bool)
            mask[keep] = True
        return jnp.asarray(mask)

    def _gh_finite(self, grad, hess):
        """One cheap jitted all-finite check over the gradient/hessian
        blocks (nan_guard; docs/ROBUSTNESS.md)."""
        if self._nan_check_fn is None:
            def _fn(g, h):
                return jnp.isfinite(g).all() & jnp.isfinite(h).all()
            self._nan_check_fn = watched_jit(_fn, name="nan_check",
                                             owner=self)
        return self._nan_check_fn(grad, hess)

    def _guard_gh(self, grad, hess, *extras):
        """nan_guard scrub: returns ``(ok_dev, grad, hess, *extras)`` with
        every array select-zeroed when the all-finite check trips — an
        all-zero gradient grows an exact single-leaf no-op tree, so the
        poisoned iteration is skipped without perturbing any later
        iteration's RNG streams.  Guard off: pass-through, ok_dev None.
        When the flag is True the selects are exact identities, so guarded
        and unguarded runs stay bit-identical."""
        if not self._nan_guard.enabled:
            return (None, grad, hess) + extras
        ok = self._gh_finite(grad, hess)
        out = tuple(jnp.where(ok, a, jnp.zeros_like(a)) if a is not None
                    else None for a in (grad, hess) + extras)
        return (ok,) + out

    def _guard_objective_state(self, old_state, ok) -> None:
        """Keep the objective's PREVIOUS per-iteration state when the guard
        tripped: gradient evaluation already wrote back state computed from
        the poisoned values (e.g. lambdarank position biases), and one NaN
        there would re-poison every later iteration's gradients."""
        if ok is None or self.objective is None:
            return
        for a, old in old_state.items():
            new = getattr(self.objective, a, None)
            if new is not None and old is not None and new is not old:
                setattr(self.objective, a, jnp.where(ok, new, old))

    def flush_nan_guard(self) -> None:
        """Resolve any deferred device flags (called at end of train()):
        the nan_guard backlog plus — on the fused-sharded path — the
        batched sampled-rows / overflow / finished fetch, so host-visible
        telemetry is final when train() returns."""
        if getattr(self, "_train_state", None) is not None \
                and self._fused_last:
            self._poll_device_flags()
        else:
            self._nan_guard.poll()

    @property
    def nan_iterations(self) -> int:
        """Boosting iterations skipped by nan_guard so far."""
        self._nan_guard.poll()
        return self._nan_guard.hits

    def _boost(self) -> Tuple[jax.Array, jax.Array]:
        """Gradient computation (reference: GBDT::Boosting, gbdt.cpp:229)."""
        if self.objective is None:
            raise LightGBMError("cannot boost without an objective "
                                "(use custom-gradient update)")
        if self._grow_params.hist_double:
            # mirror the reference's arithmetic: gradients evaluated in
            # double, stored as score_t=float32 (objective_function.h
            # GetGradients writes score_t from double expressions)
            with self._grow_x64_ctx():
                grad, hess = self.objective.get_gradients(
                    self._unpad_score().astype(jnp.float64))
                grad = grad.astype(jnp.float32)
                hess = hess.astype(jnp.float32)
        else:
            grad, hess = self.objective.get_gradients(self._unpad_score())
        # eager-chain dispatch accounting (telemetry launches counter):
        # slice + grad + hess + pads is a LOWER bound — each eager jnp op
        # is its own XLA execution and real objectives run ~10
        from ..telemetry import note_launch
        note_launch(4)
        return self._pad_gh(grad), self._pad_gh(hess)

    def _unpad_score(self):
        return self.score[:self.num_data]

    def _pad_gh(self, a):
        n = self.dd.bins.shape[0]
        if a.shape[0] == n:
            return a
        pad = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad)

    def _ensure_grad_meta(self):
        if getattr(self, "_grad_attr_names", None) is None:
            objective = self.objective
            self._grad_attr_names = [
                a for a in objective.data_bound_attrs()
                if getattr(objective, a, None) is not None]
            # per-iteration state (e.g. lambdarank position biases) threads
            # through the jit as argument + output so the trace stays pure
            self._grad_state_names = list(objective.state_attrs())
            self._bound_rows = self._shard_bind()

    def _shard_bind(self):
        """One-time placement of the objective's per-row arrays (label,
        weight, ...) for an in-process row-sharded mesh: padded on the host
        to the table's rows and put on the row sharding, so the gradient
        program reads them where its score lives — an array left on the
        default device is placed again at every launch.  The objective's
        own attributes go back to the host (nothing of them stays on device
        0; its eager methods take NumPy as the multi-process path's do).
        -> {attr: sharded array}, empty where it does not apply (no row
        sharding, multi-process, or an objective that binds per-query
        lists)."""
        if self._row_sharding is None or self._dist_mode:
            return {}
        obj, n = self.objective, self.dd.bins.shape[0]
        held = {a: getattr(obj, a) for a in self._grad_attr_names}
        if not held or any(getattr(v, "ndim", 0) < 1
                           or v.shape[0] != self.num_data
                           for v in held.values()):
            return {}
        bound = {}
        with _tel_tracer.boundary("GBDT::ShardBind", rows=n,
                                  arrays=len(held)) as bind:
            for a, v in held.items():
                host = np.asarray(v)
                setattr(obj, a, host)
                pad = [(0, n - host.shape[0])] + [(0, 0)] * (host.ndim - 1)
                bound[a] = self._shard_row_array(np.pad(host, pad))
            jax.block_until_ready(bound)
            bind.set(**device_hbm_bytes())
        return bound

    def _bound_objective(self):
        """The objective's arrays the gradient programs take as arguments:
        its bound per-row arrays (their sharded copies where _shard_bind
        made them) and its per-iteration state."""
        bound = {a: getattr(self.objective, a)
                 for a in self._grad_attr_names + self._grad_state_names}
        bound.update(self._bound_rows)
        return bound

    def _gradient_graph(self, score, bound, pad_mask, qkey, quantize=True):
        """Traced gradient chain shared by the fused-gradient and
        fused-iteration jits: rebinds the objective's captured arrays from
        `bound`, evaluates gradients (in double under hist_precision=double
        — the reference's score_t arithmetic), pads/masks, optionally
        quantizes (``quantize=False`` defers it — the fused sampled path
        must scale gradients BEFORE the quantization grid, matching the
        eager order). Returns (g, h, gq, hq, scales_or_None, new_state)."""
        objective, num_data = self.objective, self.num_data
        quant = self.config.use_quantized_grad and quantize
        qbins = self.config.num_grad_quant_bins
        qstoch = self.config.stochastic_rounding
        double = self._grow_params.hist_double
        attr_names = self._grad_attr_names + self._grad_state_names
        state_names = self._grad_state_names
        old = {a: getattr(objective, a) for a in attr_names}
        for a in attr_names:
            setattr(objective, a, bound[a])
        try:
            # bound arrays that _shard_bind padded to the table's rows take
            # the whole padded score: nothing is cut from a sharded array
            s = score if self._bound_rows else score[:num_data]
            if double:
                g, h = objective.get_gradients(s.astype(jnp.float64))
                g = g.astype(jnp.float32)
                h = h.astype(jnp.float32)
            else:
                g, h = objective.get_gradients(s)
            new_state = {a: getattr(objective, a) for a in state_names}
        finally:
            for a in attr_names:
                setattr(objective, a, old[a])
        n = score.shape[0]
        if n != g.shape[0]:
            pad = [(0, n - num_data)] + [(0, 0)] * (g.ndim - 1)
            g, h = jnp.pad(g, pad), jnp.pad(h, pad)
        pm = pad_mask if g.ndim == 1 else pad_mask[:, None]
        g, h = g * pm, h * pm
        if quant:
            gq, hq, sc = quantize_gh(g, h, qkey, qbins, qstoch)
            return g, h, gq, hq, sc, new_state
        return g, h, g, h, None, new_state

    def _boost_padded(self):
        """Gradients + pad masking as ONE compiled program. Eagerly, the
        ~10-op gradient chain costs one runtime launch each (per-launch
        overhead on a local chip: not measured); fused it is one launch.
        The objective's captured label/weight are rebound to jit arguments
        during tracing (closure-captured device arrays embed as HLO
        constants, which bloats the program at 10M rows)."""
        if self._grad_fn is None:
            self._ensure_grad_meta()

            def _fn(score, bound, pad_mask, qkey):
                return self._gradient_graph(score, bound, pad_mask, qkey)

            self._grad_fn = watched_jit(_fn, name="gradients", owner=self)
        qkey = jax.random.PRNGKey(
            (self.config.data_random_seed + 11) * 131071 + self.iter_)
        bound = self._bound_objective()
        with self._grow_x64_ctx():
            out = self._grad_fn(self.score, bound, self._pad_mask, qkey)
        for a, v in out[5].items():
            setattr(self.objective, a, v)
        return out[:5]

    def _use_batched_multiclass(self) -> bool:
        """Eligibility for the WIDENED lockstep multiclass path
        (ops.grow.grow_tree_k): one histogram contraction per growth round
        serves all K classes' gradient channels, instead of the per-class
        lax.scan rebuilding the class-independent one-hot construct K
        times. LGBTPU_MULTICLASS_BATCHED=1/0 forces the choice (A/B
        experiments); config multiclass_batched=False opts out."""
        import os as _os
        force = _os.environ.get("LGBTPU_MULTICLASS_BATCHED", "")
        if force == "0":
            return False
        # everything below the env hook is static for the training run —
        # evaluate once (the forced-splits gate re-reads a JSON file)
        cached = getattr(self, "_mc_batched_static", None)
        if cached is None:
            gp = self._grow_params
            # voting/feature learners have no grow_tree_k lockstep yet —
            # their K class trees ride the per-class lax.scan instead
            ok = (gp.plain_growth and not self._needs_grow_key
                  and not getattr(self, "_voting", False)
                  and not self._feature_mode
                  and self._parse_forced_splits() is None)
            if ok and gp.hist_backend == "stream":
                # the widened (m_rows, 2*S*K) histogram block stays VMEM-
                # resident across the whole kernel grid; past ~12 MB the
                # scan path (per-class blocks) is the safe fallback
                K = self.num_tree_per_iteration
                S = min(gp.max_splits_per_round, max(gp.num_leaves - 1, 1))
                Bpad = -(-self.dd.max_bins // 8) * 8
                if gp.bin_buckets is not None:
                    from ..binning import bucket_run_rows
                    m_rows = -(-sum(bucket_run_rows(b, g)
                                    for b, g in gp.bin_buckets) // 128) * 128
                else:
                    m_rows = self.dd.num_groups * Bpad
                ok = m_rows * 2 * S * K * 4 <= 12 * 2 ** 20
            cached = self._mc_batched_static = ok
        if not cached:
            return False
        return force == "1" or self.config.multiclass_batched

    def _grow_classes_batched(self, grad, hess, mask, col_mask, gh_scales,
                              k: int, compact_rows: int = 0):
        """All K class trees from ONE widened lockstep program
        (ops.grow.grow_tree_k): the dominant one-hot bin construct and its
        MXU contraction are built once per growth round and contract
        against the stacked (N, 2K) grad/hess channel block."""
        if self._grow_fn_kb is None:
            from ..ops.grow import grow_tree_k
            dd = self.dd
            gp = self._grow_params
            mesh = (self.mesh if (self._mesh_stream or self._mesh_2d)
                    else None)
            row_axis = self._row_axis
            feature_axis = self._feature_axis if self._mesh_2d else None

            def _fn(bins, grad2, hess2, mask, colm, packed, scales,
                    compact_rows=0):
                return grow_tree_k(bins, grad2.T, hess2.T, mask, colm,
                                   layout=dd.layout, routing=dd.routing,
                                   params=gp, packed=packed,
                                   gh_scales=scales, mesh=mesh,
                                   row_axis=row_axis,
                                   feature_axis=feature_axis,
                                   compact_rows=compact_rows)

            self._grow_fn_kb = watched_jit(_fn, name="grow_tree_k",
                                           owner=self,
                                           static_argnames=("compact_rows",))
        scales = (jnp.transpose(gh_scales) if gh_scales is not None
                  else jnp.zeros((k, 2), jnp.float32))
        arrays_k, leaf_k = self._grow_fn_kb(
            self.dd.bins, grad, hess, mask, col_mask, self._packed, scales,
            compact_rows=compact_rows)
        self._mc_stacked = (arrays_k, leaf_k)
        return [(jax.tree.map(lambda a, i=kk: a[i], arrays_k), leaf_k[kk])
                for kk in range(k)]

    def _grow_classes(self, grad, hess, mask, col_mask, gh_scales, k: int,
                      compact_rows: int = 0):
        """Grow all K class trees inside one jitted program: the widened
        lockstep path (grow_tree_k) when eligible, else a lax.scan over
        classes (one launch per iteration either way; reference: the
        per-class tree loop in GBDT::TrainOneIter, gbdt.cpp:412)."""
        self._mc_batched_last = self._use_batched_multiclass()
        if self._mc_batched_last:
            return self._grow_classes_batched(grad, hess, mask, col_mask,
                                              gh_scales, k, compact_rows)
        if self._grow_fn_k is None:
            grow = self._grow_partial
            needs_key = self._needs_grow_key

            def _fn(bins, grad2, hess2, mask, colm, packed, scales, keys,
                    compact_rows=0):
                def body(_, xs):
                    g, h, key1, sc = xs
                    arrays, lid = grow(
                        bins, g, h, mask, colm,
                        key=(key1 if needs_key else None),
                        packed=packed, cegb_used=None, gh_scales=sc,
                        compact_rows=compact_rows)
                    return None, (arrays, lid)

                _, out = jax.lax.scan(
                    body, None, (grad2.T, hess2.T, keys, scales))
                return out

            self._grow_fn_k = watched_jit(_fn, name="grow_tree_k_scan",
                                          owner=self,
                                          static_argnames=("compact_rows",))
        keys = jnp.stack([
            jax.random.PRNGKey((self.config.extra_seed or 3) * 1000003
                               + self.iter_ * (k + 1) + kk)
            for kk in range(k)])
        scales = (jnp.transpose(gh_scales) if gh_scales is not None
                  else jnp.zeros((k, 2), jnp.float32))
        arrays_k, leaf_k = self._grow_fn_k(
            self.dd.bins, grad, hess, mask, col_mask, self._packed,
            scales, keys, compact_rows=compact_rows)
        self._mc_stacked = (arrays_k, leaf_k)
        return [(jax.tree.map(lambda a, i=kk: a[i], arrays_k), leaf_k[kk])
                for kk in range(k)]

    def _can_fuse_iteration(self) -> bool:
        """Whole-iteration fusion (gradients -> sampling -> grow -> score
        update as ONE launch per iteration, docs/DISTRIBUTED.md "fused
        iteration & sharded state").

        Default ON for single-chip TPU (one launch and no host sync per
        iteration; the time it saves on a local chip is not measured) and
        for ANY row-sharded stream mesh — under a mesh every
        extra dispatch pays per-device coordination on top of the fixed
        launch latency, exactly the regime docs/PERF.md:290-296 predicted
        would dominate after the comms payload fix.  Single-chip CPU
        keeps the unfused path (XLA:CPU re-fuses the gradient chain with
        last-ulp differences, which would break the serial byte-identity
        suite).  config ``fused_iter=on|off`` and ``LGBTPU_FUSE_ITER=1/0``
        force the choice (A/B experiments, tests)."""
        c = self.config
        import os as _os
        force = _os.environ.get("LGBTPU_FUSE_ITER", "")
        mode = str(c.fused_iter).strip().lower()
        if force == "0" or (mode == "off" and force != "1"):
            return False
        base = (not _chaos.has("nan_grad")   # chaos injects eagerly
                and not c.linear_tree
                and self._cegb_used is None
                and not self._dist_mode     # multi-process keeps the
                                            # eager path (rank-local numpy
                                            # rebinds, barrier telemetry)
                and self.objective is not None
                and self.objective.jit_safe_gradients
                and not self.objective.need_renew_leaf
                and not (c.use_quantized_grad and c.quant_train_renew_leaf))
        if not base:
            return False
        if self.num_tree_per_iteration > 1 \
                and not self._use_batched_multiclass():
            return False   # the per-class scan stays on the eager path
        # default ON for every mesh learner: the row-sharded stream path,
        # the voting (PV-Tree) learner, and the feature-parallel learner —
        # each extra dispatch pays per-device coordination under a mesh
        return (force == "1" or mode == "on"
                or on_tpu()
                or (self.mesh is not None
                    and (self._mesh_stream or self._voting
                         or self._feature_mode or self._mesh_2d)))

    # ------------------------------------------------------------------
    def _shard_leaf_array(self, a):
        """Place a (K, N) class-major leaf-id array on the mesh (rows are
        the LAST axis, unlike _shard_row_array's (N, K) scores)."""
        if self._row_sharding is None or a.ndim == 1:
            return self._shard_row_array(a)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(
            a, NamedSharding(self._row_sharding.mesh,
                             P(None, self._row_sharding.spec[0])))

    def _ensure_train_state(self):
        """The ShardedTrainState this run's fused iterations thread.

        Rebuilt whenever ``self.score`` was reassigned outside the fused
        step (checkpoint restore, rollback, DART/RF score juggling) —
        the identity check makes external score surgery safe without any
        explicit invalidation protocol."""
        from ..parallel.sharded_state import ShardedTrainState
        st = getattr(self, "_train_state", None)
        if st is not None and st.score is self.score:
            return st
        k = self.num_tree_per_iteration
        n = self.dd.bins.shape[0]
        zs = self._shard_row_array(jnp.zeros_like(self.score))
        lid = self._shard_leaf_array(
            jnp.zeros(n if k == 1 else (k, n), jnp.int32))
        st = ShardedTrainState(
            score=self.score, grad=zs, hess=zs, leaf_id=lid,
            mask=self._pad_mask,
            key=jax.random.PRNGKey(0),
            sampled=jnp.asarray(0, jnp.int32),
            overflow=jnp.asarray(0, jnp.int32),
            finished=jnp.asarray(False),
            ok=jnp.asarray(True),
            hist_passes=jnp.asarray(0, jnp.int32),
            hist_small_passes=jnp.asarray(0, jnp.int32),
            scan_slots=jnp.asarray(0, jnp.int32))
        self._train_state = st
        self._overflow_seen = 0
        self._hist_passes_seen = 0, 0, 0
        self._poll_iter_seen = self.iter_
        return st

    def _fused_compact_rows(self, sample_mode: str, mask_arg=None) -> int:
        """Static per-shard compaction capacity for the fused path.

        Bagging reuses the eager per-epoch count readback (the mask is
        epoch-cached host-side, so the sync amortizes over bagging_freq
        iterations).  GOSS draws a fresh in-jit mask every iteration, so
        the capacity is ANALYTIC — expected in-bag fraction plus a
        binomial + top-skew margin — and the fused program counts
        overflows into the state so the batched poll can disable
        compaction and warn if the margin is ever breached (out-of-bag
        pad rows carry exact-zero weights, so any covering capacity grows
        the identical tree)."""
        if sample_mode == "none" or getattr(self, "_compact_overflow", False):
            return 0
        import os as _os
        cmode = str(_os.environ.get("LGBTPU_COMPACT", "")
                    or self.config.row_compaction).strip().lower()
        if cmode not in ("auto", "off", "pad"):
            # same contract as the eager path: an LGBTPU_COMPACT typo must
            # not silently run as "auto" (or silently disable compaction)
            raise LightGBMError(
                f"LGBTPU_COMPACT={cmode!r} is not one of 'auto', 'off', "
                "'pad'")
        gp = self._grow_params
        eligible = (cmode in ("auto", "pad")
                    and (self.mesh is None or self._mesh_stream
                         or self._voting or self._feature_mode))
        if not eligible:
            return 0
        n_rows = self.dd.bins.shape[0]
        D = 1
        if self.mesh is not None and self._row_axis is not None:
            D = int(self.mesh.shape[self._row_axis])
        local = n_rows // D
        unit = self._pack_block
        if cmode == "pad":
            return -(-local // unit) * unit
        if sample_mode == "bagging":
            # identical capacity rule to the eager path — the per-epoch
            # mask is host-known (built once per iteration by _iter_fused,
            # passed in here) and its count readback is cached
            return self._row_compaction_capacity(mask_arg * self._pad_mask)
        frac = self.sample_strategy.expected_fraction(self.iter_)
        exp = frac * local
        # top-a rows are chosen by a GLOBAL threshold, so a shard may hold
        # more than its share; 25% relative headroom plus six binomial
        # sigma covers both the b-sample jitter and moderate top skew —
        # a breach only costs a warning + fallback, never a wrong tree
        # left unflagged (the poll checks state.overflow)
        sigma = float(np.sqrt(max(local * frac * (1.0 - frac), 1.0)))
        q = max(unit, -(-local // (32 * unit)) * unit)
        cap = -(-int(1.25 * exp + 6.0 * sigma) // q) * q
        cap = max(unit, cap)
        if cap * 4 >= local * 3 or cap >= local:
            return 0   # <25% savings: the partition + route pass would eat it
        if not (self._compact_cap and cap <= self._compact_cap < local):
            with _tel_tracer.boundary("GBDT::SamplePlan", rows=local,
                                      expected_fraction=frac, capacity=cap):
                self._compact_cap = cap
        return self._compact_cap

    def _sample_mode(self) -> str:
        """This iteration's sampler as the program variants name it:
        "bagging" takes the epoch mask as an argument, "goss" derives its
        mask in-trace from the gradients, "none" samples nothing (no
        strategy, or GOSS's warm-up)."""
        strategy = self.sample_strategy
        mode = ("none" if not strategy.is_active()
                else strategy.fused_mode(self.iter_))
        if mode not in ("none", "mask_arg", "traced"):
            raise LightGBMError(
                f"unknown fused sample mode {mode!r} from "
                f"{type(strategy).__name__}")
        return {"none": "none", "mask_arg": "bagging",
                "traced": "goss"}[mode]

    def _iter_fused(self):
        """Gradients + sampling + tree growth + train-score update as ONE
        compiled launch per boosting iteration, with the training state
        held permanently device-sharded (ShardedTrainState; out-sharding
        == in-sharding so no implicit re-shard or host round trip ever
        touches a row-axis array between iterations).  Returns the new
        state and the stacked TreeArrays."""
        k = self.num_tree_per_iteration
        strategy = self.sample_strategy
        sample_mode = self._sample_mode()
        mask_arg = self._pad_mask
        if sample_mode == "bagging":
            mask_arg = self._shard_row_array(
                strategy.epoch_mask(self.iter_))
        compact = self._fused_compact_rows(sample_mode, mask_arg)
        if self._iter_fn is None:
            self._ensure_grad_meta()
            from ..parallel.sharded_state import (ShardedTrainState,
                                                  state_shardings)
            grow = self._grow_partial
            guarded = self._nan_guard.enabled
            quant = self.config.use_quantized_grad
            qbins = self.config.num_grad_quant_bins
            qstoch = self.config.stochastic_rounding
            dd, gp = self.dd, self._grow_params
            mesh = (self.mesh if (self._mesh_stream or self._mesh_2d)
                    else None)
            row_axis = self._row_axis
            feature_axis = self._feature_axis if self._mesh_2d else None
            # per-shard overflow detection wherever rows are sharded
            # (stream data-parallel AND voting); feature mode replicates
            # rows, so its one "shard" is the full row count
            D = (int(self.mesh.shape[row_axis])
                 if self.mesh is not None and row_axis is not None else 1)
            gather = (self._leaf_gather_fn()
                      if self._use_leaf_gather_kernel else None)
            def _fn(state, bound, pad_mask, mask_arg, qkey, skey, gkey,
                    bins, colm, packed, rate, compact_rows=0,
                    sample_mode="none"):
                g, h, gq, hq, sc, new_obj = self._gradient_graph(
                    state.score, bound, pad_mask, qkey,
                    quantize=(sample_mode == "none"))
                ok = jnp.asarray(True)
                if guarded:
                    # nan_guard inside the one-launch program: a tripped
                    # check zeroes the growing inputs (exact no-op tree,
                    # score delta 0) and keeps the objective's PREVIOUS
                    # state; the flag is read at the batched poll so the
                    # fused path keeps its async pipeline
                    ok = jnp.isfinite(g).all() & jnp.isfinite(h).all()
                    g = jnp.where(ok, g, jnp.zeros_like(g))
                    h = jnp.where(ok, h, jnp.zeros_like(h))
                    gq = jnp.where(ok, gq, jnp.zeros_like(gq))
                    hq = jnp.where(ok, hq, jnp.zeros_like(hq))
                    if sc is not None:
                        sc = jnp.where(ok, sc, jnp.zeros_like(sc))
                    new_obj = {a: jnp.where(ok, v, bound[a])
                               for a, v in new_obj.items()}
                # ---- sampling (same keys/arithmetic as the eager path,
                # so fused and unfused draws are identical) ----
                mask = pad_mask
                if sample_mode == "bagging":
                    m = mask_arg
                    gq = gq * m if gq.ndim == 1 else gq * m[:, None]
                    hq = hq * m if hq.ndim == 1 else hq * m[:, None]
                    mask = m * pad_mask
                elif sample_mode == "goss":
                    m, gq, hq = strategy.sample_traced(skey, gq, hq)
                    mask = m * pad_mask
                if sample_mode != "none" and quant:
                    gq, hq, sc = quantize_gh(gq, hq, qkey, qbins, qstoch)
                # per-shard in-bag counts: the compaction capacity is per
                # shard, so overflow detection must see the FULLEST shard
                per_shard = (mask > 0).reshape(D, -1).sum(axis=1,
                                                          dtype=jnp.int32)
                nc = jnp.sum(per_shard)
                over = state.overflow
                if compact_rows:
                    over = over + (jnp.max(per_shard)
                                   > compact_rows).astype(jnp.int32)
                # ---- growth + score update ----
                rate32 = jnp.float32(rate)
                if k == 1:
                    arrays, leaf_id, grown = grow(
                        bins, gq, hq, mask, colm, key=gkey, packed=packed,
                        cegb_used=None, gh_scales=sc,
                        compact_rows=compact_rows, with_passes=True)
                    lv = arrays.leaf_value * rate32
                    delta = (gather(leaf_id, lv) if gather is not None
                             else lv[leaf_id])
                    new_score = state.score + delta
                    fin = arrays.num_leaves <= 1
                else:
                    from ..ops.grow import grow_tree_k
                    scales = (jnp.transpose(sc) if sc is not None
                              else jnp.zeros((k, 2), jnp.float32))
                    arrays, leaf_id, grown = grow_tree_k(
                        bins, gq.T, hq.T, mask, colm, layout=dd.layout,
                        routing=dd.routing, params=gp, packed=packed,
                        gh_scales=scales, mesh=mesh, row_axis=row_axis,
                        feature_axis=feature_axis,
                        compact_rows=compact_rows, with_passes=True)
                    # stacked score add — same arithmetic as score_add_k
                    Lk = arrays.leaf_value.shape[1]
                    flat = arrays.leaf_value.reshape(-1) * rate32
                    off = (jnp.arange(k) * Lk)[:, None]
                    new_score = state.score + flat[leaf_id + off].T
                    fin = jnp.all(arrays.num_leaves <= 1)
                if guarded:
                    # a nan-skipped iteration grows a trivial tree by
                    # design — it must not read as "no more splits"
                    fin = fin & ok
                new_state = ShardedTrainState(
                    score=new_score, grad=g, hess=h, leaf_id=leaf_id,
                    mask=mask, key=qkey, sampled=nc, overflow=over,
                    finished=fin, ok=ok,
                    hist_passes=state.hist_passes + grown[0],
                    hist_small_passes=state.hist_small_passes + grown[1],
                    scan_slots=state.scan_slots + grown[2])
                return new_state, arrays, new_obj

            out_sh = None
            st_sh = state_shardings(
                self.mesh if (self._row_sharding is not None
                              or self._feature_mode) else None,
                self._row_axis, k, replicate_rows=self._feature_mode)
            if st_sh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                from ..tree import TreeArrays as _TA
                rep = NamedSharding(self.mesh, P())
                arrays_sh = _TA(*([rep] * len(_TA._fields)))
                obj_sh = {a: rep for a in self._grad_state_names}
                out_sh = (st_sh, arrays_sh, obj_sh)
            jit_kw = {"out_shardings": out_sh} if out_sh is not None else {}
            self._iter_fn = watched_jit(
                _fn, name="fused_iter", owner=self,
                static_argnames=("compact_rows", "sample_mode"), **jit_kw)
        state = self._ensure_train_state()
        qkey = jax.random.PRNGKey(
            (self.config.data_random_seed + 11) * 131071 + self.iter_)
        gkey = None
        if self._needs_grow_key:
            gkey = jax.random.PRNGKey(
                (self.config.extra_seed or 3) * 1000003 + self.iter_ * 2)
        skey = strategy.traced_key(self.iter_)
        if skey is None:
            skey = jnp.zeros(2, jnp.uint32)
        bound = self._bound_objective()
        with self._grow_x64_ctx():
            new_state, arrays, new_obj = self._iter_fn(
                state, bound, self._pad_mask, mask_arg, qkey, skey, gkey,
                self.dd.bins, self._feature_mask(), self._packed,
                self._shrinkage_rate(), compact_rows=compact,
                sample_mode=sample_mode)
        for a, v in new_obj.items():
            setattr(self.objective, a, v)
        self._train_state = new_state
        self._last_compact_rows = compact
        self._last_sample_mode = sample_mode
        self._fused_last = True
        return new_state, arrays

    def _poll_device_flags(self) -> bool:
        """ONE batched device->host fetch for every flag the host loop
        needs — the finished flag, the nan_guard backlog, the in-bag row
        count, and the compaction-overflow counter — issued once per
        ``eval_fetch_freq`` iterations instead of one blocking read per
        flag per iteration (each readback drains the device queue and
        serializes the pipelined step; its cost on a local chip is not
        measured)."""
        st = getattr(self, "_train_state", None)
        pending = self._nan_guard.take_pending()
        fetch = [self._finished_dev] + [ok for _, ok in pending]
        if st is not None:
            fetch += [st.sampled, st.overflow, st.hist_passes,
                      st.hist_small_passes, st.scan_slots]
        from ..telemetry import (hist_pass_count, hist_small_pass_count,
                                 note_hist_passes, note_host_sync,
                                 scan_slot_count)
        comm = self._poll_comm_fields()
        with _tel_tracer.boundary("GBDT::FlagPoll",
                                  iteration=self.iter_) as poll:
            # read BEFORE the blocking fetch, while the device still works
            # through its backlog: what training holds with its launches in
            # flight, at no cost to the loop (the host would wait anyway)
            poll.set(**device_hbm_bytes())
            got = jax.device_get(fetch)
            if st is not None:
                # the device's count of histogram passes rides the fetch:
                # publish what it grew since the last poll
                sampled, overflow, *now = (int(v) for v in got[-5:])
                passes, small, slots = (
                    a - b for a, b in zip(now, self._hist_passes_seen))
                # host arithmetic on the same count: under the row mesh
                # every one of those passes reduced its histogram, a
                # tree's first the root's one slot, the others a round's
                reduced = passes if comm["hist_comm_bytes_round"] else 0
                roots = min(reduced, (self.iter_ - self._poll_iter_seen)
                            * self._comms_model()["trees_per_iter"]
                            ) if reduced else 0
                note_hist_passes(
                    passes, self.iter_, small, slots, comm_rounds=reduced,
                    comm_bytes=comm["hist_reduce_limbs"] * (
                        roots * comm["hist_comm_bytes_root"]
                        + (reduced - roots) * comm["hist_comm_bytes_round"]))
                self._hist_passes_seen = now
                self._poll_iter_seen = self.iter_
                poll.set(hist_passes=hist_pass_count(),
                         hist_small_passes=hist_small_pass_count(),
                         scan_slots=scan_slot_count(),
                         **({"root_pass": self._root_pass}
                            if self._root_pass else {}),
                         **self._poll_tiling, **comm,
                         **self._poll_sampling_fields(sampled, overflow))
        note_host_sync()
        self._nan_guard.resolve(pending, got[1:1 + len(pending)])
        if st is not None:
            self._last_sampled_rows = sampled
            if overflow > getattr(self, "_overflow_seen", 0):
                self._overflow_seen = overflow
                if not getattr(self, "_compact_overflow", False):
                    self._compact_overflow = True
                    log_warning(
                        "fused iteration: a shard's in-bag row count "
                        "exceeded the analytic compaction capacity "
                        f"({self._last_compact_rows}); trees since the "
                        "last poll trained on a truncated sample — "
                        "disabling row compaction for the rest of this "
                        "run (set row_compaction=off to silence)")
        return bool(got[0])

    def train_one_iter(self, grad: Optional[jax.Array] = None,
                       hess: Optional[jax.Array] = None) -> bool:
        """One boosting iteration (reference: GBDT::TrainOneIter, gbdt.cpp:353).
        Returns True if no further training is possible (all-zero trees).

        The core step always runs inside the ``GBDT::Iteration`` boundary
        span (a profiler step annotation + one ring record, about 2 us).
        With telemetry enabled it also emits one structured record (wall
        time, phase splits, leaf count, memory) per iteration."""
        # 1-based, matching the record _emit_iter_record writes after the
        # impl increments iter_ — span N and JSONL row N are the same step
        step = _tel_tracer.boundary("GBDT::Iteration", step=True,
                                    step_num=self.iter_ + 1,
                                    booster=self.boosting_type)
        if not _tel_tracer.enabled:
            with step:
                return self._train_one_iter_impl(grad, hess)
        t0 = time.perf_counter()
        ph0 = _tel_tracer.phase_snapshot()
        cost0 = _tel_cost.dispatch_totals()
        with step:
            finished = self._train_one_iter_impl(grad, hess)
        self._emit_iter_record(t0, ph0, cost0, finished)
        return finished

    def _emit_iter_record(self, t0: float, ph0: Dict[str, float],
                          cost0: Tuple[float, float],
                          finished: bool) -> None:
        """One telemetry record per boosting iteration.

        NOTE: reading the new tree's leaf count is a device->host sync;
        telemetry mode deliberately trades the async pipeline for
        visibility (the reference's USE_TIMETAG build makes the same
        trade). Phase splits are diffs of the tracer's cumulative span
        totals, so between-iteration work (eval of the previous
        iteration) lands in the next record."""
        wall = time.perf_counter() - t0
        ph1 = _tel_tracer.phase_snapshot()
        phases = {}
        for span_name, key in _PHASE_KEYS.items():
            d = ph1.get(span_name, 0.0) - ph0.get(span_name, 0.0)
            if d > 0.0:
                phases[key] = round(d, 6)
        k = self.num_tree_per_iteration
        num_leaves = None
        # on the fused-sharded path the per-iteration leaf-count readback
        # would serialize the one-launch pipeline — fetch it only at the
        # batched-poll iterations (docs/DISTRIBUTED.md readback policy)
        fused_skip = (getattr(self, "_fused_last", False)
                      and self.iter_ % self._finished_check_every != 0)
        if self._lazy_trees and not fused_skip:
            tail = self._lazy_trees[-min(k, len(self._lazy_trees)):]
            got = jax.device_get([e["arrays"].num_leaves for e in tail])
            from ..telemetry import note_host_sync
            note_host_sync()
            num_leaves = int(np.sum(got))
        elif self._models_list and not fused_skip:
            num_leaves = int(sum(t.num_leaves
                                 for t in self._models_list[-k:]))
        rec: Dict[str, Any] = {
            "event": "iteration", "iteration": self.iter_,
            "trees": self.iter_ * k, "wall_s": round(wall, 6),
            "phases": phases, "num_leaves": num_leaves,
            "finished": bool(finished), **memory_snapshot()}
        if self._last_sampled_rows is not None:
            # GOSS/bagging: rows that actually fed this iteration's
            # histograms, plus the per-shard compaction capacity the grow
            # programs ran at (0 = dense masking)
            rec["sampled_rows"] = self._last_sampled_rows
            rec["compact_rows"] = self._last_compact_rows
            if (self._last_compact_rows > 0
                    and self._grow_params.hist_backend == "stream"):
                rec["pass_rows"] = self._pass_rows(self._last_sampled_rows)
            _tel_registry.gauge("train/sampled_rows",
                                self._last_sampled_rows)
        # ---- histogram formulation (docs/PERF.md floor A/B) ----
        gp = self._grow_params
        rec["hist_backend"] = gp.hist_backend
        if gp.int_hist and gp.hist_packed_width != 32 \
                and self.mesh is not None:
            rec["hist_packed_width"] = gp.hist_packed_width
        n_route = self._route_only_passes_per_tree() * k
        rec["route_only_passes"] = n_route
        if n_route:
            _tel_registry.inc("hist/route_only_passes", n_route)
        # ---- comms: analytic histogram payload + measured barrier wait ----
        cm = self._comms_model()
        if cm is not None:
            rec["comms_mode"] = cm["mode"]
            rec["comms_bytes"] = cm["per_iter_bytes"]
            _tel_registry.inc("comms/hist_bytes", cm["per_iter_bytes"])
            _tel_registry.gauge("comms/hist_bytes_per_round",
                                cm["per_round_bytes"])
        comms_wait = None
        if jax.process_count() > 1:
            # hosts that finish the local step early wait here for the
            # stragglers — the barrier time is the iteration's comms/skew
            # wait, separable from local compute (wall_s measured above)
            b0 = time.perf_counter()
            try:
                from jax.experimental import multihost_utils
                with _tel_tracer.span("GBDT::CommsBarrier"):
                    multihost_utils.sync_global_devices(
                        f"lgbtpu_iter_{self.iter_}")
                comms_wait = time.perf_counter() - b0
            except Exception:
                comms_wait = None
        if comms_wait is not None:
            rec["comms_wait_s"] = round(comms_wait, 6)
            rec["compute_s"] = round(wall, 6)
        self._tel_comms_waits.append(comms_wait or 0.0)
        if len(self._tel_comms_waits) > 1024:
            del self._tel_comms_waits[:512]
        # device-cost accounting: dispatch-weighted XLA flops and HBM
        # bytes this iteration executed (telemetry/costmodel.py) — the
        # fields that tell compute growth from dispatch/comms growth when
        # s/tree regresses (docs/OBSERVABILITY.md)
        if _tel_cost.active():
            cf, cb = _tel_cost.dispatch_totals()
            rec["flops"] = cf - cost0[0]
            rec["hbm_bytes"] = cb - cost0[1]
            _tel_registry.inc("cost/flops", rec["flops"])
            _tel_registry.inc("cost/hbm_bytes", rec["hbm_bytes"])
        # dispatch accounting: watched_jit launches and noted host syncs
        # this iteration consumed (window means feed the straggler
        # report's `bottleneck: dispatch` classification)
        from ..telemetry import host_sync_count, launch_count
        l1, s1 = launch_count(), host_sync_count()
        l0, s0 = getattr(self, "_tel_disp0", (l1, s1))
        self._tel_disp0 = (l1, s1)
        rec["launches"] = l1 - l0
        rec["host_syncs"] = s1 - s0
        self._tel_launches.append(l1 - l0)
        self._tel_syncs.append(s1 - s0)
        if len(self._tel_launches) > 1024:
            del self._tel_launches[:512]
            del self._tel_syncs[:512]
        _tel_registry.record(rec)
        _tel_registry.inc("train/iterations")
        _tel_registry.observe("train/iteration", wall)
        _tel_tracer.counter("iteration_wall_ms", wall=wall * 1e3)
        if num_leaves is not None:
            _tel_tracer.counter("tree_leaves", leaves=num_leaves)
        hbm = rec.get("peak_hbm_gb")
        if hbm:
            _tel_registry.gauge("train/peak_hbm_gb", hbm)
            _tel_tracer.counter("hbm_gb", gb=hbm)
        self._tel_iter_times.append(wall)
        if len(self._tel_iter_times) > 1024:
            del self._tel_iter_times[:512]
        K = int(getattr(self.config, "telemetry_straggler_every", 0) or 0)
        if K > 0 and self.iter_ > 0 and self.iter_ % K == 0 \
                and jax.process_count() > 1:
            from ..parallel.straggler import straggler_report
            straggler_report(
                self._tel_iter_times[-K:],
                warn_skew=self.config.telemetry_straggler_skew,
                comms_waits=self._tel_comms_waits[-K:],
                launches_per_iter=float(np.mean(self._tel_launches[-K:])),
                host_syncs_per_iter=float(np.mean(self._tel_syncs[-K:])))

    def _train_one_iter_impl(self, grad: Optional[jax.Array] = None,
                             hess: Optional[jax.Array] = None) -> bool:
        """The core boosting step (see train_one_iter)."""
        # ranking per-bucket arrays and position-bias state are rebound as
        # jit arguments (data_bound_attrs / state_attrs), so lambdarank runs
        # the fused path too; rank_xendcg keeps the eager path (fresh host
        # RNG draw every iteration)
        fast_path = (grad is None and hess is None
                     and self.objective is not None
                     and self.objective.jit_safe_gradients
                     and not self.sample_strategy.is_active()
                     and self._row_sharding is None)
        if grad is None and hess is None and self._can_fuse_iteration():
            k = self.num_tree_per_iteration
            with _tel_tracer.boundary("GBDT::FusedIter"):
                state, arrays_k = self._iter_fused()
            self.score = state.score
            rate = self._shrinkage_rate()
            if k == 1:
                arrays_list = [arrays_k]
            else:
                self._mc_batched_last = True
                self._mc_stacked = (arrays_k, state.leaf_id)
                arrays_list = [jax.tree.map(lambda a, i=kk: a[i], arrays_k)
                               for kk in range(k)]
            for kk, arrays in enumerate(arrays_list):
                bias = 0.0
                if (self.iter_ == 0 or self._average_output) and \
                        self.init_scores[kk] != 0.0:
                    bias = self.init_scores[kk]
                self._lazy_trees.append({"arrays": arrays, "rate": rate,
                                         "bias": bias})
            for vi, vset in enumerate(self.valid_sets):
                vdd = self._valid_device_data(vset)
                vs = self._valid_scores[vi]
                for kk, arrays in enumerate(arrays_list):
                    vs = self._add_tree_arrays_to_score(vs, arrays, vdd,
                                                        kk, rate)
                self._valid_scores[vi] = vs
            if self._nan_guard.enabled:
                self._nan_guard.note(state.ok, self.iter_, defer=True)
            self._finished_dev = state.finished
            self.iter_ += 1
            if self.iter_ % self._finished_check_every == 0:
                if self._poll_device_flags():
                    self._trim_trailing_trivial()
                    return True
            return False
        self._fused_last = False
        quant_done = False
        ok_dev = None
        old_state = ({a: getattr(self.objective, a, None)
                      for a in self.objective.state_attrs()}
                     if self.objective is not None else {})
        if fast_path:
            # no bagging: the in-bag mask IS the pad mask, and the gradient
            # chain (incl. quantization) runs as one fused program
            with _tel_tracer.boundary("GBDT::Boosting"):
                (graw, hraw, grad, hess, q_scales) = self._boost_padded()
            if _chaos.has("nan_grad"):
                grad = _chaos.inject_nan_grad(grad, self.iter_ + 1)
            (ok_dev, grad, hess, graw, hraw, q_scales) = self._guard_gh(
                grad, hess, graw, hraw, q_scales)
            self._guard_objective_state(old_state, ok_dev)
            mask = self._pad_mask
            quant_done = True
        else:
            if grad is None or hess is None:
                with _tel_tracer.boundary("GBDT::Boosting"):
                    grad, hess = self._boost()
            else:
                grad = self._pad_gh(jnp.asarray(grad, jnp.float32))
                hess = self._pad_gh(jnp.asarray(hess, jnp.float32))
            mask, grad, hess = self.sample_strategy.sample(self.iter_, grad, hess)
            if self.sample_strategy.is_active():
                from ..telemetry import note_launch
                note_launch(2)   # eager mask draw + scale (lower bound)
            mask = self._shard_row_array(mask) * self._pad_mask
            grad = self._shard_row_array(grad)
            hess = self._shard_row_array(hess)
            if grad.ndim == 2:
                grad = grad * self._pad_mask[:, None]
                hess = hess * self._pad_mask[:, None]
            else:
                grad = grad * self._pad_mask
                hess = hess * self._pad_mask
            if _chaos.has("nan_grad"):
                grad = _chaos.inject_nan_grad(grad, self.iter_ + 1)
            (ok_dev, grad, hess) = self._guard_gh(grad, hess)
            self._guard_objective_state(old_state, ok_dev)

        k = self.num_tree_per_iteration
        col_mask = self._feature_mask()
        # GOSS/bagging row compaction: static per-shard capacity for this
        # iteration's grow programs (0 = dense masking). The kwarg is only
        # passed when engaged so the unsampled jit signatures stay unchanged.
        self._last_sampled_rows = None
        compact = self._row_compaction_capacity(mask)
        self._last_compact_rows = compact
        self._last_sample_mode = self._sample_mode()
        compact_kw = {"compact_rows": compact} if compact else {}
        if quant_done:
            grad_raw, hess_raw, gh_scales = graw, hraw, q_scales
        else:
            grad_raw, hess_raw = grad, hess
            gh_scales = None
            if self.config.use_quantized_grad:
                grad, hess, gh_scales = self._quantize_gh(grad, hess)
        new_arrays = []
        # class-parallel growth as ONE compiled program: a lax.scan over the
        # K gradient columns replaces K separate grow launches (the
        # reference's class-parallel trees, num_tree_per_iteration_; each
        # launch costs fixed dispatch overhead)
        k_results = None
        if (k > 1 and not self.config.linear_tree
                and self._cegb_used is None
                and not (self.config.use_quantized_grad
                         and self.config.quant_train_renew_leaf)):
            with _tel_tracer.boundary("GBDT::TrainTree", k=k), \
                    self._grow_x64_ctx():
                k_results = self._grow_classes(grad, hess, mask, col_mask,
                                               gh_scales, k, compact)
        # stacked multiclass score update: ONE launch adds every class's
        # leaf outputs to the (N, K) score block from the grower's stacked
        # outputs, replacing K per-class gathers. BOTH multiclass grow
        # paths (widened lockstep and per-class scan) go through this same
        # jit so their training scores stay bit-identical — a jitted and an
        # eager update round differently (FMA fusion), which would leak
        # ulp-level score drift into later trees.
        batched_score_done = False
        if (k_results is not None and self._mc_stacked is not None
                and not self.config.linear_tree
                and (self.objective is None
                     or not self.objective.need_renew_leaf)):
            arrays_k, leaf_k = self._mc_stacked
            if self._score_add_k_fn is None:
                def _sadd_k(score, lid_k, lv_k, rate):
                    Lk = lv_k.shape[1]
                    flat = lv_k.reshape(-1) * rate
                    off = (jnp.arange(lv_k.shape[0]) * Lk)[:, None]
                    delta = flat[lid_k + off]                # (K, N)
                    return score + delta.T

                self._score_add_k_fn = watched_jit(_sadd_k,
                                                   name="score_add_k",
                                                   owner=self)
            self.score = self._score_add_k_fn(
                self.score, leaf_k, arrays_k.leaf_value,
                jnp.float32(self._shrinkage_rate()))
            batched_score_done = True
        for kk in range(k):
            g = grad if k == 1 else grad[:, kk]
            h = hess if k == 1 else hess[:, kk]
            gkey = None
            if self._needs_grow_key:
                gkey = jax.random.PRNGKey(
                    (self.config.extra_seed or 3) * 1000003
                    + self.iter_ * (k + 1) + kk)
            sc = None
            if gh_scales is not None:
                sc = gh_scales if k == 1 else gh_scales[:, kk]
            if k_results is not None:
                arrays, leaf_id = k_results[kk]
            else:
                with _tel_tracer.boundary("GBDT::TrainTree"), \
                        self._grow_x64_ctx():
                    out = self._grow_fn(
                        self.dd.bins, g, h, mask, col_mask, key=gkey,
                        packed=self._packed, cegb_used=self._cegb_used,
                        cegb_lazy=self._cegb_lazy, gh_scales=sc,
                        **compact_kw)
                    if len(out) == 3:
                        arrays, leaf_id, self._cegb_lazy = out
                    else:
                        arrays, leaf_id = out
            if self._cegb_used is not None:
                L = self._grow_params.num_leaves
                ni_mask = jnp.arange(L) < (arrays.num_leaves - 1)
                f_oh = jax.nn.one_hot(arrays.split_feature,
                                      self.dd.num_features, dtype=jnp.int32)
                self._cegb_used = self._cegb_used | jnp.any(
                    (f_oh > 0) & ni_mask[:, None], axis=0)
            if self.config.use_quantized_grad and \
                    self.config.quant_train_renew_leaf:
                arrays = self._renew_leaves_exact(arrays, leaf_id, grad_raw,
                                                  hess_raw, kk)
            arrays, leaf_id = self._post_grow(arrays, leaf_id, kk, mask)
            bias = 0.0
            if (self.iter_ == 0 or self._average_output) and \
                    self.init_scores[kk] != 0.0:
                bias = self.init_scores[kk]
            if batched_score_done:
                # score already updated from the stacked outputs in one
                # launch; only record the tree for lazy finalization
                self._lazy_trees.append({"arrays": arrays,
                                         "rate": self._shrinkage_rate(),
                                         "bias": bias})
                new_arrays.append(arrays)
                continue
            if self.config.linear_tree:
                # host-synced path: fit linear leaf models on the raw features
                # (reference: linear_tree_learner.cpp CalculateLinear, Eq 3 of
                # arxiv 1802.05640) and apply their outputs to the scores
                delta_np, tree = self._fit_linear_tree(arrays, leaf_id,
                                                       grad_raw, hess_raw, kk)
                if bias:
                    tree.add_bias(bias)
                self._flush_models()
                self._models_list.append(tree)
                n_pad_rows = self.dd.bins.shape[0]
                delta = jnp.zeros(n_pad_rows, jnp.float32).at[
                    :self.num_data].set(jnp.asarray(delta_np, jnp.float32))
            else:
                # score update (reference: ScoreUpdater::AddScore);
                # single-leaf trees have leaf_value 0, so no branch is needed
                if self._use_leaf_gather_kernel:
                    # one fused launch: XLA's small-table row gather runs
                    # ~100M rows/s; the streaming one-hot contraction runs
                    # at bandwidth
                    if self._score_add_fn is None:
                        leaf_gather = self._leaf_gather_fn()

                        def _sadd(score, lid, lv, rate, col):
                            delta = leaf_gather(lid, lv * rate)
                            if score.ndim == 1:
                                return score + delta
                            return score.at[:, col].add(delta)

                        self._score_add_fn = watched_jit(
                            _sadd, name="score_add", owner=self,
                            static_argnums=(4,))
                    self.score = self._score_add_fn(
                        self.score, leaf_id, arrays.leaf_value,
                        jnp.float32(self._shrinkage_rate()), kk)
                    self._lazy_trees.append({"arrays": arrays,
                                             "rate": self._shrinkage_rate(),
                                             "bias": bias})
                    new_arrays.append(arrays)
                    continue
                lv = arrays.leaf_value * self._shrinkage_rate()
                delta = lv[leaf_id]
                from ..telemetry import note_launch
                note_launch(3)   # eager scale + gather + add dispatches
                # tree finalization is DEFERRED (see `models` property);
                # record the init-score bias to fold at materialization time
                # so saved models stay self-contained (reference: gbdt.cpp:425)
                self._lazy_trees.append({"arrays": arrays,
                                         "rate": self._shrinkage_rate(),
                                         "bias": bias})
            if k == 1:
                self.score = self.score + delta
            else:
                self.score = self.score.at[:, kk].add(delta)
            new_arrays.append(arrays)

        # update validation scores with the new trees
        for vi, vset in enumerate(self.valid_sets):
            dd = self._valid_device_data(vset)
            score = self._valid_scores[vi]
            if self.config.linear_tree:
                if vset.raw_data is None:
                    raise LightGBMError(
                        "linear_tree validation needs the raw feature matrix;"
                        " construct the valid Dataset with "
                        "free_raw_data=False")
                for kk in range(k):
                    tree = self._models_list[-k + kk]
                    dv = np.asarray(tree.predict_raw(vset.raw_data))
                    # add_valid already seeded valid scores with init_scores;
                    # subtract the bias folded into the saved tree so it is
                    # not double counted (non-linear path uses bias-free
                    # device arrays)
                    if (self.iter_ == 0 or self._average_output) and \
                            self.init_scores[kk] != 0.0:
                        dv = dv - self.init_scores[kk]
                    pad = jnp.zeros(score.shape[0], jnp.float32).at[
                        :len(dv)].set(jnp.asarray(dv, jnp.float32))
                    score = (score + pad if score.ndim == 1
                             else score.at[:, kk].add(pad))
            else:
                for kk, arrays in enumerate(new_arrays):
                    score = self._add_tree_arrays_to_score(
                        score, arrays, dd, kk, self._shrinkage_rate())
            self._valid_scores[vi] = score

        flags = [a.num_leaves <= 1 for a in new_arrays]
        fin = (flags[0] if len(flags) == 1
               else jnp.all(jnp.stack(flags)))
        from ..telemetry import note_launch
        note_launch(1)           # eager finished-flag combine
        if ok_dev is not None:
            # a nan-skipped iteration grows trivial trees by design — it
            # must not read as "no more splits possible"; the flag read is
            # deferred to the finished-flag polls (an eager bool() here
            # would cost a device sync per iteration)
            fin = fin & ok_dev
            self._nan_guard.note(ok_dev, self.iter_, defer=True)
        self._finished_dev = fin
        self.iter_ += 1
        # reading the finished flag is a device->host sync that drains the
        # device queue, so poll it only periodically there; the trailing
        # single-leaf trees accumulated between polls are dropped on stop so
        # num_trees()/model files match the reference's immediate stop
        if self.iter_ % self._finished_check_every == 0:
            from ..telemetry import note_host_sync
            note_host_sync()
            with _tel_tracer.boundary(
                    "GBDT::FlagPoll", iteration=self.iter_,
                    **self._poll_sampling_fields(self._last_sampled_rows,
                                                 None)):
                self._nan_guard.poll()
                finished = bool(self._finished_dev)
            if finished:
                self._trim_trailing_trivial()
                return True
        return False

    def _trim_trailing_trivial(self) -> None:
        """Drop trailing no-op iterations (every class tree single-leaf with
        zero output) appended between finished-flag polls (reference:
        gbdt.cpp:436-447 stops without keeping the splitless tree)."""
        k = self.num_tree_per_iteration
        while self.iter_ > 0:
            if len(self._lazy_trees) >= k:
                tail = self._lazy_trees[-k:]
                got = jax.device_get(
                    [(e["arrays"].num_leaves, e["arrays"].leaf_value[0])
                     for e in tail])
                if all(int(nl) <= 1 and float(lv) == 0.0 and not e["bias"]
                       for (nl, lv), e in zip(got, tail)):
                    del self._lazy_trees[-k:]
                    self.iter_ -= 1
                    continue
            elif not self._lazy_trees and len(self._models_list) >= k:
                tail = self._models_list[-k:]
                if all(t.num_leaves <= 1 and
                       all(v == 0.0 for v in t.leaf_value)
                       for t in tail):
                    del self._models_list[-k:]
                    self.iter_ -= 1
                    continue
            break

    def _shrinkage_rate(self) -> float:
        return self.config.learning_rate

    # ------------------------------------------------------------------
    def _fit_linear_tree(self, arrays, leaf_id, grad_raw, hess_raw, kk):
        """Fit per-leaf linear models on the raw features (reference:
        linear_tree_learner.cpp CalculateLinear — weighted ridge on the
        leaf's path features, Eq 3 of arxiv 1802.05640). Host-synced: linear
        trees need the raw matrix and small per-leaf solves.

        Returns (training score delta over the unpadded rows, host Tree)."""
        k = self.num_tree_per_iteration
        nd = self.num_data
        got = jax.device_get((arrays, leaf_id,
                              grad_raw if k == 1 else grad_raw[:, kk],
                              hess_raw if k == 1 else hess_raw[:, kk]))
        arrays_h, leaf_h, g_h, h_h = got
        leaf_h = np.asarray(leaf_h)[:nd]
        g_h = np.asarray(g_h)[:nd]
        h_h = np.asarray(h_h)[:nd]
        X = self.train_data.raw_data
        mappers = self.train_data.bin_mappers()
        tree = finalize_tree(arrays_h, mappers, None, learning_rate=1.0)
        c = self.config
        L = tree.num_leaves
        ni = max(L - 1, 0)

        # branch (path) features per leaf, numerical only
        parent = np.full(ni, -1, np.int64)
        leaf_parent = np.full(L, -1, np.int64)
        for i in range(ni):
            for ch in (int(tree.left_child[i]), int(tree.right_child[i])):
                if ch >= 0:
                    parent[ch] = i
                else:
                    leaf_parent[~ch] = i
        leaf_feats: List[List[int]] = []
        for ln in range(L):
            feats = set()
            node = leaf_parent[ln]
            while node >= 0:
                f = int(tree.split_feature[node])
                if mappers[f].bin_type == 0:
                    feats.add(f)
                node = parent[node]
            leaf_feats.append(sorted(feats))

        tree.is_linear = True
        tree.leaf_const = np.asarray(tree.leaf_value, np.float64).copy()
        tree.leaf_features = [[] for _ in range(L)]
        tree.leaf_coeff = [[] for _ in range(L)]
        if self.iter_ > 0:   # reference: first tree stays constant
            lam = float(c.linear_lambda)
            for ln in range(L):
                feats = leaf_feats[ln]
                d = len(feats)
                rows = np.flatnonzero(leaf_h == ln)
                if d == 0 or len(rows) == 0:
                    continue
                A = np.column_stack([X[np.ix_(rows, feats)],
                                     np.ones(len(rows))])
                ok = ~np.isnan(A).any(axis=1)
                if int(ok.sum()) < d + 1:
                    continue
                A = A[ok]
                g = g_h[rows][ok]
                h = h_h[rows][ok]
                M = (A * h[:, None]).T @ A
                M[np.arange(d), np.arange(d)] += lam
                v = A.T @ g
                try:
                    coef = -np.linalg.solve(M, v)
                except np.linalg.LinAlgError:
                    coef = -np.linalg.pinv(M) @ v
                keep = np.abs(coef[:d]) > 1e-35
                tree.leaf_features[ln] = [f for f, kp in zip(feats, keep) if kp]
                tree.leaf_coeff[ln] = [float(cf) for cf, kp
                                       in zip(coef[:d], keep) if kp]
                tree.leaf_const[ln] = float(coef[d])
        rate = self._shrinkage_rate()
        if rate != 1.0:
            tree.shrink(rate)
        delta = tree._linear_output(X, leaf_h)
        return delta, tree

    # ------------------------------------------------------------------
    def _quantize_gh(self, grad, hess):
        key = jax.random.PRNGKey(
            (self.config.data_random_seed + 11) * 131071 + self.iter_)
        return quantize_gh(grad, hess, key, self.config.num_grad_quant_bins,
                           self.config.stochastic_rounding)

    def _renew_leaves_exact(self, arrays: TreeArrays, leaf_id, grad_raw,
                            hess_raw, kk: int) -> TreeArrays:
        """Recompute leaf outputs from the UNquantized gradients (reference:
        quant_train_renew_leaf, gradient_discretizer RenewIntGradTreeOutput)."""
        k = self.num_tree_per_iteration
        g = grad_raw if k == 1 else grad_raw[:, kk]
        h = hess_raw if k == 1 else hess_raw[:, kk]
        L = self._grow_params.num_leaves
        lid = jnp.clip(leaf_id, 0, L - 1)
        sg = jax.ops.segment_sum(g, lid, num_segments=L)
        sh = jax.ops.segment_sum(h, lid, num_segments=L)
        c = self.config
        vals = leaf_output(sg, sh, c.lambda_l1, c.lambda_l2, c.max_delta_step)
        keep = (jnp.arange(L) < arrays.num_leaves) & (arrays.leaf_count > 0)
        vals = jnp.where(keep, vals, arrays.leaf_value)
        vals = jnp.where(arrays.num_leaves > 1, vals, arrays.leaf_value)
        return arrays._replace(leaf_value=vals)

    # ------------------------------------------------------------------
    def load_init_model(self, trees: List[Tree],
                        num_tree_per_iteration: int,
                        skip_score_rebuild: bool = False) -> None:
        """Continued training: seed the engine with an existing model's trees
        and rebuild the training score with a device tree walk (reference:
        GBDT::ResetTrainingData + model-continuation init,
        src/boosting/gbdt.cpp:259-263, src/boosting/boosting.cpp:42-90).
        ``skip_score_rebuild``: a checkpoint resume restores the exact
        saved score next, so the O(trees x rows) walk would be wasted."""
        k = self.num_tree_per_iteration
        if self._nan_guard.enabled:
            # the nan_guard contract extends to continued training: refuse
            # to boost on top of a poisoned model (NaN leaf values / gains)
            check_model_trees(trees, "init model")
        if num_tree_per_iteration != k:
            raise LightGBMError(
                f"init_model has {num_tree_per_iteration} trees/iteration but "
                f"this training run needs {k}")
        if len(trees) % k != 0:
            raise LightGBMError("init_model tree count is not a multiple of "
                                "num_tree_per_iteration")
        budget = self._grow_params.num_leaves
        worst = max((t.num_leaves for t in trees), default=0)
        if worst > budget:
            raise LightGBMError(
                f"init_model contains a tree with {worst} leaves but this "
                f"training run's num_leaves budget is {budget}; continue with "
                f"num_leaves >= {worst}")
        self.models = list(trees)
        self.iter_ = len(trees) // k
        # loaded trees already contain the folded init bias (AddBias at save
        # time), so the restored score is exactly the summed tree outputs plus
        # any user-provided init_score offsets
        n = self.dd.bins.shape[0]
        score = jnp.zeros(self._score_shape, jnp.float32)
        base = self.train_data.get_init_score_padded(n, k)
        if base is not None:
            score = score + jnp.asarray(base, jnp.float32)
        if not skip_score_rebuild:
            for it in range(self.iter_):
                for kk in range(k):
                    score = self._add_tree_to_score(
                        score, self.models[it * k + kk], self.dd, kk)
        self.score = self._shard_row_array(score)
        # prevent re-folding the from-average bias into future first trees
        self.init_scores = [0.0] * k
        for vi, vset in enumerate(self.valid_sets):
            dd = self._valid_device_data(vset)
            vs = jnp.zeros_like(self._valid_scores[vi])
            vbase = vset.get_init_score_padded(dd.bins.shape[0], k)
            if vbase is not None:
                vs = vs + jnp.asarray(vbase, jnp.float32)
            for it in range(self.iter_):
                for kk in range(k):
                    vs = self._add_tree_to_score(vs, self.models[it * k + kk],
                                                 dd, kk)
            self._valid_scores[vi] = vs

    def _post_grow(self, arrays: TreeArrays, leaf_id, kk: int, mask):
        """Hook: leaf renewal for percentile objectives (reference:
        TreeLearner::RenewTreeOutput call in gbdt.cpp:419)."""
        if self.objective is not None and self.objective.need_renew_leaf:
            score = self.score if self.score.ndim == 1 else self.score[:, kk]
            new_vals = self.objective.renew_leaf_values(
                score[:self.num_data], leaf_id[:self.num_data],
                self._grow_params.num_leaves, mask[:self.num_data])
            keep = jnp.arange(new_vals.shape[0]) < arrays.num_leaves
            vals = jnp.where(keep & (arrays.leaf_count > 0), new_vals,
                             arrays.leaf_value)
            vals = jnp.where(arrays.num_leaves > 1, vals, arrays.leaf_value)
            arrays = arrays._replace(leaf_value=vals)
        return arrays, leaf_id

    # ------------------------------------------------------------------
    def _add_tree_arrays_to_score(self, score, arrays: TreeArrays, dd: DeviceData,
                                  kk: int, rate: float):
        fields = (arrays.split_feature, arrays.threshold_bin, arrays.dir_flags,
                  arrays.left_child, arrays.right_child, arrays.cat_bitset)
        maxd = self._grow_params.num_leaves  # safe static bound
        leaf = _walk_one_tree(fields, dd.bins, dd.routing, maxd)
        delta = arrays.leaf_value[leaf] * rate
        if score.ndim == 1:
            return score + delta
        return score.at[:, kk].add(delta)

    def _add_tree_to_score(self, score, tree: Tree, dd: DeviceData, kk: int):
        arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                 dd.max_bins, self.train_data)
        return self._add_tree_arrays_to_score(score, arrays, dd, kk, 1.0)

    # ------------------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        with _tel_tracer.boundary("GBDT::Eval", dataset="training"):
            score = self._score_to_host(self.score, self.num_data)
            conv = (self.objective.convert_output
                    if self.objective is not None else (lambda x: x))
            for m in self.train_metrics:
                for (name, val, hb) in m.evaluate(score, conv):
                    out.append(("training", name, val, hb))
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        conv = (self.objective.convert_output if self.objective is not None
                else (lambda x: x))
        with _tel_tracer.boundary("GBDT::Eval", dataset="valid"):
            for vi, vset in enumerate(self.valid_sets):
                n = vset.num_data()
                score = self._score_to_host(self._valid_scores[vi], n)
                for m in self.valid_metrics[vi]:
                    for (name, val, hb) in m.evaluate(score, conv):
                        out.append((self.valid_names[vi], name, val, hb))
        return out

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """reference: GBDT::RollbackOneIter (gbdt.cpp:463)."""
        if self.iter_ <= 0:
            return
        k = self.num_tree_per_iteration
        dropped = self.models[-k:]
        del self.models[-k:]
        dd = self.dd
        for kk, tree in enumerate(dropped):
            arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                     dd.max_bins, self.train_data)
            self.score = self._add_tree_arrays_to_score(
                self.score, arrays._replace(leaf_value=-arrays.leaf_value),
                dd, kk, 1.0)
        for vi, vset in enumerate(self.valid_sets):
            vdd = self._valid_device_data(vset)
            score = self._valid_scores[vi]
            for kk, tree in enumerate(dropped):
                arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                         vdd.max_bins, self.train_data)
                score = self._add_tree_arrays_to_score(
                    score, arrays._replace(leaf_value=-arrays.leaf_value),
                    vdd, kk, 1.0)
            self._valid_scores[vi] = score
        self.iter_ -= 1
        # the rolled-back score is only f32-approximately restored, so a
        # re-run of this iteration may draw a (slightly) different GOSS
        # mask under the SAME mask_key — drop the cached in-bag counts so
        # the compaction capacity is re-sized against the fresh mask
        # (a stale undersized capacity would silently truncate in-bag rows)
        self._sample_count_cache = None

    @property
    def num_trees(self) -> int:
        return len(self.models)


class DART(GBDT):
    """Dropout boosting (reference: src/boosting/dart.hpp)."""

    boosting_type = "dart"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._drop_rng = np.random.RandomState(self.config.drop_seed)
        # DART rescales the just-trained trees on host each iteration, so the
        # lazy-finalize optimization cannot skip the per-iter sync anyway
        self._finished_check_every = 1

    def _train_one_iter_impl(self, grad=None, hess=None) -> bool:
        c = self.config
        k = self.num_tree_per_iteration
        n_iters = self.iter_
        # choose dropped trees
        drop_idx: List[int] = []
        if n_iters > 0 and self._drop_rng.rand() >= c.skip_drop:
            if c.uniform_drop:
                sel = self._drop_rng.rand(n_iters) < c.drop_rate
                drop_idx = list(np.where(sel)[0])
            else:
                kcnt = max(1, int(round(c.drop_rate * n_iters)))
                drop_idx = list(self._drop_rng.choice(n_iters, size=min(kcnt, n_iters),
                                                      replace=False))
            if len(drop_idx) > c.max_drop > 0:
                drop_idx = drop_idx[:c.max_drop]
        kfac = len(drop_idx)
        # remove dropped trees from the score
        dd = self.dd
        for it in drop_idx:
            for kk in range(k):
                tree = self.models[it * k + kk]
                arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                         dd.max_bins, self.train_data)
                self.score = self._add_tree_arrays_to_score(
                    self.score, arrays._replace(leaf_value=-arrays.leaf_value),
                    dd, kk, 1.0)
        finished = super()._train_one_iter_impl(grad, hess)
        # normalization (reference: dart.hpp Normalize)
        if kfac > 0 and not finished:
            if c.xgboost_dart_mode:
                new_scale = c.learning_rate / (kfac + c.learning_rate)
                old_scale = kfac / (kfac + c.learning_rate)
            else:
                new_scale = 1.0 / (kfac + 1.0)
                old_scale = kfac / (kfac + 1.0)
            # rescale the just-added trees
            for kk in range(k):
                tree = self.models[-k + kk]
                factor = new_scale / self._shrinkage_rate()
                arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                         dd.max_bins, self.train_data)
                delta = arrays.leaf_value * (factor - 1.0)
                self.score = self._add_tree_arrays_to_score(
                    self.score, arrays._replace(leaf_value=delta), dd, kk, 1.0)
                tree.shrink(new_scale / tree.shrinkage if tree.shrinkage else new_scale)
            # rescale dropped trees and re-add
            for it in drop_idx:
                for kk in range(k):
                    tree = self.models[it * k + kk]
                    tree.shrink(old_scale)
                    arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                             dd.max_bins, self.train_data)
                    self.score = self._add_tree_arrays_to_score(
                        self.score, arrays, dd, kk, 1.0)
        elif kfac > 0:
            # tree was trivial; restore dropped trees unchanged
            for it in drop_idx:
                for kk in range(k):
                    tree = self.models[it * k + kk]
                    arrays = _tree_to_device(tree, self._grow_params.num_leaves,
                                             dd.max_bins, self.train_data)
                    self.score = self._add_tree_arrays_to_score(
                        self.score, arrays, dd, kk, 1.0)
        return finished

    def _shrinkage_rate(self) -> float:
        return self.config.learning_rate


class RF(GBDT):
    """Random forest mode (reference: src/boosting/rf.hpp): bagging required, no
    shrinkage, averaged outputs; gradients always taken at the init score."""

    boosting_type = "rf"
    _average_output = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        k = self.num_tree_per_iteration
        self._init_score_const = jnp.zeros(self._score_shape, jnp.float32) + \
            jnp.asarray(self.init_scores if k > 1 else self.init_scores[0], jnp.float32)
        self._tree_sum = jnp.zeros(self._score_shape, jnp.float32)

    def _boost(self):
        if self.objective is None:
            raise LightGBMError("rf requires an objective")
        saved = self.score
        self.score = self._init_score_const
        try:
            return super()._boost()
        finally:
            self.score = saved

    def _shrinkage_rate(self) -> float:
        return 1.0

    def load_init_model(self, trees, num_tree_per_iteration) -> None:
        raise LightGBMError(
            "continued training (init_model) is not supported with "
            "boosting=rf: the averaged-output bookkeeping cannot be rebuilt "
            "from a saved model")

    def _train_one_iter_impl(self, grad=None, hess=None) -> bool:
        # track tree-sum separately: score = init + tree_sum / iter
        self.score = self._tree_sum
        finished = GBDT._train_one_iter_impl(self, grad, hess)
        self._tree_sum = self.score
        t = max(self.iter_, 1)
        self.score = self._init_score_const + self._tree_sum / t
        return finished

    def eval_valid(self):
        # average the accumulated sums for metric evaluation
        t = max(self.iter_, 1)
        out = []
        conv = (self.objective.convert_output if self.objective is not None
                else (lambda x: x))
        k = self.num_tree_per_iteration
        for vi, vset in enumerate(self.valid_sets):
            n = vset.num_data()
            init = np.asarray(self.init_scores if k > 1 else self.init_scores[0])
            raw = np.asarray(self._valid_scores[vi][:n])
            # _valid_scores started at init and accumulated full tree outputs;
            # averaged score = init + (raw - init)/t
            score = init + (raw - init) / t
            for m in self.valid_metrics[vi]:
                for (name, val, hb) in m.evaluate(score, conv):
                    out.append((self.valid_names[vi], name, val, hb))
        return out

    def eval_train(self):
        out = []
        conv = (self.objective.convert_output if self.objective is not None
                else (lambda x: x))
        score = np.asarray((self._init_score_const +
                            self._tree_sum / max(self.iter_, 1))[:self.num_data])
        for m in self.train_metrics:
            for (name, val, hb) in m.evaluate(score, conv):
                out.append(("training", name, val, hb))
        return out


def _tree_to_device(tree: Tree, num_leaves_budget: int, max_bins: int,
                    train_data) -> TreeArrays:
    """Host Tree -> padded device TreeArrays (bin-space) for score walks."""
    L = num_leaves_budget
    ni = L - 1 if L > 1 else 1
    Bmax = max_bins

    def pad1(a, size, dtype, fill=0):
        out = np.full(size, fill, dtype)
        out[:len(a)] = a
        return out

    n_int = len(tree.split_feature)
    dirf = np.zeros(n_int, np.int32)
    cat_bits = np.zeros((L, Bmax), bool)
    mappers = train_data.bin_mappers()
    thr_bin = np.asarray(tree.threshold_bin, np.int64).copy()
    for i in range(n_int):
        dt = int(tree.decision_type[i])
        if dt & 1:
            dirf[i] |= 2
            # rebuild bin-space bitset from category-value bitset
            f = int(tree.split_feature[i])
            m = mappers[f]
            kcat = int(tree.threshold_bin[i])
            s, e = tree.cat_boundaries[kcat], tree.cat_boundaries[kcat + 1]
            words = tree.cat_threshold[s:e]
            for b, c in enumerate(m.categories):
                c = int(c)
                if c // 32 < len(words) and (int(words[c // 32]) >> (c % 32)) & 1:
                    cat_bits[i, b] = True
        else:
            if dt & 2:
                dirf[i] |= 1
            # bin threshold from real threshold
            f = int(tree.split_feature[i])
            m = mappers[f]
            thr_bin[i] = int(np.searchsorted(m.upper_bounds, tree.threshold[i],
                                             side="left"))

    return TreeArrays(
        split_feature=jnp.asarray(pad1(tree.split_feature, L, np.int32)),
        threshold_bin=jnp.asarray(pad1(thr_bin, L, np.int32)),
        dir_flags=jnp.asarray(pad1(dirf, L, np.int32)),
        left_child=jnp.asarray(pad1(tree.left_child, L, np.int32)),
        right_child=jnp.asarray(pad1(tree.right_child, L, np.int32)),
        split_gain=jnp.asarray(pad1(tree.split_gain, L, np.float32)),
        internal_value=jnp.asarray(pad1(tree.internal_value, L, np.float32)),
        internal_weight=jnp.asarray(pad1(tree.internal_weight, L, np.float32)),
        internal_count=jnp.asarray(pad1(tree.internal_count, L, np.float32)),
        cat_bitset=jnp.asarray(cat_bits),
        leaf_value=jnp.asarray(pad1(tree.leaf_value, L, np.float32)),
        leaf_weight=jnp.asarray(pad1(tree.leaf_weight, L, np.float32)),
        leaf_count=jnp.asarray(pad1(tree.leaf_count, L, np.float32)),
        leaf_parent=jnp.zeros(L, jnp.int32),
        num_leaves=jnp.asarray(tree.num_leaves, jnp.int32),
        leaf_depth=jnp.zeros(L, jnp.int32),
    )


def create_boosting(config: Config, train_data, objective, metrics) -> GBDT:
    """reference: Boosting::CreateBoosting (boosting.cpp:42)."""
    t = config.boosting
    if t in ("gbdt", "gbrt", "goss"):
        return GBDT(config, train_data, objective, metrics)
    if t == "dart":
        return DART(config, train_data, objective, metrics)
    if t in ("rf", "random_forest"):
        return RF(config, train_data, objective, metrics)
    raise LightGBMError(f"Unknown boosting type {t}")

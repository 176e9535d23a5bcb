"""Device mesh + sharding policy — the distributed backend.

Reference: src/network/ (from-scratch socket/MPI collectives: Allreduce/ReduceScatter/
Allgather, network.cpp:72-307) and the three distributed learners in src/treelearner/
(feature_parallel_tree_learner.cpp, data_parallel_tree_learner.cpp,
voting_parallel_tree_learner.cpp).

TPU re-design: the entire collective layer is replaced by XLA GSPMD over a
jax.sharding.Mesh. The tree grower (ops/grow.py) is pure jnp, so:

  * tree_learner="data"    -> shard rows (N) across the mesh. The histogram build
    contracts over N, so XLA inserts an all-reduce of histogram blocks — exactly the
    reference's ReduceScatter+Allgather specialisation (data_parallel_tree_learner.
    cpp:285-299) chosen automatically, riding ICI instead of TCP.
  * tree_learner="feature" -> shard the feature-group axis (G). Each device builds
    histograms and split candidates for its feature slice; the argmax over features
    becomes an all-gather of per-shard bests (the reference Allreduces SplitInfo,
    feature_parallel_tree_learner.cpp:25-83).
  * tree_learner="voting"  -> planned as a comm optimisation of "data" for DCN-connected
    hosts (top-k vote before the histogram reduce, PV-Tree); round-2 work.

Multi-host: call jax.distributed.initialize() before building the mesh; the same
program runs SPMD across hosts (replaces LGBM_NetworkInit / machine_list entirely).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.log import LightGBMError, log_info

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def parse_mesh_shape(spec: str) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """Parse "data:4,feature:2" into axis names/sizes.

    Malformed specs raise LightGBMError naming the offending part instead
    of leaking a bare ValueError (e.g. "data:") or silently building a
    mesh with duplicate/empty axis names or non-positive sizes."""
    names, sizes = [], []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size = part.partition(":")
        name = name.strip()
        if not sep or not name:
            raise LightGBMError(
                f"mesh_shape part {part!r} must be '<axis>:<size>' "
                f"(full spec: {spec!r})")
        try:
            n = int(size)
        except ValueError:
            raise LightGBMError(
                f"mesh_shape part {part!r} has a non-integer size "
                f"{size.strip()!r} (full spec: {spec!r})") from None
        if n <= 0:
            raise LightGBMError(
                f"mesh_shape part {part!r} has non-positive size {n} "
                f"(full spec: {spec!r})")
        if name in names:
            raise LightGBMError(
                f"mesh_shape {spec!r} repeats axis name {name!r}")
        names.append(name)
        sizes.append(n)
    if not names:
        raise LightGBMError(f"mesh_shape {spec!r} names no axes")
    return tuple(names), tuple(sizes)


def create_mesh(mesh_shape: str = "", tree_learner: str = "serial",
                num_machines: int = 1) -> Optional[Mesh]:
    """Build the device mesh for the configured parallelism (None = single device)."""
    devices = jax.devices()
    n = len(devices)
    if num_machines > 1 and jax.process_count() < num_machines:
        log_info(f"num_machines={num_machines} but only {jax.process_count()} "
                 "JAX process(es) are initialized; call jax.distributed.initialize() "
                 "on every host before training (replaces LGBM_NetworkInit). "
                 "Proceeding with the devices visible to this process.")
    if mesh_shape:
        names, sizes = parse_mesh_shape(mesh_shape)
        # combined 2-axis meshes: ONLY tree_learner=data consumes both
        # axes (histograms build shard-locally over feature groups and
        # psum_scatter over rows — docs/DISTRIBUTED.md "2D mesh"). The
        # feature and voting learners run their collectives on a single
        # axis, so a combined mesh would leave the second axis unconsumed
        # and the bins sharding and split collectives would disagree.
        # Trailing size-1 axes are harmless (their collectives are
        # identities) and stay allowed for sweep tooling.
        big = [f"{nm}:{sz}" for nm, sz in zip(names, sizes) if sz > 1]
        big_names = {nm for nm, sz in zip(names, sizes) if sz > 1}
        if len(big) > 1 and not (tree_learner == "data"
                                 and big_names <= {DATA_AXIS, FEATURE_AXIS}):
            raise LightGBMError(
                f"mesh_shape {mesh_shape!r} requests a combined "
                f"{' x '.join(big)} mesh; 2-axis sharding is only "
                f"supported as \"{DATA_AXIS}:R,{FEATURE_AXIS}:F\" with "
                "tree_learner=data (rows x feature-groups, docs/"
                "DISTRIBUTED.md \"2D mesh\") — other learners shard ONE "
                "axis (\"data:D\" with tree_learner=voting, or "
                "\"feature:D\" with tree_learner=feature)")
        if tree_learner == "feature" and FEATURE_AXIS not in names:
            raise LightGBMError(
                f"tree_learner=feature needs a mesh with a "
                f"{FEATURE_AXIS!r} axis but mesh_shape {mesh_shape!r} "
                f"names {names}; use e.g. \"feature:{n}\"")
        if tree_learner in ("data", "voting") and FEATURE_AXIS in names \
                and DATA_AXIS not in names:
            raise LightGBMError(
                f"tree_learner={tree_learner} shards rows but mesh_shape "
                f"{mesh_shape!r} names only the {FEATURE_AXIS!r} axis; use "
                f"e.g. \"{DATA_AXIS}:{n}\"")
        total = int(np.prod(sizes))
        if total > n:
            raise LightGBMError(f"mesh {mesh_shape} needs {total} devices, have {n}")
        dev = np.asarray(devices[:total]).reshape(sizes)
        return Mesh(dev, names)
    if tree_learner in ("data", "voting"):
        if n == 1:
            log_info("tree_learner=data with a single device: running serial")
            return None
        return Mesh(np.asarray(devices), (DATA_AXIS,))
    if tree_learner == "feature":
        if n == 1:
            log_info("tree_learner=feature with a single device: running serial")
            return None
        return Mesh(np.asarray(devices), (FEATURE_AXIS,))
    return None


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded across the data axis (bins (N, G), grad/hess/leaf_id (N,))."""
    axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else mesh.axis_names[0]
    return NamedSharding(mesh, P(axis))


def bins_sharding(mesh: Mesh, tree_learner: str) -> NamedSharding:
    if tree_learner == "feature" and FEATURE_AXIS not in mesh.axis_names:
        raise LightGBMError(
            f"tree_learner=feature needs a mesh with a {FEATURE_AXIS!r} "
            f"axis; this mesh names {tuple(mesh.axis_names)}")
    if tree_learner == "feature" or (FEATURE_AXIS in mesh.axis_names
                                     and DATA_AXIS not in mesh.axis_names):
        return NamedSharding(mesh, P(None, FEATURE_AXIS))
    axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else mesh.axis_names[0]
    if FEATURE_AXIS in mesh.axis_names and (
            tree_learner != "data" or int(mesh.shape[FEATURE_AXIS]) > 1):
        # tree_learner=data with a real feature axis is the 2D mesh: bins
        # (N, G) shard over BOTH axes; a size-1 feature axis keeps the
        # rows-only spec so the 1D stream path is untouched.
        return NamedSharding(mesh, P(axis, FEATURE_AXIS))
    return NamedSharding(mesh, P(axis))

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_rows(mesh: Optional[Mesh], *arrays):
    """Place row-dimension arrays on the mesh (no-op without a mesh)."""
    if mesh is None:
        return arrays if len(arrays) > 1 else arrays[0]
    sh = data_sharding(mesh)
    out = tuple(jax.device_put(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]


def pad_rows_for_mesh(n: int, mesh: Optional[Mesh], base: int = 256) -> int:
    """Row count padded so every shard is equal-sized and tile-aligned."""
    mult = base
    if mesh is not None:
        mult = base * int(np.prod(mesh.devices.shape))
    return -(-n // mult) * mult


def shard_map_rows(fn, mesh: Mesh, in_specs, out_specs):
    """shard_map a per-device function over the mesh with the replication
    check OFF: pallas_call cannot annotate varying-mesh-axes on its outputs,
    so callers psum whatever must come back replicated (the reference's
    per-worker histogram construction + ReduceScatter,
    data_parallel_tree_learner.cpp:285-299)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

"""Histogram construction — the hot op of GBDT training.

Reference: src/io/dense_bin.hpp:99-170 (ConstructHistogramInner: per-row fused add of
grad/hess into hist[2*bin]) and src/treelearner/cuda/cuda_histogram_constructor.cu (device
shared-memory atomics). TPUs have no fast scatter-add, so the TPU-native formulation is a
one-hot matmul on the MXU:

    hist[s, g, b, c] = sum_n  1[slot[n] == s] * 1[bins[n, g] == b] * w_c[n]

with w = (grad, hess, count). ``slot`` assigns each row to the histogram slot of its leaf
(-1 = row not needed this round), so histograms for up to S leaves are built in ONE pass
over the data. Histogram layout is (S, G, Bmax, 3) — groups padded to a common bin count,
which keeps shapes static for XLA.

The formulations (``hist_backend``; HIST_BACKENDS is the one list of the names):
  * ``segsum`` — jax.ops.segment_sum scatter.  The reference every other one is
    tested against, and what ``auto`` is off the chip.
  * ``onehot`` — blocked one-hot matmul, pure XLA, so GSPMD can partition it: what
    a feature-sharded or 2D mesh and the voting learner run on a TPU.
  * ``stream`` — the growers' fused route + histogram Pallas kernel
    (pallas/stream_kernel.py) over a packed transposed bin copy: what ``auto``
    is on a TPU and what every benchmark cell runs.  It is a fork of the
    growers (``use_stream``), not of build_histograms.
  * ``auto``   — resolve_hist_backend()'s choice from what it can observe.

The rule lives here and nowhere else: resolve_hist_backend() turns a requested
name into a resolved one (the engine gathers the facts; build_histograms*'s
``auto`` goes through the same function), and hist_backend_refusal() says which
jobs (mesh kind, double precision, row compaction) a formulation cannot run —
the engine's validation and both growers ask it instead of listing names.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..runtime import on_tpu
from ..utils.log import LightGBMError

NUM_CHANNELS = 3  # grad, hess, count

HIST_BACKENDS = ("auto", "segsum", "onehot", "stream")
# how the bin matrix is sharded: not at all, over rows alone (tree_learner=
# data), over feature groups (tree_learner=feature), over both (the 2D mesh),
# or over rows under the voting learner's own shard_map grower
MESH_KINDS = ("none", "rows", "feature", "rows_x_feature", "voting")


def mesh_kind(mesh, row_axis, feature_axis) -> str:
    """The MESH_KINDS name of a grower's (mesh, row_axis, feature_axis)."""
    if mesh is None:
        return "none"
    if feature_axis is None:
        return "rows"
    return "feature" if row_axis is None else "rows_x_feature"


def hist_backend_refusal(backend: str, *, mesh: str = "none",
                         double: bool = False,
                         compact: bool = False) -> Optional[str]:
    """THE capability check: why ``backend`` cannot run a job, or None.

    mesh: a MESH_KINDS name; double: hist_precision=double (f64 histograms);
    compact: GOSS/bagging row compaction.  Anything but ``stream`` is a
    contraction XLA partitions and widens freely.
    """
    if mesh not in MESH_KINDS:
        raise ValueError(f"unknown mesh kind {mesh!r}; one of {MESH_KINDS}")
    if backend == "stream":
        if mesh in ("feature", "rows_x_feature"):
            return ("feature-sharded growth (tree_learner=feature or the 2D "
                    "data x feature mesh) needs hist_backend=segsum or "
                    "onehot: the stream kernel packs row-major group words, "
                    "which group sharding cannot slice")
        if double:
            return ("hist_precision=double requires hist_backend=segsum or "
                    "onehot (the stream kernel is f32/int8)")
    if compact and (mesh == "rows_x_feature"
                    or (mesh == "rows" and backend != "stream")):
        return ("row compaction under a row-sharded mesh requires "
                "hist_backend=stream on a rows-only mesh (per-shard "
                "partition)")
    return None


def check_hist_backend(backend: str, **job) -> None:
    """Raise hist_backend_refusal()'s reason as a LightGBMError."""
    why = hist_backend_refusal(backend, **job)
    if why:
        raise LightGBMError(why)


def resolve_hist_backend(requested: str, *, tpu: bool, mesh: str = "none",
                         stream_fits: bool = False) -> str:
    """THE rule: a requested ``hist_backend`` to the formulation that runs.

    tpu: on a TPU or not; mesh: a MESH_KINDS name; stream_fits: whether the
    stream kernel takes the job (GBDT._stream_fits: leaves, splits a round
    and bin width; never for a bare build_histograms call, which has no
    route to fuse).  A name the job cannot run is a LightGBMError, except
    under the voting learner, which ignores the request (its shard_map
    grower never reads the stream layout).
    """
    if requested not in HIST_BACKENDS:
        raise LightGBMError(
            f"unknown hist_backend={requested!r}; one of {HIST_BACKENDS}")
    contraction = "onehot" if tpu else "segsum"
    if mesh == "voting":
        return contraction
    if requested != "auto":
        check_hist_backend(requested, mesh=mesh)
        return requested
    if tpu and stream_fits \
            and hist_backend_refusal("stream", mesh=mesh) is None:
        return "stream"
    return contraction


def _op_backend(backend: str) -> str:
    """build_histograms*'s backend: the engine's rule with no stream kernel
    on offer (``stream`` is the growers' fused pass, not an op)."""
    backend = resolve_hist_backend(backend, tpu=on_tpu())
    if backend == "stream":
        raise ValueError(
            "hist_backend=stream is the growers' fused route+histogram "
            "kernel (pallas/stream_kernel.route_and_hist); build_histograms "
            "runs segsum or onehot")
    return backend


def build_histograms(bins: jax.Array, slot: jax.Array, grad: jax.Array,
                     hess: jax.Array, cnt: jax.Array, num_slots: int,
                     max_group_bins: int, backend: str = "auto",
                     block_rows: int = 16384, dtype=jnp.float32,
                     acc_dtype=jnp.float32) -> jax.Array:
    """Build per-slot histograms.

    Args:
      bins: (N, G) integer bin matrix (uint8/uint16).
      slot: (N,) int32 — histogram slot per row; negative = skip row.
      grad/hess: (N,) float32 (pre-multiplied by any bagging mask).
      cnt: (N,) float32 count weight (the bagging mask itself; 1.0 = in-bag).
      num_slots: S (static).
      max_group_bins: Bmax (static).
      acc_dtype: accumulator dtype. float64 (hist_precision=double; needs an
        enclosing jax.enable_x64) mirrors the
        reference's float32-gradients-into-double-histograms arithmetic
        (hist_t, src/io/dense_bin.hpp) so near-tied split gains resolve the
        same way stock LightGBM resolves them.
    Returns:
      (S, G, Bmax, 3) acc_dtype histograms.
    """
    if _op_backend(backend) == "segsum":
        return _hist_segsum(bins, slot, grad, hess, cnt, num_slots, max_group_bins,
                            acc_dtype)
    return _hist_onehot(bins, slot, grad, hess, cnt, num_slots, max_group_bins,
                        block_rows, dtype, acc_dtype)


def _hist_segsum(bins, slot, grad, hess, cnt, num_slots, max_group_bins,
                 acc_dtype=jnp.float32):
    n, num_groups = bins.shape
    valid = slot >= 0
    s = jnp.where(valid, slot, 0)
    w = jnp.stack([grad, hess, cnt], axis=-1).astype(acc_dtype)  # (N, 3)
    w = w * valid[:, None].astype(w.dtype)

    def per_group(bins_col):
        ids = s * max_group_bins + bins_col.astype(jnp.int32)  # (N,)
        h = jax.ops.segment_sum(w, ids, num_segments=num_slots * max_group_bins)
        return h.reshape(num_slots, max_group_bins, NUM_CHANNELS)

    # scan over groups keeps peak memory at O(N) instead of O(N*G)
    hist_g = jax.lax.map(per_group, bins.T)          # (G, S, Bmax, 3)
    return jnp.transpose(hist_g, (1, 0, 2, 3))       # (S, G, Bmax, 3)


def _hist_onehot(bins, slot, grad, hess, cnt, num_slots, max_group_bins, block_rows,
                 dtype, acc_dtype=jnp.float32):
    """Blocked one-hot matmul: per row block and group, (Bmax, T) @ (T, 3S) on the MXU."""
    n, num_groups = bins.shape
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        slot = jnp.pad(slot, (0, pad), constant_values=-1)
        grad = jnp.pad(grad, (0, pad))
        hess = jnp.pad(hess, (0, pad))
        cnt = jnp.pad(cnt, (0, pad))

    valid = slot >= 0
    s = jnp.where(valid, slot, 0)
    # W[n, 3*s + c] = w_c[n] * 1[slot[n] == s]   -> (N, 3S)
    slot_oh = jax.nn.one_hot(s, num_slots, dtype=dtype) * valid[:, None].astype(dtype)
    w = jnp.stack([grad.astype(dtype), hess.astype(dtype),
                   cnt.astype(dtype)], axis=-1)          # (N, 3)
    W = (slot_oh[:, :, None] * w[:, None, :]).reshape(-1, num_slots * NUM_CHANNELS)

    bins_b = bins.reshape(nb, block_rows, num_groups)
    W_b = W.reshape(nb, block_rows, num_slots * NUM_CHANNELS)

    def block_body(carry, xs):
        b_blk, w_blk = xs                                  # (T, G), (T, 3S)
        def group_body(g, acc):
            col = jax.lax.dynamic_index_in_dim(b_blk, g, axis=1, keepdims=False)
            oh = jax.nn.one_hot(col.astype(jnp.int32), max_group_bins,
                                dtype=dtype, axis=0)       # (Bmax, T)
            h = jax.lax.dot(oh, w_blk,
                            preferred_element_type=acc_dtype)   # (Bmax, 3S)
            return acc.at[g].add(h)
        acc0 = carry
        acc = jax.lax.fori_loop(0, num_groups, group_body, acc0)
        return acc, None

    init = jnp.zeros((num_groups, max_group_bins, num_slots * NUM_CHANNELS), acc_dtype)
    hist, _ = jax.lax.scan(block_body, init, (bins_b, W_b))
    # (G, Bmax, 3S) -> (S, G, Bmax, 3)
    hist = hist.reshape(num_groups, max_group_bins, num_slots, NUM_CHANNELS)
    return jnp.transpose(hist, (2, 0, 1, 3))


def build_histograms_k(bins: jax.Array, slot: jax.Array, grad: jax.Array,
                       hess: jax.Array, cnt: jax.Array, num_class: int,
                       num_slots: int, max_group_bins: int,
                       backend: str = "auto", block_rows: int = 16384,
                       dtype=jnp.float32,
                       acc_dtype=jnp.float32) -> jax.Array:
    """Per-class per-slot histograms for the BATCHED MULTICLASS path.

    slot/grad/hess: (K, N) — class k's histogram slot / gradient per row;
    cnt: (N,) shared count weight. Returns (K, S, G, Bmax, 3) acc_dtype.

    onehot amortizes the class-independent bin one-hot across the stacked
    class x slot channel axis — ONE widened contraction serves all K
    classes' gradient channels (the reference's single histogram pass over
    all class gradients, cuda_histogram_constructor.cu) — while segsum
    vmaps the per-class scatter so each class's sums are bit-identical to
    a standalone call.
    """
    if _op_backend(backend) == "segsum":
        return jax.vmap(
            lambda s, g, h: _hist_segsum(bins, s, g, h, cnt, num_slots,
                                         max_group_bins, acc_dtype)
        )(slot, grad, hess)
    return _hist_onehot_k(bins, slot, grad, hess, cnt, num_class,
                          num_slots, max_group_bins, block_rows, dtype,
                          acc_dtype)


def _hist_onehot_k(bins, slot, grad, hess, cnt, num_class, num_slots,
                   max_group_bins, block_rows, dtype,
                   acc_dtype=jnp.float32):
    """Widened blocked one-hot matmul: per block and group, ONE (Bmax, T)
    bin one-hot contracted against the stacked (T, K*S*3) class x slot
    weight operand — all K classes' histograms from a single pass over the
    bin matrix (vs K passes each rebuilding the one-hot)."""
    n, num_groups = bins.shape
    K, S = num_class, num_slots
    # W carries K*S*3 channels; shrink blocks so its footprint stays put
    block_rows = max(256, block_rows // max(K, 1))
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        slot = jnp.pad(slot, ((0, 0), (0, pad)), constant_values=-1)
        grad = jnp.pad(grad, ((0, 0), (0, pad)))
        hess = jnp.pad(hess, ((0, 0), (0, pad)))
        cnt = jnp.pad(cnt, (0, pad))

    valid = slot >= 0
    s = jnp.where(valid, slot, 0)
    w3 = jnp.stack([grad.astype(dtype), hess.astype(dtype),
                    jnp.broadcast_to(cnt, grad.shape).astype(dtype)],
                   axis=2)                                   # (K, N, 3)
    bins_b = bins.reshape(nb, block_rows, num_groups)
    s_b = s.reshape(K, nb, block_rows).transpose(1, 0, 2)    # (nb, K, T)
    v_b = valid.reshape(K, nb, block_rows).transpose(1, 0, 2)
    w_b = w3.reshape(K, nb, block_rows, 3).transpose(1, 0, 2, 3)

    def block_body(carry, xs):
        b_blk, s_blk, v_blk, w_blk = xs
        slot_oh = jax.nn.one_hot(s_blk, S, dtype=dtype) \
            * v_blk[..., None].astype(dtype)                 # (K, T, S)
        W = (slot_oh[..., :, None] * w_blk[..., None, :])    # (K, T, S, 3)
        W = W.transpose(1, 0, 2, 3).reshape(block_rows, K * S * 3)

        def group_body(g, acc):
            col = jax.lax.dynamic_index_in_dim(b_blk, g, axis=1,
                                               keepdims=False)
            oh = jax.nn.one_hot(col.astype(jnp.int32), max_group_bins,
                                dtype=dtype, axis=0)         # (Bmax, T)
            h = jax.lax.dot(oh, W,
                            preferred_element_type=acc_dtype)
            return acc.at[g].add(h)
        return jax.lax.fori_loop(0, num_groups, group_body, carry), None

    init = jnp.zeros((num_groups, max_group_bins, K * S * 3), acc_dtype)
    hist, _ = jax.lax.scan(block_body, init, (bins_b, s_b, v_b, w_b))
    hist = hist.reshape(num_groups, max_group_bins, K, S, NUM_CHANNELS)
    return jnp.transpose(hist, (2, 3, 0, 1, 4))              # (K, S, G, B, 3)


def hist_subtract(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Histogram subtraction trick (reference: serial_tree_learner.cpp:481
    use_subtract). Shape-agnostic: works on (S, G, Bmax, C) and on the
    batched multiclass (K, S, G, Bmax, C) channel layout alike."""
    return parent - child

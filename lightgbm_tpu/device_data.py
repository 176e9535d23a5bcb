"""Host BinnedData -> device arrays + static layouts for the growers.

Mirrors the reference's CUDA io layer (src/io/cuda/cuda_row_data.cpp, CUDAColumnData):
the binned matrix is resident in HBM; layout metadata is baked into the compiled program.
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .binning import (BIN_CATEGORICAL, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinnedData)
from .ops.grow import RoutingLayout
from .ops.split import FeatureLayout


class DeviceData(NamedTuple):
    bins: jax.Array              # (N, G)
    layout: FeatureLayout
    routing: RoutingLayout
    num_data: int
    num_features: int
    num_groups: int
    max_bins: int                # Bmax


def build_layouts(binned: BinnedData, pad_rows_to: int = 256):
    """Compute FeatureLayout + RoutingLayout (numpy, then device constants)."""
    F = binned.num_features
    G = binned.num_groups
    Bmax = int(max(int(binned.group_bin_counts.max()) if G else 1,
                   int(binned.feature_num_bins.max()) if F else 1))

    gather_idx = np.zeros((F, Bmax), np.int32)
    valid_mask = np.zeros((F, Bmax), bool)
    residual_pos = np.full(F, -1, np.int32)
    nan_bin = np.full(F, -1, np.int32)
    is_cat = np.zeros(F, bool)
    num_bins = np.asarray(binned.feature_num_bins, np.int32).copy()

    feat_group = np.zeros(F, np.int32)
    span_start = np.zeros(F, np.int32)
    default_bin = np.zeros(F, np.int32)
    bundled = np.zeros(F, bool)
    mzero_bin = np.full(F, -1, np.int32)

    for gi, feats in enumerate(binned.group_features):
        base = gi * Bmax
        if len(feats) == 1:
            f = feats[0]
            m = binned.bin_mappers[f]
            nb = m.num_bins
            gather_idx[f, :nb] = base + np.arange(nb)
            valid_mask[f, :nb] = True
            feat_group[f] = gi
            span_start[f] = 0
            default_bin[f] = m.default_bin
            if m.bin_type == BIN_CATEGORICAL:
                is_cat[f] = True
            elif m.missing_type == MISSING_NAN:
                nan_bin[f] = nb - 1
            elif m.missing_type == MISSING_ZERO:
                # zeros are the missing value (zero_as_missing): they live
                # in the default bin and follow the split's default
                # direction (reference: MissingType::Zero, bin.h:28)
                mzero_bin[f] = m.default_bin
        else:
            in_group = 1
            for f in feats:
                m = binned.bin_mappers[f]
                nb = m.num_bins
                d = m.default_bin
                for b in range(nb):
                    if b == d:
                        continue
                    stored = in_group + (b if b < d else b - 1)
                    gather_idx[f, b] = base + stored
                    valid_mask[f, b] = True
                residual_pos[f] = d
                feat_group[f] = gi
                span_start[f] = in_group
                default_bin[f] = d
                bundled[f] = True
                if m.bin_type == BIN_CATEGORICAL:
                    is_cat[f] = True
                elif m.missing_type == MISSING_NAN:
                    nan_bin[f] = nb - 1
                elif m.missing_type == MISSING_ZERO:
                    mzero_bin[f] = d
                in_group += nb - 1

    layout = FeatureLayout(
        gather_idx=jnp.asarray(gather_idx),
        valid_mask=jnp.asarray(valid_mask),
        residual_pos=jnp.asarray(residual_pos),
        nan_bin=jnp.asarray(nan_bin),
        is_cat=jnp.asarray(is_cat),
        num_bins=jnp.asarray(num_bins),
        mzero_bin=jnp.asarray(mzero_bin),
    )
    routing = RoutingLayout(
        feat_group=jnp.asarray(feat_group),
        span_start=jnp.asarray(span_start),
        default_bin=jnp.asarray(default_bin),
        bundled=jnp.asarray(bundled),
        nan_bin=jnp.asarray(nan_bin),
        num_bins=jnp.asarray(num_bins),
        mzero_bin=jnp.asarray(mzero_bin),
    )
    return layout, routing, Bmax


def _ship_supported() -> bool:
    """Chunked device ship pays off only where buffer donation lets the
    update run in place (TPU/GPU); XLA:CPU copies the whole buffer per
    chunk.  LGBTPU_INGEST_SHIP=1 forces it (tests, perf sentinel)."""
    import os
    env = os.environ.get("LGBTPU_INGEST_SHIP", "")
    if env in ("0", "1"):
        return env == "1"
    from .runtime import platform_name
    return platform_name() != "cpu"


_ship_jit = None


def ship_binned_chunks(bins: np.ndarray, n_pad: int,
                       chunk_rows: int) -> jax.Array:
    """Bin-and-ship: place host row blocks into a device-resident
    (n_pad, G) buffer one chunk at a time through a single compiled
    dynamic_update_slice program (watched_jit name ``ingest_ship``,
    donated buffer) — the host never stages a padded full-size copy.
    Chunks are padded to one fixed shape so the program compiles once."""
    global _ship_jit
    from .telemetry import watched_jit
    if _ship_jit is None:
        def _ship(buf, chunk, start):
            return jax.lax.dynamic_update_slice(
                buf, chunk, (start, jnp.int32(0)))
        _ship_jit = watched_jit(_ship, name="ingest_ship",
                                donate_argnums=(0,))
    n, g = bins.shape
    R = max(256, -(-int(chunk_rows) // 256) * 256)
    n_ship = -(-n_pad // R) * R
    buf = jnp.zeros((n_ship, g), bins.dtype)
    staged = np.zeros((R, g), bins.dtype)
    for s in range(0, n, R):
        m = min(R, n - s)
        staged[:m] = bins[s:s + m]
        if m < R:
            staged[m:] = 0
        buf = _ship_jit(buf, jnp.asarray(staged), jnp.int32(s))
    return buf[:n_pad] if n_ship != n_pad else buf


def _ship_shards(bins: np.ndarray, n_pad: int, g_pad: int,
                 sharding) -> jax.Array:
    """Place a host table straight onto a mesh: each device is handed its
    own (rows, groups) block, cut from the host array and zero-filled where
    it reaches past the table (the row pad to whole kernel blocks a shard,
    the group pad of a feature-sharded mesh).  No device holds more than
    its shard at any time and the pad is never a device copy — the
    in-process form of parallel/dist_data.py ``make_global_bins``."""
    n, g = bins.shape

    def block(index):
        rows, cols = (sl.indices(dim) for sl, dim in
                      zip(index, (n_pad, g_pad)))
        part = np.ascontiguousarray(bins[rows[0]:min(rows[1], n),
                                         cols[0]:min(cols[1], g)])
        short = (rows[1] - rows[0] - part.shape[0],
                 cols[1] - cols[0] - part.shape[1])
        if any(short):
            part = np.pad(part, ((0, short[0]), (0, short[1])))
        return part

    return jax.make_array_from_callback((n_pad, g_pad), sharding, block)


def device_view(binned: BinnedData) -> DeviceData:
    """The layouts and the table's dimensions without the table (``bins``
    is None): what an engine decides its mesh padding from before any row
    is shipped."""
    layout, routing, Bmax = build_layouts(binned)
    return DeviceData(bins=None, layout=layout, routing=routing,
                      num_data=binned.bins.shape[0],
                      num_features=binned.num_features,
                      num_groups=binned.num_groups, max_bins=Bmax)


def to_device(binned: BinnedData, pad_rows_to: int = 256,
              sharding=None, ship_chunk_rows=None,
              pad_groups_to: int = 1, view: DeviceData = None) -> DeviceData:
    """``device_view`` (or the caller's, ``view``) with the binned table on
    the device.  With ``sharding`` (an in-process mesh) the table goes a
    shard to a device from the host (``_ship_shards``), rows padded to
    ``pad_rows_to`` and groups to ``pad_groups_to``; without, whole onto
    the default device."""
    from .telemetry import boundary, device_hbm_bytes
    view = view or device_view(binned)
    bins = binned.bins
    n, g = bins.shape
    n_pad = -(-n // pad_rows_to) * pad_rows_to
    # the span ends with the bins ON the device: the transfer is
    # asynchronous, and everything built next reads them anyway
    with boundary("Dataset::Ship", rows=n, groups=g) as span:
        if sharding is not None:
            arr = _ship_shards(bins, n_pad, -(-g // pad_groups_to)
                               * pad_groups_to, sharding)
        elif ship_chunk_rows and _ship_supported():
            arr = ship_binned_chunks(bins, n_pad, int(ship_chunk_rows))
        elif isinstance(bins, np.memmap):
            # out-of-core bins: transfer straight from the mapping (pages
            # stream in, file-backed and reclaimable) and pad ON DEVICE —
            # never materialize a padded full-size host copy
            arr = jnp.asarray(bins)
            if n_pad != n:
                arr = jnp.pad(arr, ((0, n_pad - n), (0, 0)))
        else:
            bins = np.ascontiguousarray(bins)
            if n_pad != n:
                bins = np.pad(bins, ((0, n_pad - n), (0, 0)))
            arr = jnp.asarray(bins)
        arr.block_until_ready()
        if sharding is not None:
            shards = arr.addressable_shards
            span.set(shards=len({s.device for s in shards}),
                     bytes_per_shard=int(shards[0].data.nbytes))
        span.set(**device_hbm_bytes())
    return view._replace(bins=arr)

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


def to_device(binned: BinnedData, pad_rows_to: int = 256,
              sharding=None, ship_chunk_rows=None) -> DeviceData:
    from .telemetry import boundary
    layout, routing, Bmax = build_layouts(binned)
    bins = binned.bins
    n = bins.shape[0]
    n_pad = -(-n // pad_rows_to) * pad_rows_to
    # the span ends with the bins ON the device: the transfer is
    # asynchronous, and everything built next reads them anyway
    with boundary("Dataset::Ship", rows=n, groups=bins.shape[1]):
        if ship_chunk_rows and _ship_supported():
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
        if sharding is not None:
            arr = jax.device_put(arr, sharding)
        arr.block_until_ready()
    return DeviceData(bins=arr, layout=layout, routing=routing,
                      num_data=n, num_features=binned.num_features,
                      num_groups=binned.num_groups, max_bins=Bmax)

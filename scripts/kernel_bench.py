"""Microbenchmark of the fused route+hist stream kernel (dev tool).

Times route_and_hist directly at HIGGS bench shapes (10.5M rows, G=28,
B=64, S=64, L=255) under each LGBTPU_KABLATE probe, isolating kernel-phase
costs from engine overhead (the full-bench ablation route corrupts training
and shifts time into trivial-tree host syncs, so it cannot attribute time).

Usage: python scripts/kernel_bench.py [rows] — runs ONE configuration per
process; the sweep driver loops over LGBTPU_KABLATE values externally
(the probe is read at stream_kernel import time).

KB_TRACE_OUT=<path> records each pass as a telemetry span and writes a
Chrome/Perfetto trace (lightgbm_tpu.telemetry.export_trace) on exit.
"""
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    int_path = os.environ.get("KB_INT", "1") == "1"
    two_pass = os.environ.get("KB_TWOPASS", "0") == "1"
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.runtime import configure_compile_cache, require_tpu
    configure_compile_cache()
    # a kernel time from the CPU interpreter says nothing about the chip
    dev = require_tpu("kernel_bench (device kernel timings)")
    from lightgbm_tpu.pallas.stream_kernel import (build_route_tables,
                                                   pack_bins_T,
                                                   route_and_hist,
                                                   stream_block_rows)
    from lightgbm_tpu.ops.grow import RoutingLayout

    G, Bmax, S, L = 28, 63, 64, 255
    T = stream_block_rows(Bmax, G)
    rs = np.random.RandomState(0)
    bins = rs.randint(0, Bmax, size=(rows, G)).astype(np.uint8)
    layout = pack_bins_T(jnp.asarray(bins), T, max_bins=Bmax)
    n_pad = layout.n_pad
    F = G
    routing = RoutingLayout(
        feat_group=jnp.arange(F, dtype=jnp.int32),
        span_start=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        bundled=jnp.zeros(F, bool),
        nan_bin=jnp.full(F, -1, jnp.int32),
        num_bins=jnp.full(F, Bmax, jnp.int32))

    leaf_id = jnp.zeros((1, n_pad), jnp.int32)
    if int_path:
        g = rs.randint(-32, 32, size=n_pad).astype(np.float32)
        h = rs.randint(0, 32, size=n_pad).astype(np.float32)
    else:
        g = rs.randn(n_pad).astype(np.float32)
        h = rs.rand(n_pad).astype(np.float32)
    w_T = jnp.zeros((8, n_pad), jnp.float32)
    w_T = w_T.at[0].set(jnp.asarray(g)).at[1].set(jnp.asarray(h)) \
             .at[2].set(1.0)

    # S/2 random leaf splits (plausible mid-tree round)
    zL = jnp.zeros(L, jnp.int32)
    chosen = jnp.zeros(L, jnp.int32).at[:S].set(1)
    feats = jnp.asarray(rs.randint(0, F, L), jnp.int32)
    thrs = jnp.asarray(rs.randint(1, Bmax - 1, L), jnp.int32)
    newid = jnp.asarray(np.arange(L) + 1, jnp.int32) % L
    s1 = jnp.zeros(L, jnp.int32).at[:S].set(jnp.arange(1, S + 1, dtype=jnp.int32))
    tabs = build_route_tables(chosen, feats, thrs, zL, newid, s1, zL, zL,
                              routing, L)
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)

    def run(lid):
        nl, hist, cnt = route_and_hist(
            layout.bins_T, lid, w_T, tabs, bits, S, Bmax, G, L,
            block_rows=T, has_cat=False, two_pass=two_pass,
            int_weights=int_path)
        return nl, hist, cnt

    from lightgbm_tpu import telemetry as tel
    trace_out = os.environ.get("KB_TRACE_OUT", "")
    if trace_out:
        tel.configure(enabled=True, trace_out=trace_out)

    with tel.span("kernel_bench::warmup", rows=rows):
        nl, hist, cnt = run(leaf_id)
        jax.block_until_ready((nl, hist, cnt))
    reps = 10
    # chain each rep on the previous output so every dispatch is real
    # sequential device work (identical repeated dispatches could be
    # deduplicated or overlapped by the runtime)
    lid = nl % L
    t0 = time.time()
    for rep in range(reps):
        with tel.span("kernel_bench::route_and_hist", rep=rep):
            out = run(lid)
            lid = out[0] % L
    jax.block_until_ready(out)
    dt = (time.time() - t0) / reps
    if trace_out:
        tel.flush()
        print(f"KB trace written to {trace_out}")
    gbps = (layout.bins_T.size * 4 + n_pad * (4 + 12)) / dt / 1e9
    print(f"KB {dev['device_kind']} x{dev['device_count']} "
          f"ablate={os.environ.get('LGBTPU_KABLATE','')!r} "
          f"int={int_path} two_pass={two_pass} rows={rows} T={T} "
          f"-> {dt*1e3:.2f} ms/pass  ({rows/dt/1e9:.2f} Grows/s, "
          f"~{gbps:.0f} GB/s effective)")


if __name__ == "__main__":
    main()

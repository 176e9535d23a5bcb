#!/usr/bin/env python3
"""pallas/compact_kernel.py on the chip at the shape the cell
`higgs_goss_train` compacts (32 x 31,498,240 int8 bins and 8 float32 weight
rows -> 11,796,480 columns, 30% in-bag), against what it replaced: the stable
`sort_key_val` permutation and two `jnp.take` gathers.  Tolerance 0 on every
byte (live columns the take's, zero behind), then both times.  Interpret mode
proves the arithmetic (tests/test_compact_kernel.py); this proves Mosaic's
lowering, which no CPU run can.

    chiprun -- python scripts/compact_rows_check.py [seed]

Last line of standard output: a JSON record; exit 1 on a mismatch, 2 off the
chip.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp

from lightgbm_tpu import runtime
from lightgbm_tpu.ops.compact import plan_sample_rows
from lightgbm_tpu.pallas.compact_kernel import compact_rows
from lightgbm_tpu.telemetry.watchdog import watched_jit

BLOCK, GROUPS, CHANNELS, MASK_ROW = 4096, 32, 8, 2
ROWS, CAPACITY = 7690 * BLOCK, 2880 * BLOCK


@functools.partial(watched_jit, name="compact_check_operands", warn_after=0)
def operands(key):
    k_bins, k_bag, k_w = jax.random.split(key, 3)
    bins = jax.random.randint(k_bins, (GROUPS, ROWS), 0, 63, jnp.int8)
    bag = (jax.random.uniform(k_bag, (ROWS,)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(k_w, (CHANNELS, ROWS), jnp.float32) * bag
    return bins, w.at[MASK_ROW].set(bag)


@functools.partial(watched_jit, name="compact_check_by_take", warn_after=0)
def by_take(bins, w):
    plan = plan_sample_rows(w[MASK_ROW], CAPACITY)
    return (jnp.take(bins, plan.perm, axis=1),
            jnp.take(w, plan.perm, axis=1), plan.nc)


@functools.partial(watched_jit, name="compact_check_differing", warn_after=0)
def differing(got_bins, got_w, want_bins, want_w, nc):
    """Bytes that differ on the live columns, or are not zero behind them."""
    live = jnp.arange(CAPACITY) < nc
    got = jax.lax.bitcast_convert_type(got_w, jnp.int32)
    want = jax.lax.bitcast_convert_type(want_w, jnp.int32)
    return (jnp.sum(jnp.where(live, got_bins != want_bins, got_bins != 0)),
            jnp.sum(jnp.where(live, got != want, got != 0)))


def timed_ms(fn, *args, reps):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e3


def main():
    if not runtime.on_tpu():
        print(f"compact_rows_check: needs a TPU, found {jax.default_backend()}")
        return 2
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    bins, w = operands(jax.random.PRNGKey(seed % (2 ** 31)))
    (want_bins, want_w, nc), take_ms = timed_ms(by_take, bins, w, reps=2)
    (got_bins, got_w, placed), stream_ms = timed_ms(
        lambda b, x: compact_rows(b, x, mask_row=MASK_ROW, capacity=CAPACITY,
                                  block_rows=BLOCK), bins, w, reps=5)
    bad = [int(n) for n in differing(got_bins, got_w, want_bins, want_w, nc)]
    ok = not any(bad) and int(placed[0]) == min(int(nc), CAPACITY)
    print(json.dumps({"ok": ok, "differing_bytes": bad,
                      "rows": ROWS, "capacity": CAPACITY, "in_bag": int(nc),
                      "placed": int(placed[0]),
                      "sort_and_take_ms": take_ms, "compact_rows_ms": stream_ms,
                      "device": runtime.device_record()}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

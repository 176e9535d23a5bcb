"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the HIGGS-like configuration `python bench.py`
times (28 features, 255 leaves, 63 bins, quantized gradients; rows cut to
CHIP_SMOKE_ROWS): `lgb.train` -> `Booster.predict` on a held-out batch big
enough for the device walk -> a few requests to an in-process
`serving.ServingApp`.  It checks that the answers are right by the repo's
own references and that no CPU/interpret/host fallback was taken on the
way, then prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and exits 0.  Any failed check, or no TPU, exits non-zero with the reason and
prints no result.  With >= 4 devices it adds the data-parallel mesh leg, at
the shape of a benchmark configuration (MESH_CONFIG: the four-chip cell's).

It reports no s/tree or rows/s: the wall and compile seconds it prints exist
so a cold and a warm run can be compared, not to be read as a benchmark.

    python chip_smoke.py                      # on the chip (through chiprun)
    CHIP_SMOKE_ROWS=10500000 python chip_smoke.py

`--digest` is the identity run instead: the benchmark's three training
configurations at their own shapes and reduced rows, 33 trees each, and as
the last line the model text's digest and `hist_passes` at every flag poll,
by configuration.  It imports the program from the WORKING DIRECTORY, so two
checkouts are compared by running this one file from each; a change that
keeps the trees prints the parent's line.

    (cd scratch_src/parent && python ../../chip_smoke.py --digest)

`--mesh [configuration file]` is the mesh leg alone (four chips): the file's
generator, width and parameters at CHIP_SMOKE_ROWS rows, the per-device
kernel's root and 64-slot passes reduced over the mesh against the NumPy
reference on the whole table, the two collectives' models, and the devices'
memory balance with nothing else in the process.

    chiprun --chips 4 -- python chip_smoke.py --mesh
"""
import contextlib
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

ROWS = int(os.environ.get("CHIP_SMOKE_ROWS", 2_097_152))
MESH_CONFIG = "benchmark/configs/criteo_dp_like.json"
HOLDOUT = 100_000
ITERS = 6                      # one warm-up iteration plus five more
FAILED = []
_T0 = time.time()


def say(msg):
    print(f"[{time.time() - _T0:7.1f}s] {msg}", flush=True)


def check(name, ok, detail=""):
    ok = bool(ok)
    say(f"{'ok  ' if ok else 'FAIL'} {name}" + (f" — {detail}" if detail else ""))
    if not ok:
        FAILED.append(name)
    return ok


@contextlib.contextmanager
def phase(name):
    """A failed phase is recorded and the run goes on to the phases that do
    not depend on it — one chip call should say everything that is wrong."""
    say(f"== {name}")
    t0 = time.time()
    try:
        yield
    except Exception:  # noqa: BLE001 — reported; the run exits non-zero
        traceback.print_exc(file=sys.stdout)
        FAILED.append(f"{name} (raised)")
    say(f"== {name}: {time.time() - t0:.1f} s")


class CompileClock:
    """Seconds JAX itself reports spending in compilation and in the
    persistent cache, summed per event name."""

    def __init__(self):
        import jax.monitoring
        self.secs = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if "compil" in event or "cache" in event:
            self.secs[event] = self.secs.get(event, 0.0) + duration

    def report(self):
        for k in sorted(self.secs):
            say(f"   {k}: {self.secs[k]:.2f} s")
        backend = sum(v for k, v in self.secs.items()
                      if k.endswith("backend_compile_duration"))
        say(f"compile seconds (backend_compile_duration): {backend:.2f}")
        return backend


def leaf_counts(node, out):
    if "leaf_count" in node:
        out.append(node["leaf_count"])
    else:
        leaf_counts(node["left_child"], out)
        leaf_counts(node["right_child"], out)
    return out


def check_trees(bst, n_rows, num_leaves, tag):
    dumped = bst.dump_model()["tree_info"]
    leaves = [t["num_leaves"] for t in dumped]
    sums = [sum(leaf_counts(t["tree_structure"], [])) for t in dumped]
    check(f"{tag}: every tree reaches {num_leaves} leaves",
          leaves and all(v == num_leaves for v in leaves), f"{leaves}")
    check(f"{tag}: leaf counts sum to N={n_rows} in every tree",
          all(s == n_rows for s in sums), f"{sums}")


def kernel_exactness(dd, params):
    """tests/test_stream_kernel.py::test_int8_hist_exact on the chip, at the
    cell's block shape, plus a 64-slot pass (the round shape) — the compiled
    Mosaic kernel against the segment-sum reference, exactly."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import _hist_segsum
    from lightgbm_tpu.pallas.stream_kernel import (build_route_tables,
                                                   pack_bins_T,
                                                   route_and_hist,
                                                   stream_block_rows)
    G, Bmax, L = dd.num_groups, dd.max_bins, params["num_leaves"]
    T = stream_block_rows(Bmax, G, True)
    check("block shape is the cell's", (G, Bmax, L, T) == (28, 63, 255, 4096),
          f"G={G} Bmax={Bmax} L={L} T={T}")
    N = 8 * T                                   # eight grid blocks
    bins = dd.bins[:N]
    rs = np.random.RandomState(0)
    gi = jnp.asarray(rs.randint(-32, 33, N).astype(np.float32))
    hi = jnp.asarray(rs.randint(0, 33, N).astype(np.float32))
    cnt = jnp.ones(N, jnp.float32)
    slay = pack_bins_T(bins, T, max_bins=Bmax)
    check("bins are in the u8 layout the trainer uses",
          slay.bins_T.dtype == jnp.int8, str(slay.bins_T.dtype))
    w_T = (jnp.zeros((8, N), jnp.float32).at[0].set(gi).at[1].set(hi)
           .at[2].set(cnt))
    zL = jnp.zeros(L, jnp.int32)
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)
    kw = dict(block_rows=T, has_cat=False, int_weights=True)

    # root pass: every row in leaf 0 -> slot 0
    tabs = build_route_tables(zL, zL, zL, zL, zL, zL, zL, zL.at[0].set(1),
                              dd.routing, L)
    leaf = jnp.zeros((1, N), jnp.int32)
    lowered = route_and_hist.lower(slay.bins_T, leaf, w_T, tabs, bits, 1,
                                   Bmax, G, L, **kw).as_text()
    check("route_and_hist lowers to a Mosaic tpu_custom_call",
          "tpu_custom_call" in lowered)
    _, hist, scnt = route_and_hist(slay.bins_T, leaf, w_T, tabs, bits, 1,
                                   Bmax, G, L, **kw)
    ref = _hist_segsum(bins, jnp.zeros(N, jnp.int32), gi, hi, cnt, 1, Bmax)
    check("root pass: int32 histogram == _hist_segsum exactly",
          hist.dtype == jnp.int32 and np.array_equal(
              np.asarray(hist, np.float64),
              np.asarray(ref[..., :2], np.float64)))
    check("root pass: slot count == N", float(scnt[0]) == float(N))
    # the form the trainer's root takes on this path: the factored contraction
    _, fact, _ = route_and_hist(slay.bins_T, leaf, w_T, tabs, bits, 1, Bmax,
                                G, L, root=True, **kw)
    check("factored root pass: int32 histogram == the one-hot root's exactly",
          fact.dtype == jnp.int32 and fact.shape == hist.shape
          and np.array_equal(np.asarray(fact), np.asarray(hist)))

    # round shape: rows spread over 64 leaves, leaf l kept in slot l
    S = 64
    lid = jnp.asarray(rs.randint(0, S, N).astype(np.int32))
    keep = jnp.where(jnp.arange(L) < S, jnp.arange(L) + 1, 0).astype(jnp.int32)
    tabs = build_route_tables(zL, zL, zL, zL, zL, zL, zL, keep, dd.routing, L)
    new_leaf, hist, scnt = route_and_hist(slay.bins_T, lid.reshape(1, -1),
                                          w_T, tabs, bits, S, Bmax, G, L,
                                          **kw)
    ref = _hist_segsum(bins, lid, gi, hi, cnt, S, Bmax)
    check("64-slot pass: int32 histogram == _hist_segsum exactly",
          np.array_equal(np.asarray(hist, np.float64),
                         np.asarray(ref[..., :2], np.float64)))
    check("64-slot pass: rows keep their leaf, slot counts exact",
          np.array_equal(np.asarray(new_leaf[0]), np.asarray(lid))
          and np.array_equal(np.asarray(scnt),
                             np.bincount(np.asarray(lid), minlength=S)))
    small_pass_exactness("one tile", bins, slay.bins_T, dd.routing, gi, hi,
                         kw, G, Bmax, L,
                         [(0, 3, 24, 4, True), (2, 17, 40, 5, False)])


def round_tables(splits, routing, L):
    """(leaf, feature, threshold bin, new leaf, smaller child is the left
    one) in slot order -> the route tables of a round that makes those
    splits, slot s + 1 for the smaller child of split s."""
    import jax.numpy as jnp
    from lightgbm_tpu.pallas.stream_kernel import build_route_tables
    cols = np.zeros((7, L), np.int32)       # chosen feat thr dir new sl1 sr1
    for s, (at, feat, thr, new, left) in enumerate(splits):
        cols[:, at] = (1, feat, thr, 0, new, (s + 1) * left,
                       (s + 1) * (not left))
    return build_route_tables(*(jnp.asarray(c) for c in cols),
                              jnp.zeros(L, jnp.int32), routing, L)


def small_pass_exactness(tag, bins, bins_T, routing, gi, hi, kw, G, Bmax, L,
                         splits):
    """The small-slot pass (a round that splits one or two leaves) on the
    chip: route_and_hist_live at k = 1 and k = 2 against the 64-slot pass on
    the same rows and against NumPy alone (reference_hist: np.add.at, int64),
    tolerance 0.  splits: (leaf, feature, threshold bin, new leaf, smaller
    child is the left one) in slot order; rows sit in leaves 0..3, so some
    are in no slot."""
    import jax.numpy as jnp
    from lightgbm_tpu.pallas.stream_kernel import (route_and_hist,
                                                   route_and_hist_live)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    import reference_hist
    N = bins_T.shape[1]
    rs = np.random.RandomState(2)
    lid = rs.randint(0, 4, N).astype(np.int32)
    leaf = jnp.asarray(lid).reshape(1, -1)
    bins_np = np.asarray(bins)
    group_of = np.asarray(routing.feat_group)
    w_T = (jnp.zeros((8, N), jnp.float32).at[0].set(gi).at[1].set(hi)
           .at[2].set(1.0))
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)
    for k in (1, 2):
        want_leaf, slot = lid.copy(), np.full(N, -1, np.int64)
        for s, (at, feat, thr, new, left) in enumerate(splits[:k]):
            right = (lid == at) & (bins_np[:, group_of[feat]] > thr)
            want_leaf[right] = new
            slot[(lid == at) & (right != left)] = s
        tabs = round_tables(splits[:k], routing, L)
        full = route_and_hist(bins_T, leaf, w_T, tabs, bits, 64, Bmax, G, L,
                              **kw)
        live = route_and_hist_live(jnp.int32(k), bins_T, leaf, w_T, tabs,
                                   bits, 64, Bmax, G, L, **kw)
        check(f"{tag} small-slot pass, k={k}: leaf ids, int32 histograms and "
              "slot counts == the 64-slot pass's exactly",
              all(a.shape == b.shape and a.dtype == b.dtype
                  and np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(full, live)))
        plain = reference_hist.histograms(
            bins_np, slot, np.asarray(gi, np.int64), np.asarray(hi, np.int64),
            k, Bmax)
        check(f"{tag} small-slot pass, k={k}: rows routed as NumPy routes "
              "them, histogram == NumPy reference exactly",
              np.array_equal(np.asarray(live[0][0]), want_leaf)
              and np.array_equal(np.asarray(live[1][:k], np.int64),
                                 plain[..., :2])
              and not np.asarray(live[1][k:]).any()
              and np.array_equal(np.asarray(live[2][:k]),
                                 plain[:, 0, :, 2].sum(1)),
              f"{int((slot >= 0).sum())} of {N} rows in a slot")


def sampled_kernel_exactness(dd, params):
    """What a sampled tree (GOSS / bagging with row compaction) adds to the
    kernels' work, on the chip at G = 28 and the cell's block: (1) a 64-slot
    histogram pass over the COMPACT view (ops/compact.py: the in-bag rows
    streamed to the front of a fixed capacity by pallas/compact_kernel.py,
    itself held to `jnp.take` by the stable permutation bit for bit) against
    NumPy alone over the in-bag rows (reference_hist: np.add.at, int64),
    tolerance 0; (2)
    `route_replay`'s leaf ids over every row against the chain of per-round
    route-only passes it fuses, and against NumPy's routing."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.compact import (compact_transposed_view,
                                          plan_sample_rows)
    from lightgbm_tpu.pallas.compact_kernel import compact_rows
    from lightgbm_tpu.pallas.stream_kernel import (NUM_TAB, pack_bins_T,
                                                   route_and_hist,
                                                   route_replay,
                                                   stream_block_rows)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    import reference_hist
    G, Bmax, L = dd.num_groups, dd.max_bins, params["num_leaves"]
    T = stream_block_rows(Bmax, G, True)
    N, capacity = 16 * T, 6 * T
    bins = dd.bins[:N]
    bins_np = np.asarray(bins)
    group_of = np.asarray(dd.routing.feat_group)
    rs = np.random.RandomState(4)
    mask = rs.rand(N) < 0.30                     # goss 0.2 / 0.1 keeps 30%
    gi = rs.randint(-32, 33, N) * mask
    hi = rs.randint(0, 33, N) * mask
    bins_T = pack_bins_T(bins, T, max_bins=Bmax).bins_T
    w_T = (jnp.zeros((8, N), jnp.float32)
           .at[0].set(jnp.asarray(gi, jnp.float32))
           .at[1].set(jnp.asarray(hi, jnp.float32))
           .at[2].set(jnp.asarray(mask, jnp.float32)))
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)
    kw = dict(block_rows=T, has_cat=False, int_weights=True)

    # (1) one round over the compact view: rows sit in leaves 0..3, three
    # of which split; the histogram slots hold the smaller children
    splits = [(0, 3, 24, 4, True), (2, 17, 40, 5, False), (3, 9, 12, 6, True)]
    lid = rs.randint(0, 4, N).astype(np.int32)
    plan = plan_sample_rows(w_T[2], capacity)
    check("sampled: the partition keeps every in-bag row, in order",
          int(plan.nc) == int(mask.sum()) <= capacity
          and np.array_equal(np.asarray(plan.perm[:int(plan.nc)]),
                             np.nonzero(mask)[0]),
          f"{int(plan.nc)} in-bag of {N}, capacity {capacity}")
    bins_T_c, w_T_c = compact_transposed_view(bins_T, w_T, 2, capacity, T)
    nc, perm = int(plan.nc), np.asarray(plan.perm)
    check("sampled: compact_rows lowers to a Mosaic tpu_custom_call",
          "tpu_custom_call" in compact_rows.lower(
              bins_T, w_T, mask_row=2, capacity=capacity,
              block_rows=T).as_text())
    # float weights no sum could vouch for, through the same kernel: the
    # view is the in-bag columns' own bits, and zero behind them
    def raw(a):
        return np.ascontiguousarray(a).view(np.uint8)

    scale = np.array([[1e-40], [1e30]] * 4)     # subnormal and huge rows
    w_f = jnp.asarray((rs.standard_normal((8, N)) * scale * mask).astype(
        np.float32)).at[2].set(w_T[2])
    bins_T_f, w_f_c = compact_transposed_view(bins_T, w_f, 2, capacity, T)
    check("sampled: the streamed compact view == jnp.take by the stable "
          "permutation bit for bit (int8 bins, integer and float32 weight "
          "rows), zero past the in-bag rows",
          all(np.array_equal(raw(np.asarray(got)[:, :nc]),
                             raw(np.asarray(src)[:, perm[:nc]]))
              and not raw(np.asarray(got)[:, nc:]).any()
              for got, src in ((bins_T_c, bins_T), (w_T_c, w_T),
                               (bins_T_f, bins_T), (w_f_c, w_f))),
          f"{nc} columns of {capacity}")
    lid_c = jnp.asarray(lid)[plan.perm].reshape(1, -1)
    new_c, hist, scnt = route_and_hist(bins_T_c, lid_c, w_T_c,
                                       round_tables(splits, dd.routing, L),
                                       bits, 64, Bmax, G, L, **kw)
    want_leaf = reference_hist.route(
        bins_np[:, group_of], lid,
        {at: (feat, thr, new) for at, feat, thr, new, _ in splits})
    slot = np.full(N, -1, np.int64)
    for s, (at, feat, thr, new, left) in enumerate(splits):
        right = (lid == at) & (bins_np[:, group_of[feat]] > thr)
        slot[(lid == at) & (right != left)] = s
    slot[~mask] = -1                              # the in-bag rows alone
    plain = reference_hist.histograms(bins_np, slot, gi.astype(np.int64),
                                      hi.astype(np.int64), len(splits), Bmax)
    k = len(splits)
    check("sampled: 64-slot pass over the compact view == NumPy reference "
          "over the in-bag rows exactly (histogram, slot counts, leaf ids)",
          hist.dtype == jnp.int32
          and np.array_equal(np.asarray(hist[:k], np.int64), plain[..., :2])
          and not np.asarray(hist[k:]).any()
          and np.array_equal(np.asarray(scnt[:k]), plain[:, 0, :, 2].sum(1))
          and np.array_equal(np.asarray(new_c[0])[:nc], want_leaf[perm[:nc]]),
          f"{int((slot >= 0).sum())} of {int(mask.sum())} in-bag rows in a "
          "slot")

    # (2) three rounds of tables replayed over every row in one launch
    rounds = [[(0, 3, 24, 1, True)],
              [(0, 17, 40, 2, True), (1, 9, 12, 3, False)],
              [(2, 5, 30, 4, True), (3, 21, 8, 5, True), (1, 0, 33, 6, False)]]
    R = L + 10                                    # the grower's buffer
    tabs_buf = jnp.zeros((R * NUM_TAB, L), jnp.float32)
    chain = jnp.zeros((1, N), jnp.int32)
    want = np.zeros(N, np.int64)
    for r, sp in enumerate(rounds):
        tb = round_tables(sp, dd.routing, L)
        tabs_buf = tabs_buf.at[r * NUM_TAB:(r + 1) * NUM_TAB].set(tb)
        chain, _, _ = route_and_hist(bins_T, chain, w_T, tb, bits, 64, Bmax,
                                     G, L, with_hist=False, **kw)
        want = reference_hist.route(
            bins_np[:, group_of], want,
            {at: (feat, thr, new) for at, feat, thr, new, _ in sp})
    lowered = route_replay.lower(bins_T, tabs_buf, jnp.int32(len(rounds)), L,
                                 block_rows=T, rounds_buf=R).as_text()
    check("sampled: route_replay lowers to a Mosaic tpu_custom_call",
          "tpu_custom_call" in lowered)
    replayed = route_replay(bins_T, tabs_buf, jnp.int32(len(rounds)), L,
                            block_rows=T, rounds_buf=R)
    check("sampled: route_replay's leaf ids == the per-round route-only "
          "chain's == NumPy's routing, every row",
          np.array_equal(np.asarray(replayed), np.asarray(chain[0]))
          and np.array_equal(np.asarray(replayed), want),
          f"leaves reached {sorted(set(want.tolist()))}")


def wide_kernel_exactness():
    """The same on a table too wide for one M-tile, at the benchmark's wide
    cell's tile shape (2,000 groups of 63 bins: sixteen tiles of 128 groups,
    1024-row blocks): the route-only pass, the tiles' sweeps and the tiled
    factored root against NumPy routing and _hist_segsum, exactly, with this
    round's splits testing features of every tile."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.histogram import _hist_segsum
    from lightgbm_tpu.pallas.stream_kernel import (build_route_tables,
                                                   pack_bins_T,
                                                   route_and_hist,
                                                   stream_tiling)
    G, Bmax, L, S = 2000, 63, 255, 64
    plan = stream_tiling(Bmax, G, True)
    check("wide: 2,000 groups go sixteen 128-group tiles at T=1024",
          tuple(plan) == (1024, 128, 16, 128 * 64), str(plan))
    T, N = plan.block_rows, 8 * plan.block_rows
    rs = np.random.RandomState(1)
    ds = lgb.Dataset(rs.randn(N, G).astype(np.float32),
                     label=(rs.rand(N) < 0.5).astype(np.float32),
                     params={"max_bin": Bmax, "verbosity": -1})
    ds.construct()
    dd = ds.device_data()
    bins = dd.bins[:N]
    check("wide: one group a column, 63 bins",
          (dd.num_groups, dd.max_bins) == (G, Bmax),
          f"G={dd.num_groups} Bmax={dd.max_bins}")
    gi = jnp.asarray(rs.randint(-32, 33, N).astype(np.float32))
    hi = jnp.asarray(rs.randint(0, 33, N).astype(np.float32))
    cnt = jnp.ones(N, jnp.float32)
    bins_T = pack_bins_T(bins, T, max_bins=Bmax,
                         tile_groups=plan.tile_groups).bins_T
    check("wide: u8 bins packed to whole tiles",
          bins_T.dtype == jnp.int8 and bins_T.shape == (2048, N),
          f"{bins_T.dtype} {bins_T.shape}")
    w_T = (jnp.zeros((8, N), jnp.float32).at[0].set(gi).at[1].set(hi)
           .at[2].set(cnt))
    bits = jnp.zeros((64, L), jnp.bfloat16)
    kw = dict(block_rows=T, has_cat=False, int_weights=True,
              tile_groups=plan.tile_groups)
    # rows over 32 leaves, every one split on a feature of its own (two a
    # tile, the last in the ragged one): left rows keep leaf l in slot l,
    # right rows move to leaf 32 + l in slot 32 + l
    half = np.arange(L) < 32
    feat = np.where(half, (np.arange(L) * 64 + 15) % G, 0).astype(np.int32)
    thr = np.where(half, 20 + np.arange(L) % 24, 0).astype(np.int32)
    ids = np.arange(L, dtype=np.int32)
    cols = (half, feat, thr, 0 * ids, np.where(half, 32 + ids, 0),
            np.where(half, ids + 1, 0), np.where(half, 33 + ids, 0), 0 * ids)
    tabs = build_route_tables(*(jnp.asarray(c, jnp.int32) for c in cols),
                              dd.routing, L)
    lid = rs.randint(0, 32, N).astype(np.int32)
    group_of = np.asarray(dd.routing.feat_group)
    fb = np.asarray(bins)[np.arange(N), group_of[feat[lid]]]
    want_leaf = np.where(fb <= thr[lid], lid, 32 + lid)
    leaf = jnp.asarray(lid).reshape(1, -1)
    new_leaf, hist, scnt = route_and_hist(bins_T, leaf, w_T, tabs, bits, S,
                                          Bmax, G, L, **kw)
    ref = _hist_segsum(bins, jnp.asarray(want_leaf), gi, hi, cnt, S, Bmax)
    check("wide 64-slot pass: rows routed as NumPy routes them",
          np.array_equal(np.asarray(new_leaf[0]), want_leaf))
    check("wide 64-slot pass: int32 histogram == _hist_segsum exactly",
          hist.dtype == jnp.int32 and np.array_equal(
              np.asarray(hist, np.float64),
              np.asarray(ref[..., :2], np.float64)))
    # and against NumPy alone (np.add.at, int64), which shares no code with
    # the program: leaf ids below 64 are this round's slots
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    import reference_hist
    plain = reference_hist.histograms(
        np.asarray(bins), want_leaf, np.asarray(gi, np.int64),
        np.asarray(hi, np.int64), S, Bmax)
    check("wide 64-slot pass: int32 histogram == NumPy reference exactly",
          np.array_equal(np.asarray(hist, np.int64), plain[..., :2]))
    check("wide 64-slot pass: slot counts exact",
          np.array_equal(np.asarray(scnt),
                         np.bincount(want_leaf, minlength=S)))
    only_leaf, _, only_cnt = route_and_hist(bins_T, leaf, w_T, tabs, bits, S,
                                            Bmax, G, L, with_hist=False, **kw)
    check("wide route-only pass: the same leaf ids and counts",
          np.array_equal(np.asarray(only_leaf), np.asarray(new_leaf))
          and np.array_equal(np.asarray(only_cnt), np.asarray(scnt)))
    _, fact, _ = route_and_hist(bins_T, jnp.zeros((1, N), jnp.int32), w_T,
                                tabs, bits, 1, Bmax, G, L, root=True, **kw)
    ref = _hist_segsum(bins, jnp.zeros(N, jnp.int32), gi, hi, cnt, 1, Bmax)
    check("wide factored root: int32 histogram == _hist_segsum exactly",
          fact.dtype == jnp.int32 and np.array_equal(
              np.asarray(fact, np.float64),
              np.asarray(ref[..., :2], np.float64)))
    plain = reference_hist.histograms(
        np.asarray(bins), np.zeros(N, np.int64), np.asarray(gi, np.int64),
        np.asarray(hi, np.int64), 1, Bmax)
    # tolerance 0: the sums are integers, so a path of lower precision than
    # the int8 x int8 -> int32 contraction fails this by construction
    check("wide factored root: int32 histogram == NumPy reference exactly",
          np.array_equal(np.asarray(fact, np.int64), plain[..., :2]))
    # the rounds of one and two splits, on features of the fourth, the
    # eleventh and the last (ragged) tile: a lost tile shows here
    small_pass_exactness("wide", bins, bins_T, dd.routing, gi, hi, kw, G,
                         Bmax, L, [(0, 1300, 30, 4, True),
                                   (2, 1999, 25, 5, False)])
    small_pass_exactness("wide, first tiles", bins, bins_T, dd.routing, gi,
                         hi, kw, G, Bmax, L, [(1, 400, 35, 6, False),
                                              (3, 5, 28, 7, True)])
    tail_width_identity(ds, N)


def tail_width_identity(ds, n_rows):
    """The rounds' tails on the chip at the wide shape: one 41-leaf tree
    (a budget of 40 splits a round) whose rounds subtract and scan a chunk
    of tail_chunk(40) = 8 pairs a step (splits of 1, 2, 4, 8, 16 and 9: one
    step, and two), against the same tree with every round's tail 40 pairs
    wide at once (tail_chunk patched here, not an option of the program):
    model text, every row's leaf and the pass count, tolerance 0."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops import grow
    params = {"objective": "binary", "num_leaves": 41, "max_bin": 63,
              "verbosity": -1, "use_quantized_grad": True,
              "num_grad_quant_bins": 64, "stochastic_rounding": False,
              "eval_fetch_freq": 1}
    real, grown = grow.tail_chunk, {}
    check("wide tails: a budget of 40 goes 8 pairs a step", real(40) == 8)
    for name, chunk in (("chunked", real), ("budget", lambda b: 0)):
        before = telemetry.scan_slot_count()
        grow.tail_chunk = chunk
        try:
            bst = lgb.Booster(params, ds)
            bst.update()
        finally:
            grow.tail_chunk = real
        poll = telemetry.recent_spans(name="GBDT::FlagPoll")[-1].args
        grown[name] = (bst.model_to_string().split("\nparameters:")[0],
                       np.asarray(bst.engine._train_state.leaf_id)[:n_rows],
                       bst.engine._train_state.hist_passes.item(),
                       poll["scan_slots"] - before)
    (text, leaf, passes, slots), budget = grown["chunked"], grown["budget"]
    check("wide tails: the tree, every row's leaf and the pass count are "
          "the 40-pair tails' exactly",
          text == budget[0] and np.array_equal(leaf, budget[1])
          and passes == budget[2], f"{passes} passes")
    check("wide tails: the rounds took whole chunks of 8, fewer than 40",
          slots % 8 == 0 and slots < budget[3] == 40 * (passes - 1),
          f"scan_slots {slots} against {budget[3]}")


DIGEST_ROWS = {"higgs_like": 2_000_000, "mslr_like": 600_000,
               "epsilon_like": 60_000}
DIGEST_TREES = 33              # polls at trees 16 and 32, and the last


def digest():
    """The identity run (the module docstring): no check of its own, the
    caller compares two checkouts' last lines."""
    import hashlib
    import importlib.util
    here = os.getcwd()
    sys.path.insert(0, here)
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    if jax.devices()[0].platform != "tpu":
        print("chip_smoke --digest: needs a TPU", file=sys.stderr)
        return 2
    out = {"program": os.path.dirname(os.path.abspath(lgb.__file__))}
    for name, rows in DIGEST_ROWS.items():
        bench_dir = os.path.join(here, "benchmark")
        with open(os.path.join(bench_dir, "configs", f"{name}.json")) as f:
            config = json.load(f)
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(bench_dir, "generators",
                               f"{config['generator']}.py"))
        gen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gen)
        data = gen.make(3000003100, rows, config["shape"])
        params = dict(config["params"], verbosity=-1)
        telemetry.reset_counters()
        since = time.time_ns()
        bst = lgb.train(params, lgb.Dataset(data["X"], label=data["y"],
                                            group=data.get("sizes")),
                        num_boost_round=DIGEST_TREES)
        text = bst.model_to_string().split("\nparameters:")[0]
        polls = [(r.args["iteration"], r.args["hist_passes"])
                 for r in telemetry.recent_spans(name="GBDT::FlagPoll",
                                                 since_unix_ns=since)
                 if r.args and "hist_passes" in r.args]
        out[name] = {"rows": rows, "trees": bst.num_trees(),
                     "sha256": hashlib.sha256(text.encode()).hexdigest(),
                     "polls": polls,
                     "small_passes": getattr(
                         telemetry, "hist_small_pass_count", lambda: None)()}
        say(f"{name}: {out[name]}")
        del bst, data
        gc.collect()
    print(json.dumps(out))
    return 0


def main():
    argv = sys.argv[1:]
    if "--digest" in argv:
        return digest()
    try:
        import jax
    except ImportError as e:
        print(f"chip_smoke: cannot import jax: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device['platform']!r} ({device['kind']} x {device['count']})",
              file=sys.stderr)
        return 2
    if "--mesh" in argv:
        given = argv[argv.index("--mesh") + 1:]
        return run_mesh(device, devs, given[0] if given else MESH_CONFIG)
    return run(device, devs)


def finish(device):
    if FAILED:
        print("chip_smoke FAILED: " + "; ".join(FAILED), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def run_mesh(device, devs, config):
    """The mesh leg alone, its memory checks on peaks (nothing else has
    touched the devices)."""
    clock = CompileClock()
    if device["count"] < 4:
        print(f"chip_smoke --mesh: needs 4 chips; JAX found "
              f"{device['count']}", file=sys.stderr)
        return 2
    from lightgbm_tpu import runtime
    runtime.configure_compile_cache()
    with phase(f"mesh: {config} over {device['count']} devices"):
        mesh_leg(config, devs, alone=True)
    clock.report()
    say(f"total wall {time.time() - _T0:.1f} s")
    return finish(device)


def run(device, devs):
    import jax
    clock = CompileClock()

    import jaxlib
    from importlib import metadata

    import bench
    import lightgbm_tpu as lgb
    from lightgbm_tpu import native, runtime, telemetry
    from lightgbm_tpu.telemetry import costmodel

    cache_dir = runtime.configure_compile_cache()
    say(f"device: {device}")
    say(f"versions: python {sys.version.split()[0]} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {metadata.version('libtpu')} "
        f"numpy {np.__version__}")
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} "
        f"({'from ' + runtime.CACHE_ENV if os.environ.get(runtime.CACHE_ENV) else 'checkout default'}; "
        f"{n_cached} entries at start)")
    check("native binner loaded (no silent NumPy fallback)",
          native.get_lib() is not None)
    bal = costmodel.machine_balance()
    check("device_kind has published peaks incl. int8",
          bal["peak_int8_ops_per_s"], f"{bal}")
    check("runtime: on_tpu and Pallas compiled, not interpreted",
          runtime.on_tpu() and not runtime.pallas_interpret())

    # ---------------------------------------------------------------- data
    params = {
        "objective": "binary", "num_leaves": bench.NUM_LEAVES,
        "learning_rate": 0.1, "max_bin": 63, "verbosity": -1,
        "use_quantized_grad": True, "num_grad_quant_bins": 64,
    }
    with phase(f"data: HIGGS-like {ROWS}+{HOLDOUT} x {bench.N_FEATURES}"):
        X, y = bench.make_higgs_like(ROWS + HOLDOUT, bench.N_FEATURES)
        X_tr, y_tr, X_te, y_te = X[:ROWS], y[:ROWS], X[ROWS:], y[ROWS:]
        ds = lgb.Dataset(X_tr, label=y_tr)

    # --------------------------------------------------------------- train
    bst = None
    with phase(f"train: lgb.train, {ITERS} iterations"):
        after_warmup = {}
        stamps = []

        def watch(env):
            stamps.append(time.time())
            if env.iteration == 0:       # the warm-up iteration just ended
                after_warmup.update(telemetry.recompile_counts())

        t0 = time.time()
        trained = lgb.train(params, ds, num_boost_round=ITERS,
                            callbacks=[watch])
        trained.engine.score.block_until_ready()
        say(f"lgb.train wall {time.time() - t0:.1f} s; iteration ends at "
            + " ".join(f"{s - t0:.1f}" for s in stamps))
        eng = trained.engine
        gp = eng._grow_params
        check("engine resolved hist_backend=stream",
              gp.hist_backend == "stream", gp.hist_backend)
        check("engine resolved int8 histograms (int_hist)", gp.int_hist)
        check("iterations ran fused (one launch, no host sync)",
              eng._can_fuse_iteration() and eng._fused_last)
        check("max_splits_per_round resolved to 64",
              gp.max_splits_per_round == 64, str(gp.max_splits_per_round))
        now = telemetry.recompile_counts()
        grew = {k: (after_warmup.get(k, 0), v) for k, v in now.items()
                if v != after_warmup.get(k, 0)}
        check("zero recompiles after the warm-up iteration", not grew,
              f"{grew}" if grew else f"{sum(now.values())} traces, all in "
              "warm-up")
        check_trees(trained, ROWS, bench.NUM_LEAVES, "train")
        bst = trained

    if bst is not None:
        with phase("kernel: route_and_hist vs _hist_segsum at the cell's "
                   "block shape"):
            kernel_exactness(bst.engine.dd, params)
        with phase("kernel: a sampled tree's compact pass and route replay "
                   "(G = 28)"):
            sampled_kernel_exactness(bst.engine.dd, params)
        with phase("kernel: the same over sixteen M-tiles (G = 2,000)"):
            wide_kernel_exactness()

        # ----------------------------------------------------------- predict
        ref = None
        with phase(f"predict: Booster.predict on {HOLDOUT} held-out rows"):
            raw = bst.predict(X_te, raw_score=True)
            check("predict took the device path (predict_stream)",
                  bst.last_predict_path == "device", bst.last_predict_path)
            ref = lgb.Booster(model_str=bst.model_to_string())
            host = ref.predict(X_te, raw_score=True)
            check("host walk of the reloaded model is the host path",
                  ref.last_predict_path.startswith("host"),
                  ref.last_predict_path)
            check("device predict == host walk (rtol 1e-4, atol 1e-5)",
                  raw.shape == host.shape and np.all(np.isfinite(raw))
                  and np.allclose(raw, host, rtol=1e-4, atol=1e-5),
                  f"max abs diff {np.max(np.abs(raw - host)):.3g}")
            auc_last = bench.auc_score(y_te, raw)
            auc_first = bench.auc_score(
                y_te, bst.predict(X_te, raw_score=True, num_iteration=1))
            check("holdout AUC after the last iteration above the first",
                  auc_last > auc_first > 0.5,
                  f"first {auc_first:.4f} -> last {auc_last:.4f}")

        # ----------------------------------------------------------- serving
        with phase("serve: in-process ServingApp, binary wire"):
            serve_requests(bst, ref, X_te)

    # ---------------------------------------------------------------- mesh
    if device["count"] >= 4 and bst is not None:
        del bst, trained
        gc.collect()
        del X_tr, y_tr, X_te, y_te
        gc.collect()
        with phase(f"mesh: {MESH_CONFIG} over {device['count']} devices"):
            mesh_leg(MESH_CONFIG, devs)
    else:
        say(f"mesh leg skipped: {device['count']} device(s) visible, "
            "needs >= 4")

    # -------------------------------------------------------------- memory
    stats = devs[0].memory_stats() or {}
    check("memory_stats reports peak_bytes_in_use",
          stats.get("peak_bytes_in_use"),
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_in_use={stats.get('bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    clock.report()
    say(f"total wall {time.time() - _T0:.1f} s")

    return finish(device)


def serve_requests(bst, ref, X_te):
    import tempfile

    from lightgbm_tpu import telemetry
    from lightgbm_tpu.serving import BinaryClient, ServingApp
    from lightgbm_tpu.serving.compiled import device_accumulation_supported

    say(f"device_accum (f64 probe on this backend): "
        f"{device_accumulation_supported()}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        path = os.path.join(td, "model.txt")
        bst.save_model(path)
        app = ServingApp(path, port=0, max_batch=256, max_delay_ms=2.0,
                         queue_size=4096, binary_port=0).start()
        try:
            model = app.registry.current()
            ladder = model.describe()["buckets"]
            compiled = model._compiled
            if check("serving model compiled for the device (no host "
                     "predictor fallback)", compiled is not None):
                say(f"serving predictor: device_accum="
                    f"{compiled.device_accum} buckets={ladder}")
            exact = True
            with BinaryClient(app.host, app.binary_port) as c:
                for sweep in range(2):
                    if sweep == 1:
                        warm = telemetry.recompile_counts()
                    for m in ladder:
                        resp = c.request(X_te[:m], raw_score=True)
                        got = np.asarray(resp["predictions"])
                        want = ref.predict(X_te[:m], raw_score=True)
                        ok = resp["status"] == 0 and np.array_equal(got, want)
                        if not ok:
                            say(f"   bucket {m}: status {resp['status']}, "
                                f"max abs diff "
                                f"{np.max(np.abs(got - want)):.3g}")
                        exact &= ok
            check("ServingApp responses bitwise == Booster.predict(raw_score)"
                  f" on buckets {ladder}", exact)
            now = telemetry.recompile_counts()
            grew = {k: v for k, v in now.items() if v != warm.get(k, 0)}
            check("serving: zero recompiles on the second sweep", not grew,
                  f"{grew}")
        finally:
            app.shutdown()


def mesh_leg(config, devs, alone=False):
    """tree_learner=data over every device, at the shape `config` (a
    benchmark configuration file) states: its generator, width and
    parameters, CHIP_SMOKE_ROWS rows.  `alone`: nothing ran on the devices
    before, so their PEAKS are this leg's and are held to the balance too."""
    import importlib.util
    import jax

    import bench
    import lightgbm_tpu as lgb

    cfg = json.loads(open(config).read())
    spec = importlib.util.spec_from_file_location(
        "generator", os.path.join(os.path.dirname(config), "..", "generators",
                                  cfg["generator"] + ".py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    data = gen.make(1, ROWS + HOLDOUT, cfg["shape"])
    X_tr, y_tr = data["X"][:ROWS], data["y"][:ROWS]
    X_te, y_te = data["X"][ROWS:], data["y"][ROWS:]
    params = dict(cfg["params"], verbosity=-1)
    say(f"mesh: {cfg['name']}: {ROWS} x {X_tr.shape[1]} rows, {params}")

    def stat(key):
        return [(d.memory_stats() or {}).get(key, 0) for d in devs]

    def strip(model_str):
        return model_str.split("\nparameters:")[0]

    def mib(values):
        return [round(v / 2 ** 20, 1) for v in values]

    base = stat("bytes_in_use")
    models = {}
    for mode in ("psum", "reduce_scatter"):
        p = dict(params, tree_learner="data", hist_comms=mode)
        bst = lgb.train(p, lgb.Dataset(X_tr, label=y_tr), num_boost_round=3)
        jax.block_until_ready(bst.engine.score)
        eng = bst.engine
        if mode == "psum":
            check("mesh: stream kernel under shard_map (_mesh_stream)",
                  eng._mesh_stream and eng._grow_params.hist_backend == "stream")
            check("mesh: takes every device",
                  eng.mesh is not None and eng.mesh.devices.size == len(devs),
                  f"{None if eng.mesh is None else eng.mesh.shape}")
            span = {d.id for d in eng.dd.bins.sharding.device_set}
            check("mesh: bins' sharding spans all devices",
                  span == {d.id for d in devs}
                  and not eng.dd.bins.sharding.is_fully_replicated,
                  f"{eng.dd.bins.sharding}")
            delta = [b - a for a, b in zip(base, stat("bytes_in_use"))]
            # the table goes a shard to a device and the label with it: what
            # is left to device 0 alone is small change (layouts, masks)
            check("mesh: per-device bytes_in_use balanced (none piled on "
                  "device 0)", min(delta) > 0
                  and max(delta) <= 1.1 * min(delta), f"delta MiB {mib(delta)}")
            if alone:
                # no device-0 transient either: a table shipped through one
                # chip first reads 4x its shard there (PR 22: the pad too)
                peak = stat("peak_bytes_in_use")
                check("mesh: per-device peak_bytes_in_use balanced (no "
                      "table through device 0)", min(peak) > 0
                      and max(peak) <= 1.1 * min(peak), f"peak MiB {mib(peak)}")
            check_trees(bst, len(y_tr), params["num_leaves"], "mesh")
            mesh_kernel_exactness(eng)
            auc = bench.auc_score(y_te, bst.predict(X_te, raw_score=True))
            check("mesh: holdout predict took the device path",
                  bst.last_predict_path == "device", bst.last_predict_path)
            check("mesh: holdout AUC sane", auc > 0.6, f"{auc:.4f}")
        models[mode] = strip(bst.model_to_string())
        del bst, eng
        gc.collect()
    check("mesh: psum and reduce_scatter grow byte-identical models "
          "(tests/test_distributed_fast.py)",
          models["psum"] == models["reduce_scatter"])
    serial = lgb.train(params, lgb.Dataset(X_tr, label=y_tr),
                       num_boost_round=3)
    say("mesh: serial vs data-parallel models byte-identical: "
        f"{strip(serial.model_to_string()) == models['psum']} "
        "(reported, not required)")


def mesh_kernel_exactness(eng):
    """One root pass and one 64-slot pass of the per-device kernel over the
    engine's own sharded table, reduced over the mesh by each collective
    (the grower's `psum`, comms.reduce_hist), against
    benchmark/reference_hist.py on the WHOLE table (int64 NumPy;
    `_hist_segsum` sums in float32 and is itself inexact past 2^24 a bin:
    the root's zero bins at 4M rows): integers, so at tolerance 0.  A shard that the reduction lost, or a
    collective that dropped a group slice, cannot pass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    import reference_hist
    from lightgbm_tpu.pallas.stream_kernel import (build_route_tables,
                                                   route_and_hist)
    from lightgbm_tpu.parallel.comms import (make_rs_context,
                                             reduce_hist_rows)
    from lightgbm_tpu.parallel.mesh import shard_map_rows
    from lightgbm_tpu.telemetry import watched_jit
    dd, mesh, ax = eng.dd, eng.mesh, eng._row_axis
    G, Bmax, L = dd.num_groups, dd.max_bins, eng._grow_params.num_leaves
    T, N, S = eng._pack_block, dd.bins.shape[0], 64
    say(f"mesh kernel: G={G} Bmax={Bmax} L={L} T={T}, "
        f"{N // mesh.devices.size} rows a shard, bins_T {eng._packed.shape} "
        f"{eng._packed.dtype}")
    rs = np.random.RandomState(0)
    live = np.arange(N) < eng.num_data
    gi = rs.randint(-32, 33, N) * live
    hi = rs.randint(0, 33, N) * live
    lid = rs.randint(0, S, N)
    w_T = np.zeros((8, N), np.float32)
    w_T[0], w_T[1], w_T[2] = gi, hi, live
    cols = NamedSharding(mesh, P(None, ax))
    w_T = jax.device_put(w_T, cols)
    zL = jnp.zeros(L, jnp.int32)
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)
    keep = jnp.where(jnp.arange(L) < S, jnp.arange(L) + 1, 0).astype(jnp.int32)
    plan = make_rs_context(mesh, ax, dd.layout, dd.routing, G, Bmax,
                           eng._grow_params)[0]

    def reduced(slots, leaf, tabs, root, scatter, limbs=1):
        def local(bT, lf, wT, tb, bi):
            _, h, c = route_and_hist(bT, lf, wT, tb, bi, slots, Bmax, G, L,
                                     block_rows=T, has_cat=False,
                                     int_weights=True, root=root)
            # the grower's own collective, and its int32 count psum
            h = reduce_hist_rows(h, ax, 1, plan if scatter else None,
                                 limbs=limbs)
            return h, jax.lax.psum(c.astype(jnp.int32), ax)
        out = P(None, ax, None, None) if scatter else P()
        fn = watched_jit(shard_map_rows(
            local, mesh, (P(None, ax),) * 3 + (P(None, None),) * 2,
            (out, P())), name="chip_smoke_mesh_pass", warn_after=0)
        h, c = fn(eng._packed, jax.device_put(leaf.reshape(1, -1), cols),
                  w_T, tabs, bits)
        return np.asarray(h)[:, :G], np.asarray(c)

    table = np.asarray(dd.bins)
    for tag, slots, leaf, keep_tab, root in (
            ("root pass", 1, np.zeros(N, np.int32), zL.at[0].set(1), True),
            ("64-slot pass", S, lid.astype(np.int32), keep, False)):
        tabs = build_route_tables(zL, zL, zL, zL, zL, zL, zL, keep_tab,
                                  dd.routing, L)
        at = np.where(live, leaf, -1)
        ref = reference_hist.histograms(table, at, gi, hi, slots, Bmax)
        got = {}
        for name, scatter in (("psum", False), ("reduce_scatter", True)):
            h, c = got[name] = reduced(slots, leaf, tabs, root, scatter)
            check(f"mesh {tag}, {name}: reduced int32 histogram == the "
                  "whole table's, exactly",
                  h.dtype == np.int32 and np.array_equal(h, ref[..., :2]))
            check(f"mesh {tag}, {name}: reduced slot counts exact (int32)",
                  c.dtype == np.int32 and np.array_equal(
                      c.reshape(-1)[:slots], ref[:, 0, :, 2].sum(axis=-1)),
                  f"{c.reshape(-1)[:4]}")
        check(f"mesh {tag}: psum and reduce_scatter byte-identical",
              got["psum"][0].tobytes() == got["reduce_scatter"][0].tobytes())
        h2 = reduced(slots, leaf, tabs, root, False, limbs=2)[0]
        check(f"mesh {tag}: the two-limb reduce is float32 of the whole "
              "table's sums", h2.dtype == np.float32 and np.array_equal(
                  h2, ref[..., :2].astype(np.float32)))


if __name__ == "__main__":
    sys.exit(main())

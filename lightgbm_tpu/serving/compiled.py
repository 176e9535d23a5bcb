"""Shape-bucketed compiled predictor: one traced XLA program per bucket.

Serving traffic arrives in arbitrary batch sizes; tracing a fresh XLA
program per size would turn every odd-shaped request into a multi-second
compile stall (the exact failure mode the telemetry recompile watchdog
exists to catch).  Incoming batches are therefore padded to a fixed
ladder of row-count buckets (powers of two by default, capped at
``serve_max_batch``) so after one warmup pass every dispatch hits an
already-compiled program — Clipper-style (Crankshaw et al., NSDI 2017)
"compile once per shape, amortize forever".

Bit-identity with ``Booster.predict`` is non-negotiable for serving (a
hot-reload A/B must never change scores), but the device is float32 and
model thresholds are float64.  The walk therefore never compares floats
on device: each float64 value ``v`` is mapped on the host to a MONOTONE
64-bit integer key (sign-flip trick: ``bits ^ (bits < 0 ? ~0 : 1<<63)``,
with -0.0 normalized to +0.0) carried as two uint32 lanes, and ``v <=
threshold`` becomes an exact lexicographic integer compare.

Score accumulation ALSO runs on device: the per-tree leaf-value table
rides into the program as a float64 argument (under a scoped
``jax.enable_x64``), and one sequential ``fori_loop``
replays the host batch loop's exact tree order — per row, the same
IEEE-754 float64 adds in the same order — so the returned scores are
bitwise equal to ``Booster.predict`` without the host ever touching a
per-tree Python loop (the pre-PR-13 hot path burned ~40% of serving CPU
there).  Backends without real float64 (probed once at import of the
first predictor; ``LGBTPU_SERVE_ACCUM=host`` forces it) keep the old
host-side float64 accumulation over device leaf indices — same bits,
more host work.  The only models the device path refuses entirely are
linear trees (raw-feature float64 dot products per leaf).

Missing handling mirrors tree.py ``predict_raw`` exactly: NaN rows carry
a host-computed mask; the ``zero_as_missing`` band ``|v| < 1e-35`` is an
exact key-range test; categorical values use a host-truncated int32 and
the model's category bitset words.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..utils.log import LightGBMError, log_warning

# monotone keys of +/-1e-35 — the reference's kZeroThreshold band used by
# zero-as-missing routing (tree.py predict_raw: np.abs(v) < 1e-35)
def _key64(v: np.ndarray) -> np.ndarray:
    """Float64 -> monotone uint64 key; total order matches <= on reals
    (±0 collapse to +0 first so the two zeros compare equal)."""
    v = np.ascontiguousarray(np.where(v == 0.0, 0.0, v), np.float64)
    b = v.view(np.uint64)
    return np.where(b >> np.uint64(63), ~b, b | np.uint64(1 << 63))


def _split_key(key: np.ndarray):
    return ((key >> np.uint64(32)).astype(np.uint32),
            (key & np.uint64(0xFFFFFFFF)).astype(np.uint32))


_ZLO = _split_key(_key64(np.asarray([-1e-35])))   # ([hi], [lo]) of -1e-35
_ZHI = _split_key(_key64(np.asarray([1e-35])))
_ZLO = (int(_ZLO[0][0]), int(_ZLO[1][0]))
_ZHI = (int(_ZHI[0][0]), int(_ZHI[1][0]))


class PackedServingTrees(NamedTuple):
    """Model arrays rectangularized to (T, M) for the jitted walk; passed
    as traced ARGUMENTS (not closure constants) so a hot-reloaded model of
    the same shape reuses the compiled program."""
    split_feature: object   # (T, M) i32
    thr_hi: object          # (T, M) u32 — monotone key lanes of threshold
    thr_lo: object          # (T, M) u32
    decision_type: object   # (T, M) i32 — LightGBM bits (cat/dleft/missing)
    left_child: object      # (T, M) i32
    right_child: object     # (T, M) i32
    cat_ord: object         # (T, M) i32 — row into cat_words, -1 numeric
    cat_words: object       # (C, W) u32 — per-cat-node bitset words


def _x64_scope():
    """Scoped float64 (the repo-wide pattern: models/gbdt.py _x64_scope) —
    the global x64 flag stays off; serving traces/dispatches its scored
    programs inside the scope so the f64 leaf table and accumulator are
    real IEEE doubles on capable backends."""
    import jax
    return jax.enable_x64()


_DEVICE_F64: Optional[bool] = None


def device_accumulation_supported() -> bool:
    """Can this backend hold float64 arrays and add them with IEEE-754
    semantics?  Probed ONCE: a sequential sum of sixteen full-mantissa
    doubles must come back bitwise equal to NumPy's.  (The earlier probe,
    1.0 + 1e-16, is 1.0 in IEEE double too and so passed on the TPU v5e,
    whose emulated float64 keeps ~48 mantissa bits: responses there were
    off by a few 1e-15 — chip_smoke.py, PR 22.)
    ``LGBTPU_SERVE_ACCUM=host`` forces the host-accumulation fallback;
    ``=device`` raises if the probe fails (no silent downgrade)."""
    global _DEVICE_F64
    mode = os.environ.get("LGBTPU_SERVE_ACCUM", "auto").strip().lower()
    if mode not in ("auto", "device", "host"):
        raise LightGBMError(
            f"LGBTPU_SERVE_ACCUM={mode!r} must be auto, device, or host")
    if mode == "host":
        return False
    if _DEVICE_F64 is None:
        # a backend that cannot hold or add f64 answers False here; one
        # that RAISES has a fault serving would hit again on its first
        # request, so the exception is the caller's to see
        import jax.numpy as jnp
        vals = np.random.RandomState(0).standard_normal(16)
        want = np.float64(0.0)
        for v in vals:
            want = want + v
        with _x64_scope():
            a = jnp.asarray(vals)
            ok = a.dtype == jnp.float64
            if ok:
                # eager device adds (no bare jit), in the host loop's
                # order: an emulation that drops mantissa bits on the
                # transfer or in an add fails the bit compare
                s = jnp.zeros((), jnp.float64)
                for i in range(len(vals)):
                    s = s + a[i]
                got = np.asarray(s)
                ok = (got.dtype == np.float64
                      and got.view(np.uint64) == want.view(np.uint64))
        _DEVICE_F64 = bool(ok)
        if not _DEVICE_F64:
            log_warning("serving: this backend's float64 is not IEEE-754 "
                        "(a 16-term sum differs bitwise from NumPy's); "
                        "leaf accumulation runs on the host")
    if mode == "device" and not _DEVICE_F64:
        raise LightGBMError(
            "LGBTPU_SERVE_ACCUM=device but this backend has no IEEE "
            "float64 — unset it to fall back to host accumulation")
    return _DEVICE_F64


def _walk_impl(pack: PackedServingTrees, keys_hi, keys_lo, nan_mask, iv,
               max_depth: int):
    """(T, n) leaf index per tree per row — integer ops only."""
    import jax
    import jax.numpy as jnp

    n = keys_hi.shape[0]
    W = pack.cat_words.shape[1]
    rows = jnp.arange(n)

    def lex_le(ahi, alo, bhi, blo):
        return (ahi < bhi) | ((ahi == bhi) & (alo <= blo))

    def lex_lt(ahi, alo, bhi, blo):
        return (ahi < bhi) | ((ahi == bhi) & (alo < blo))

    zlo_hi = jnp.uint32(_ZLO[0])
    zlo_lo = jnp.uint32(_ZLO[1])
    zhi_hi = jnp.uint32(_ZHI[0])
    zhi_lo = jnp.uint32(_ZHI[1])

    def one_tree(tf):
        sf, thi, tlo, dt, lc, rc, co = tf

        def step(_, node):
            active = node >= 0
            ni = jnp.maximum(node, 0)
            f = sf[ni]
            khi = keys_hi[rows, f]
            klo = keys_lo[rows, f]
            isn = nan_mask[rows, f]
            cv = iv[rows, f]
            d = dt[ni]
            is_cat = (d & 1) != 0
            def_left = (d & 2) != 0
            zero_missing = ((d >> 2) & 3) == 1
            le = lex_le(khi, klo, thi[ni], tlo[ni])
            near_zero = (lex_lt(zlo_hi, zlo_lo, khi, klo)
                         & lex_lt(khi, klo, zhi_hi, zhi_lo))
            miss = isn | (zero_missing & near_zero)
            word = cv >> 5
            row_ix = co[ni]
            cvalid = (cv >= 0) & (word < W) & (row_ix >= 0)
            w = pack.cat_words[jnp.maximum(row_ix, 0),
                               jnp.clip(word, 0, W - 1)]
            bit = (w >> (cv & 31).astype(jnp.uint32)) & jnp.uint32(1)
            gl_cat = cvalid & (bit == 1)
            go_left = jnp.where(is_cat, gl_cat,
                                jnp.where(miss, def_left, le))
            nxt = jnp.where(go_left, lc[ni], rc[ni])
            return jnp.where(active, nxt, node)

        node = jax.lax.fori_loop(0, max_depth, step, jnp.zeros(n, jnp.int32))
        # trivial/padded trees loop on node 0 forever: resolve to leaf 0,
        # matching the host path's single-leaf output (tree.py:113)
        return jnp.where(node < 0, ~node, 0)

    return jax.lax.map(one_tree, tuple(pack[:7]))


def _score_impl(pack: PackedServingTrees, leaf_values, keys_hi, keys_lo,
                nan_mask, iv, max_depth: int, num_class: int):
    """Walk + on-device float64 accumulation in the host loop's exact
    tree order (traced under enable_x64; bitwise == Booster.predict).

    ``leaf_values`` is (T, L) float64.  num_class == 1: one fori_loop
    ``score += lv[t][leaf[t]]`` — per element the identical IEEE add
    sequence as the host ``for t: score += lv[leaves[t]]`` loop.
    num_class > 1: trees iterate round-major (tree i feeds column i % k),
    so looping rounds r and adding the (k, n) gather keeps every COLUMN's
    adds in ascending tree order — again the host loop's order."""
    import jax
    import jax.numpy as jnp

    leaves = _walk_impl(pack, keys_hi, keys_lo, nan_mask, iv, max_depth)
    n = keys_hi.shape[0]
    T = leaf_values.shape[0]
    if num_class == 1:
        def body(t, s):
            return s + leaf_values[t][leaves[t]]
        return jax.lax.fori_loop(0, T, body, jnp.zeros(n, jnp.float64))
    k = num_class
    lv3 = leaf_values.reshape(T // k, k, leaf_values.shape[1])
    lf3 = leaves.reshape(T // k, k, n)

    def body(r, s):
        return s + jnp.take_along_axis(lv3[r], lf3[r], axis=1).T

    return jax.lax.fori_loop(0, T // k, body,
                             jnp.zeros((n, k), jnp.float64))


def _score_multi_impl(pack: PackedServingTrees, leaf_values, keys_hi,
                      keys_lo, nan_mask, iv, max_depth: int, num_class: int):
    """Model-axis-stacked scoring: every argument carries a leading model
    axis G and slot ``g`` is scored with slot ``g``'s pack — a vmap of
    ``_score_impl``, so per slot the walk and the float64 accumulation
    are the IDENTICAL element-wise IEEE-754 op sequence as the
    single-model program (bitwise equal to each member's own
    ``Booster.predict``).  One dispatch serves a whole multi-tenant
    micro-batch window with zero cross-model launches."""
    import jax

    def one(p, lv, kh, kl, nm, i):
        return _score_impl(PackedServingTrees(*p), lv, kh, kl, nm, i,
                           max_depth, num_class)

    return jax.vmap(one)(tuple(pack), leaf_values, keys_hi, keys_lo,
                         nan_mask, iv)


_serve_walk = None    # lazily-built watched_jits (import must stay jax-free)
_serve_score = None
_serve_score_multi = None


def _get_walk():
    global _serve_walk
    if _serve_walk is None:
        from ..telemetry import watched_jit
        # leaf-index-only program: the host-accumulation fallback and the
        # leaves() introspection surface (buckets legitimately
        # re-specialize per ladder shape: count, never warn)
        _serve_walk = watched_jit(_walk_impl, name="serve_leaves",
                                  warn_after=0,
                                  static_argnames=("max_depth",))
    return _serve_walk


def _get_score():
    global _serve_score
    if _serve_score is None:
        from ..telemetry import watched_jit
        # the serving hot path: walk + f64 accumulation in ONE program.
        # Keeps the historical entry name — every zero-recompiles gate
        # (tests, BENCH_SERVE, /stats) keys off "serve_predict"
        _serve_score = watched_jit(_score_impl, name="serve_predict",
                                   warn_after=0,
                                   static_argnames=("max_depth",
                                                    "num_class"))
    return _serve_score


def _get_score_multi():
    global _serve_score_multi
    if _serve_score_multi is None:
        from ..telemetry import watched_jit
        # the multi-tenant hot path: same program vmapped over a model
        # axis; model-count/bucket ladders legitimately re-specialize
        _serve_score_multi = watched_jit(_score_multi_impl,
                                         name="serve_predict_multi",
                                         warn_after=0,
                                         static_argnames=("max_depth",
                                                          "num_class"))
    return _serve_score_multi


def bucket_ladder(max_batch: int, spec: str = "",
                  floor: int = 8) -> List[int]:
    """Row-count buckets, ascending.  Default: powers of two from
    ``floor`` up to (and including) the next power >= max_batch; an
    explicit comma ``spec`` overrides the whole ladder."""
    if spec and str(spec).strip():
        try:
            out = sorted({int(tok) for tok in str(spec).split(",")
                          if str(tok).strip()})
        except ValueError:
            raise LightGBMError(f"serve_buckets={spec!r} must be a "
                                "comma-separated list of integers")
        if not out or out[0] < 1:
            raise LightGBMError(f"serve_buckets={spec!r} must list "
                                "positive row counts")
        return out
    cap = max(int(max_batch), floor)
    out, b = [], floor
    while b < cap:
        out.append(b)
        b *= 2
    out.append(b)   # first power of two >= cap
    return out


class CompiledPredictor:
    """Pre-packed model + bucket ladder; every call pads to a bucket and
    dispatches one already-traced program that returns FINISHED float64
    raw scores (device accumulation), or leaf indices on f64-less
    backends (host accumulation fallback)."""

    def __init__(self, trees: Sequence, num_class: int, num_features: int,
                 max_batch: int = 256, buckets: Optional[Sequence[int]] = None,
                 envelope: Optional[Tuple[int, int, int, int]] = None):
        for t in trees:
            if getattr(t, "is_linear", False):
                # linear leaves need raw-feature dot products in float64 —
                # host path (registry falls back to Booster.predict)
                raise LightGBMError(
                    "linear trees are not supported by the compiled "
                    "serving predictor")
        self.num_class = int(num_class)
        self.num_features = int(num_features)
        self.buckets = (sorted(int(b) for b in buckets) if buckets
                        else bucket_ladder(max_batch))
        self._leaf_values = [np.asarray(t.leaf_value, np.float64)
                             for t in trees]
        nt = len(trees)
        # envelope = (leaves-1, cat rows, cat words, depth) MINIMUMS: pad
        # the pack out to a shared rounded shape (shape_envelope) so
        # same-family models of a multi-tenant cache land on identical
        # traced shapes and reuse ONE compiled serve_predict program.
        # Padding only widens never-visited node/bitset slots and no-op
        # walk iterations (a settled leaf is inactive), so scores are
        # bit-identical to the unpadded pack.
        env_m, env_c, env_w, env_d = (int(x) for x in envelope) \
            if envelope is not None else (0, 0, 0, 0)
        M = max(max((t.num_leaves - 1 for t in trees), default=0), 1, env_m)

        sf = np.zeros((nt, M), np.int32)
        thr = np.zeros((nt, M), np.float64)
        dt = np.zeros((nt, M), np.int32)
        lc = np.zeros((nt, M), np.int32)
        rc = np.zeros((nt, M), np.int32)
        co = np.full((nt, M), -1, np.int32)
        cat_rows: List[np.ndarray] = []
        from ..pallas.predict_kernel import tree_max_depth
        maxd = 1
        for ti, t in enumerate(trees):
            ni = max(t.num_leaves - 1, 0)
            if ni == 0:
                continue
            maxd = max(maxd, tree_max_depth(t))
            sf[ti, :ni] = np.asarray(t.split_feature[:ni], np.int32)
            thr[ti, :ni] = np.asarray(t.threshold[:ni], np.float64)
            d = np.asarray(t.decision_type[:ni], np.uint8).astype(np.int32)
            dt[ti, :ni] = d
            lc[ti, :ni] = np.asarray(t.left_child[:ni], np.int32)
            rc[ti, :ni] = np.asarray(t.right_child[:ni], np.int32)
            for i in np.nonzero(d & 1)[0]:
                k = int(t.threshold_bin[i])
                s, e = int(t.cat_boundaries[k]), int(t.cat_boundaries[k + 1])
                co[ti, i] = len(cat_rows)
                cat_rows.append(np.asarray(t.cat_threshold[s:e], np.uint32))
        self.max_depth = max(int(maxd), env_d)
        W = max([1, env_w] + [len(r) for r in cat_rows])
        cw = np.zeros((max(len(cat_rows), 1, env_c), W), np.uint32)
        for ri, r in enumerate(cat_rows):
            cw[ri, :len(r)] = r

        import jax.numpy as jnp
        thi, tlo = _split_key(_key64(thr))
        # host copies kept only in envelope (multi-tenant) mode — the
        # stacked serve_predict_multi dispatch stacks them per call
        self._host_pack = (sf, thi, tlo, dt, lc, rc, co, cw) \
            if envelope is not None else None
        self._host_lv = None
        self._pack = PackedServingTrees(
            split_feature=jnp.asarray(sf), thr_hi=jnp.asarray(thi),
            thr_lo=jnp.asarray(tlo), decision_type=jnp.asarray(dt),
            left_child=jnp.asarray(lc), right_child=jnp.asarray(rc),
            cat_ord=jnp.asarray(co), cat_words=jnp.asarray(cw))
        # (T, L) float64 leaf-value table for the on-device accumulation;
        # created under the x64 scope so the device array is real f64
        self.device_accum = (device_accumulation_supported()
                             and (self.num_class == 1
                                  or nt % self.num_class == 0))
        self._lv_dev = None
        if self.device_accum:
            lvt = np.zeros((max(nt, 1), M + 1), np.float64)
            for ti, t in enumerate(trees):
                nlv = min(t.num_leaves, M + 1)
                lvt[ti, :nlv] = np.asarray(t.leaf_value[:nlv], np.float64)
            if envelope is not None:
                self._host_lv = lvt
            with _x64_scope():
                self._lv_dev = jnp.asarray(lvt)
        # pinned per-bucket pad buffers: one (bucket, F) set per bucket,
        # filled in place per chunk — the hot path never np.pad-allocates.
        # One dispatch at a time per predictor (the micro-batcher's single
        # worker is the expected caller; direct concurrent callers
        # serialize on this lock rather than corrupt each other's pads)
        self._buf_lock = threading.Lock()
        self._pads: Dict[int, Tuple[np.ndarray, ...]] = {}

    @property
    def shape_signature(self) -> Tuple:
        """Everything a traced serve_predict program specializes on:
        models with equal signatures share compiled programs (and may be
        dispatched together by ``raw_scores_stacked``)."""
        T, M = self._pack.split_feature.shape
        C, W = self._pack.cat_words.shape
        return (int(T), int(M), int(C), int(W), self.max_depth,
                self.num_class, self.num_features, bool(self.device_accum),
                tuple(self.buckets))

    def device_bytes(self) -> int:
        """Bytes of device residency this model pins (pack + f64 leaf
        table) — the multi-tenant cache's HBM accounting unit."""
        n = 0
        for a in self._pack:
            n += int(np.prod(a.shape)) * int(np.dtype(a.dtype).itemsize)
        if self._lv_dev is not None:
            n += int(np.prod(self._lv_dev.shape)) * 8
        return n

    # -- host-side row encoding -------------------------------------------
    def _encode(self, X: np.ndarray):
        X = np.ascontiguousarray(X, np.float64)
        nan = np.isnan(X)
        khi, klo = _split_key(_key64(X))
        # categorical int: truncate-toward-zero like the host walk's
        # astype(int64); NaN -> -1 (routes right), huge values clamp into
        # the always-invalid range beyond any bitset
        iv = np.where(nan, -1.0, X)
        iv = np.clip(iv, -1.0, float(2 ** 31 - 1)).astype(np.int64)
        return khi, klo, nan, iv.astype(np.int32)

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _pad_buffers(self, bucket: int) -> Tuple[np.ndarray, ...]:
        """The pinned (bucket, F) khi/klo/nan/iv pad set (caller holds
        ``_buf_lock``).  Pad rows keep whatever the previous chunk left —
        their walk output is sliced away, so stale contents are unread."""
        bufs = self._pads.get(bucket)
        if bufs is None:
            F = self.num_features
            bufs = (np.zeros((bucket, F), np.uint32),
                    np.zeros((bucket, F), np.uint32),
                    np.zeros((bucket, F), bool),
                    np.zeros((bucket, F), np.int32))
            self._pads[bucket] = bufs
        return bufs

    def _fill(self, bucket: int, khi, klo, nan, iv, s: int, m: int):
        bufs = self._pad_buffers(bucket)
        for buf, src in zip(bufs, (khi, klo, nan, iv)):
            buf[:m] = src[s:s + m]
        return bufs

    def leaves(self, X: np.ndarray) -> np.ndarray:
        """(T, n) leaf indices; internally chunks to the largest bucket
        and pads each chunk, so any n works without a fresh trace.
        Introspection / host-accumulation surface — the serving hot path
        is :meth:`raw_scores`."""
        import jax.numpy as jnp
        n = X.shape[0]
        khi, klo, nan, iv = self._encode(X)
        cap = self.buckets[-1]
        walk = _get_walk()
        outs = []
        with self._buf_lock:
            for s in range(0, n, cap) if n else []:
                m = min(cap, n - s)
                b = self.bucket_for(m)
                bufs = self._fill(b, khi, klo, nan, iv, s, m)
                out = walk(self._pack, jnp.asarray(bufs[0]),
                           jnp.asarray(bufs[1]), jnp.asarray(bufs[2]),
                           jnp.asarray(bufs[3]), max_depth=self.max_depth)
                outs.append(np.asarray(out)[:, :m])
        if not outs:
            return np.zeros((len(self._leaf_values), 0), np.int32)
        return np.concatenate(outs, axis=1)

    def raw_scores(self, X: np.ndarray) -> np.ndarray:
        """Pre-average raw scores, (n,) or (n, K) float64 — bitwise
        identical to the ``Booster.predict`` host loop.  Device path:
        walk + float64 leaf accumulation inside one compiled program per
        bucket.  Fallback (f64-less backend / LGBTPU_SERVE_ACCUM=host):
        device walk to leaf indices, host float64 loop in tree order."""
        n = X.shape[0]
        k = self.num_class
        if self._lv_dev is None:
            return self._raw_scores_host(X)
        import jax.numpy as jnp
        khi, klo, nan, iv = self._encode(X)
        cap = self.buckets[-1]
        score = _get_score()
        outs = []
        with self._buf_lock, _x64_scope():
            for s in range(0, n, cap) if n else []:
                m = min(cap, n - s)
                b = self.bucket_for(m)
                bufs = self._fill(b, khi, klo, nan, iv, s, m)
                out = score(self._pack, self._lv_dev, jnp.asarray(bufs[0]),
                            jnp.asarray(bufs[1]), jnp.asarray(bufs[2]),
                            jnp.asarray(bufs[3]), max_depth=self.max_depth,
                            num_class=k)
                outs.append(np.asarray(out)[:m])
        if not outs:
            return np.zeros((0,) if k == 1 else (0, k), np.float64)
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _raw_scores_host(self, X: np.ndarray) -> np.ndarray:
        """Host float64 accumulation over device leaf indices, in the
        exact order of the Booster.predict host loop."""
        n = X.shape[0]
        k = self.num_class
        leaves = self.leaves(X)
        if k == 1:
            score = np.zeros(n, np.float64)
            for i, lv in enumerate(self._leaf_values):
                score += lv[leaves[i]]
            return score
        score = np.zeros((n, k), np.float64)
        for i, lv in enumerate(self._leaf_values):
            score[:, i % k] += lv[leaves[i]]
        return score

    def warmup(self) -> int:
        """Trace every bucket once (called by the registry BEFORE the
        version swap, so live traffic never pays a compile). Returns the
        number of buckets primed."""
        for b in self.buckets:
            self.raw_scores(np.zeros((b, self.num_features), np.float64))
        return len(self.buckets)


def shape_envelope(trees: Sequence) -> Tuple[int, int, int, int]:
    """Deterministic rounded-up pack minimums (leaves-1, cat rows, cat
    words, depth) for :class:`CompiledPredictor`'s ``envelope`` argument.
    Same-family models (same feature count / class count / tree count /
    similar size) round to the SAME envelope without any cross-model
    coordination, so every member of a multi-tenant cache group shares
    one compiled program per bucket — zero cross-model recompile churn."""
    from ..pallas.predict_kernel import tree_max_depth
    m = c = w = 0
    d = 1
    for t in trees:
        ni = max(t.num_leaves - 1, 0)
        m = max(m, ni)
        if ni == 0:
            continue
        d = max(d, tree_max_depth(t))
        dts = np.asarray(t.decision_type[:ni], np.uint8)
        for i in np.nonzero(dts & 1)[0]:
            k = int(t.threshold_bin[i])
            c += 1
            w = max(w, int(t.cat_boundaries[k + 1])
                    - int(t.cat_boundaries[k]))

    def up(v: int, step: int) -> int:
        return max(step, ((int(v) + step - 1) // step) * step)

    return (up(m, 16), up(c, 8), up(w, 4), up(d, 4))


def raw_scores_stacked(preds: Sequence["CompiledPredictor"],
                       X_list: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Score several SAME-SHAPE models in ONE ``serve_predict_multi``
    dispatch: member ``g``'s pack and rows ride slot ``g`` of a
    model-axis stack (models padded to a power-of-two slot count, rows
    padded to a shared bucket).  Returns per-member float64 raw scores,
    bitwise equal to each member's own :meth:`raw_scores`.  Requires
    every member built with the same ``envelope`` (identical
    ``shape_signature``) and device accumulation."""
    if len(preds) != len(X_list) or not preds:
        raise LightGBMError("raw_scores_stacked: one row block per model")
    lead = preds[0]
    sig = lead.shape_signature
    for p in preds[1:]:
        if p.shape_signature != sig:
            raise LightGBMError("stacked dispatch requires identical "
                                "pack shapes (same envelope group)")
    if lead._lv_dev is None or any(p._host_pack is None for p in preds):
        raise LightGBMError("stacked dispatch requires device "
                            "accumulation and envelope packing")
    rows = [np.ascontiguousarray(x, np.float64) for x in X_list]
    m_max = max(x.shape[0] for x in rows)
    if m_max > lead.buckets[-1]:
        raise LightGBMError("stacked dispatch rows exceed the bucket "
                            "ladder; use per-model raw_scores")
    b = lead.bucket_for(max(m_max, 1))
    g_pad = 1
    while g_pad < len(preds):
        g_pad *= 2
    F = lead.num_features
    khi = np.zeros((g_pad, b, F), np.uint32)
    klo = np.zeros((g_pad, b, F), np.uint32)
    nan = np.zeros((g_pad, b, F), bool)
    iv = np.zeros((g_pad, b, F), np.int32)
    for g, (p, x) in enumerate(zip(preds, rows)):
        if x.shape[0] == 0:
            continue
        h, lo, nm, i32 = p._encode(x)
        m = x.shape[0]
        khi[g, :m], klo[g, :m], nan[g, :m], iv[g, :m] = h, lo, nm, i32
    # pad slots replicate member 0's pack (their rows are zeros whose
    # walk output is sliced away)
    order = list(range(len(preds))) + [0] * (g_pad - len(preds))
    import jax.numpy as jnp
    stacked = [np.stack([preds[i]._host_pack[j] for i in order])
               for j in range(8)]
    lv = np.stack([preds[i]._host_lv for i in order])
    score = _get_score_multi()
    k = lead.num_class
    with _x64_scope():
        pack = PackedServingTrees(*(jnp.asarray(a) for a in stacked))
        out = np.asarray(score(
            pack, jnp.asarray(lv), jnp.asarray(khi), jnp.asarray(klo),
            jnp.asarray(nan), jnp.asarray(iv),
            max_depth=lead.max_depth, num_class=k))
    return [out[g, :x.shape[0]] for g, x in enumerate(rows)]

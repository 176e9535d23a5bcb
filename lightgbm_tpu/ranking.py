"""Ranking objectives: LambdaRank-NDCG and XE-NDCG.

Reference: src/objective/rank_objective.hpp — RankingObjective (:26, per-query OpenMP
loops), LambdarankNDCG (:139, pairwise lambdas with delta-NDCG weighting, truncation,
sigmoid table, per-query normalisation), RankXENDCG (:385).

TPU re-design: queries are bucketed by size into padded (Q_bucket, M) blocks host-side;
each bucket's gradient is one jitted dense computation — LambdaRank materialises the
(chunked) all-pairs (q, M, M) tensors on the VPU instead of scalar double loops; the
sigmoid lookup table is unnecessary. Outputs scatter back to the flat document order.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .objectives import ObjectiveFunction
from .telemetry.watchdog import watched_jit
from .utils.log import LightGBMError, log_warning


def default_label_gain(max_label: int = 31) -> np.ndarray:
    return (2.0 ** np.arange(max_label + 1)) - 1.0


def query_spans(query_boundaries) -> Tuple[np.ndarray, np.ndarray]:
    """(starts, sizes) from either 1-D cumulative boundaries or (nq, 2)
    [start, size] spans (the distributed shard-padded layout, which has pad
    gaps between ranks' queries — see Dataset.get_query_boundaries)."""
    qb = np.asarray(query_boundaries, np.int64)
    if qb.ndim == 2:
        return qb[:, 0], qb[:, 1]
    return qb[:-1], np.diff(qb)


class _QueryBuckets(NamedTuple):
    sizes: List[int]                  # padded M per bucket
    doc_index: List[np.ndarray]       # (Qb, M) flat doc indices, -1 = pad
    inv_max_dcg: List[np.ndarray]     # (Qb,) per query
    query_ids: List[np.ndarray]       # (Qb,) original query index


def _bucketize(query_boundaries: np.ndarray, labels: np.ndarray,
               label_gain: np.ndarray, truncation_level: int) -> _QueryBuckets:
    starts, sizes = query_spans(query_boundaries)
    nq = len(starts)
    max_m = int(sizes.max()) if nq else 1
    bucket_sizes: List[int] = []
    m = 8
    while m < max_m:
        bucket_sizes.append(m)
        m *= 2
    bucket_sizes.append(max(m, 8))

    # per-query 1/maxDCG@truncation (reference: DCGCalculator::CalMaxDCGAtK)
    inv_max = np.zeros(nq)
    gains = label_gain[np.clip(labels.astype(np.int64), 0, len(label_gain) - 1)]
    disc_all = 1.0 / np.log2(np.arange(max_m) + 2.0)
    for qi in range(nq):
        g = np.sort(gains[starts[qi]:starts[qi] + sizes[qi]])[::-1][:truncation_level]
        md = float(np.sum(g * disc_all[:len(g)]))
        inv_max[qi] = 1.0 / md if md > 0 else 0.0

    which = np.searchsorted(bucket_sizes, sizes)
    out_sizes, out_idx, out_inv, out_qids = [], [], [], []
    for bi, m in enumerate(bucket_sizes):
        qsel = np.where(which == bi)[0]
        if len(qsel) == 0:
            continue
        idx = np.full((len(qsel), m), -1, np.int64)
        for r, qi in enumerate(qsel):
            s, z = starts[qi], sizes[qi]
            idx[r, :z] = np.arange(s, s + z)
        out_sizes.append(m)
        out_idx.append(idx)
        out_inv.append(inv_max[qsel])
        out_qids.append(qsel)
    return _QueryBuckets(out_sizes, out_idx, out_inv, out_qids)


def _contiguous_span(idx: np.ndarray):
    """(offset, true_size) when every query in the bucket has the same true
    size and their rows are consecutive in the flat doc order — then the
    bucket's (Q, M) padded gather collapses to slice+reshape+pad, and the
    gradient scatter to one contiguous slice-add.  Real ranking sets are
    close to uniform (MSLR ~120 docs/query), so this removes two random
    N-sized gathers per boosting iteration (~105M rows/s on TPU =
    ~20 ms/iter at MSLR scale)."""
    q, m = idx.shape
    valid = idx >= 0
    z = int(valid[0].sum())
    if z == 0 or not (valid.sum(axis=1) == z).all() or not valid[:, :z].all():
        return None
    off = int(idx[0, 0])
    expect = off + np.arange(q * z, dtype=np.int64).reshape(q, z)
    if not np.array_equal(idx[:, :z], expect):
        return None
    return off, z


def _bucket_scores(score, idx, span):
    """Per-bucket (Q, M) padded scores: slice+reshape+pad on contiguous
    uniform buckets, generic gather otherwise."""
    if span is not None:
        off, z = span
        q, m = idx.shape
        s = jax.lax.dynamic_slice(score, (off,), (q * z,)).reshape(q, z)
        return jnp.pad(s, ((0, 0), (0, m - z))) if z < m else s
    return score[idx.reshape(-1)].reshape(idx.shape)


def _bucket_scatter_add(vec, vals, idx, valid, span, n):
    """Accumulate per-bucket (Q, M) grads back into the flat (N,) vector."""
    if span is not None:
        off, z = span
        q = idx.shape[0]
        return vec.at[off:off + q * z].add(
            vals[:, :z].reshape(-1).astype(vec.dtype))
    flat_idx = jnp.where(valid.reshape(-1), idx.reshape(-1), n)
    return vec.at[flat_idx].add(vals.reshape(-1).astype(vec.dtype),
                                mode="drop")


@functools.partial(watched_jit, name="lambdarank_bucket", warn_after=0,
                   static_argnames=("sigma", "norm", "trunc", "chunk"))
def _lambdarank_bucket(scores, labels_q, valid, inv_max_dcg, gains_q,
                       sigma: float, norm: bool, trunc: int, chunk: int = 256):
    """Pairwise lambdas for one padded bucket.

    scores/labels_q/valid: (Q, M); inv_max_dcg: (Q,). Returns (grad, hess) (Q, M)."""
    Q, M = scores.shape
    NEG = -1e30
    K = min(trunc, M)

    def one_chunk(args):
        # Sorted-space top-K pair formulation (reference:
        # rank_objective.hpp:180 GetGradientsForOneQuery iterates
        # `for i < min(truncation_level, cnt): for j in (i, cnt)` over docs
        # sorted by score desc).  Forming only those (K, M) pairs — instead
        # of all (M, M) pairs masked down — cuts the pairwise tensor work
        # by M/K (~4x at the MSLR shapes M~128, truncation 30), and the
        # positional discounts become a static vector.
        s, lab, v, imd, gain = args                       # (q, M) ...
        masked = jnp.where(v, s, NEG)
        # multi-operand stable sort carries every per-doc array into sorted
        # space in ONE pass, and a second sort on the carried original
        # position unsorts the results.  take_along_axis gathers here were
        # 2x the cost of the whole pairwise computation (TPU random gather
        # ~105M rows/s vs sort ~230M rows/s).
        iota = jnp.broadcast_to(
            jnp.arange(M, dtype=jnp.int32), masked.shape)
        neg_ss, labs, gains_s, vf, orig_pos = jax.lax.sort(
            (-masked, lab, gain, v.astype(jnp.float32), iota),
            dimension=-1, num_keys=1, is_stable=True)
        ss = -neg_ss
        vs = vf > 0.5                                     # valid = prefix
        disc = 1.0 / jnp.log2(jnp.arange(M, dtype=jnp.float32) + 2.0)
        best = jnp.max(masked, axis=-1, keepdims=True)
        worst = jnp.min(jnp.where(v, s, -NEG), axis=-1, keepdims=True)
        has_range = (best != worst)

        sk, labk, gk, vk = ss[:, :K], labs[:, :K], gains_s[:, :K], vs[:, :K]
        sd = sk[:, :, None] - ss[:, None, :]              # (q, K, M)
        sgn = jnp.sign(labk[:, :, None] - labs[:, None, :])
        upper = (jnp.arange(M)[None, :] > jnp.arange(K)[:, None])  # j > a
        pair_valid = (vk[:, :, None] & vs[:, None, :] & (sgn != 0)
                      & upper[None])
        delta = (jnp.abs(gk[:, :, None] - gains_s[:, None, :])
                 * jnp.abs(disc[:K][None, :, None] - disc[None, None, :])
                 * imd[:, None, None])
        if norm:
            delta = jnp.where(has_range[..., None],
                              delta / (0.01 + jnp.abs(sd)), delta)
        # p = sigmoid(-sigma * (s_high - s_low)); the higher-labelled doc of
        # the pair is position a when sgn>0 else position j
        p = jax.nn.sigmoid(-sigma * sgn * sd)
        lam = -sigma * p * delta                          # lambda for the high doc
        hs = sigma * sigma * p * (1.0 - p) * delta
        lam = jnp.where(pair_valid, lam, 0.0)
        hs = jnp.where(pair_valid, hs, 0.0)
        slam = sgn * lam                                  # signed for pos a
        # high doc += lam, low doc -= lam (in sorted space), then unsort
        g_sorted = (-jnp.sum(slam, axis=1)).at[:, :K].add(jnp.sum(slam, axis=2))
        h_sorted = jnp.sum(hs, axis=1).at[:, :K].add(jnp.sum(hs, axis=2))
        sum_lambdas = -2.0 * jnp.sum(lam, axis=(1, 2))
        if norm:
            factor = jnp.where(sum_lambdas > 0,
                               jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-20),
                               1.0)
            g_sorted = g_sorted * factor[:, None]
            h_sorted = h_sorted * factor[:, None]
        _, g, h = jax.lax.sort((orig_pos, g_sorted, h_sorted),
                               dimension=-1, num_keys=1, is_stable=True)
        return g, h

    pad_q = -(-Q // chunk) * chunk - Q
    if pad_q:
        scores = jnp.pad(scores, ((0, pad_q), (0, 0)))
        labels_q = jnp.pad(labels_q, ((0, pad_q), (0, 0)))
        valid = jnp.pad(valid, ((0, pad_q), (0, 0)))
        inv_max_dcg = jnp.pad(inv_max_dcg, (0, pad_q))
        gains_q = jnp.pad(gains_q, ((0, pad_q), (0, 0)))
    nb = scores.shape[0] // chunk
    xs = tuple(a.reshape((nb, chunk) + a.shape[1:])
               for a in (scores, labels_q, valid, inv_max_dcg, gains_q))
    g, h = jax.lax.map(one_chunk, xs)
    g = g.reshape(-1, M)[:Q]
    h = h.reshape(-1, M)[:Q]
    return g, h


class LambdarankNDCG(ObjectiveFunction):
    """reference: rank_objective.hpp:139."""
    name = "lambdarank"
    is_ranking = True

    def init(self, label, weight, query_boundaries=None, position=None, n=0):
        super().init(label, weight)
        if query_boundaries is None:
            raise LightGBMError("lambdarank requires query information (set group)")
        c = self.config
        lg = c.label_gain
        if lg is None:
            lg = default_label_gain(max(int(np.max(label)) if len(label) else 1, 31))
        self.label_gain_np = np.asarray(lg, np.float64)
        max_label = int(np.max(label)) if len(label) else 0
        if max_label >= len(self.label_gain_np):
            raise LightGBMError(f"label {max_label} exceeds label_gain size")
        self.qb = np.asarray(query_boundaries, np.int64)
        self.buckets = _bucketize(self.qb, np.asarray(label), self.label_gain_np,
                                  c.lambdarank_truncation_level)
        self.n = n
        self._dev_idx = [jnp.asarray(np.maximum(ix, 0)) for ix in self.buckets.doc_index]
        self._dev_valid = [jnp.asarray(ix >= 0) for ix in self.buckets.doc_index]
        self._spans = [_contiguous_span(ix) for ix in self.buckets.doc_index]
        self._dev_inv = [jnp.asarray(v, jnp.float32) for v in self.buckets.inv_max_dcg]
        lab = np.asarray(label)
        gains = self.label_gain_np[np.clip(lab.astype(np.int64), 0,
                                           len(self.label_gain_np) - 1)]
        self._dev_lab = [jnp.asarray(lab[np.maximum(ix, 0)], jnp.float32)
                         for ix in self.buckets.doc_index]
        self._dev_gain = [jnp.asarray(gains[np.maximum(ix, 0)], jnp.float32)
                          for ix in self.buckets.doc_index]
        # position-debiased lambdarank (reference: rank_objective.hpp:44-66
        # score adjustment + :303 UpdatePositionBiasFactors Newton step)
        self._positions = None
        if position is not None:
            # the per-iteration Newton bias update stays traceable: pos_biases
            # is declared in state_attrs(), so the fused gradient jit threads
            # it in as an argument and returns the new value (GBDT._boost_padded)
            pos = np.asarray(position, np.int64).reshape(-1)
            if len(pos) != n:
                raise LightGBMError(
                    f"position has {len(pos)} entries for {n} rows")
            self.num_position_ids = int(pos.max()) + 1 if len(pos) else 0
            self._positions = jnp.asarray(pos, jnp.int32)
            self.pos_biases = jnp.zeros(self.num_position_ids, jnp.float32)
            self._pos_counts = jnp.asarray(
                np.bincount(pos, minlength=self.num_position_ids), jnp.float32)
            self._pos_reg = float(c.lambdarank_position_bias_regularization)
            self._pos_lr = float(c.learning_rate)

    def data_bound_attrs(self):
        return ("label", "weight", "_dev_idx", "_dev_valid", "_dev_inv",
                "_dev_lab", "_dev_gain", "_positions", "_pos_counts")

    def state_attrs(self):
        return ("pos_biases",) if self._positions is not None else ()

    def get_gradients(self, score):
        c = self.config
        n = score.shape[0]
        if self._positions is not None:
            score = score + self.pos_biases[self._positions]
        grad = jnp.zeros(n, jnp.float32)
        hess = jnp.zeros(n, jnp.float32)
        for bi in range(len(self.buckets.sizes)):
            idx = self._dev_idx[bi]
            span = self._spans[bi]
            s = _bucket_scores(score, idx, span)
            g, h = _lambdarank_bucket(
                s, self._dev_lab[bi], self._dev_valid[bi], self._dev_inv[bi],
                self._dev_gain[bi], sigma=float(c.sigmoid),
                norm=bool(c.lambdarank_norm),
                trunc=int(c.lambdarank_truncation_level))
            grad = _bucket_scatter_add(grad, g, idx, self._dev_valid[bi],
                                       span, n)
            hess = _bucket_scatter_add(hess, h, idx, self._dev_valid[bi],
                                       span, n)
        grad, hess = self._apply_weight(grad, hess)
        if self._positions is not None:
            self._update_position_bias(grad, hess)
        return grad, hess

    def _update_position_bias(self, grad, hess) -> None:
        """Newton-Raphson step on the per-position bias factors (reference:
        rank_objective.hpp:303 UpdatePositionBiasFactors); stays on device —
        a host readback would stall the iteration on the device queue."""
        P = self.num_position_ids
        d1 = -jax.ops.segment_sum(grad, self._positions, num_segments=P)
        d2 = -jax.ops.segment_sum(hess, self._positions, num_segments=P)
        d1 = d1 - self.pos_biases * self._pos_reg * self._pos_counts
        d2 = d2 - self._pos_reg * self._pos_counts
        self.pos_biases = (self.pos_biases
                           + self._pos_lr * d1 / (jnp.abs(d2) + 0.001))


@functools.partial(watched_jit, name="xendcg_bucket", warn_after=0,
                   static_argnames=())
def _xendcg_bucket(scores, phi, valid):
    """XE-NDCG gradients for one padded bucket (reference: rank_objective.hpp:401-452)."""
    NEG = -1e30
    masked = jnp.where(valid, scores, NEG)
    rho = jax.nn.softmax(masked, axis=-1)
    rho = jnp.where(valid, rho, 0.0)
    inv_denom = 1.0 / jnp.maximum(jnp.sum(phi * valid, axis=-1, keepdims=True), 1e-15)
    l1 = -phi * inv_denom + rho
    params1 = jnp.where(valid, l1 / jnp.maximum(1.0 - rho, 1e-15), 0.0)
    sum_l1 = jnp.sum(params1, axis=-1, keepdims=True)
    l2 = rho * (sum_l1 - params1)
    params2 = jnp.where(valid, l2 / jnp.maximum(1.0 - rho, 1e-15), 0.0)
    sum_l2 = jnp.sum(params2, axis=-1, keepdims=True)
    l3 = rho * (sum_l2 - params2)
    grad = jnp.where(valid, l1 + l2 + l3, 0.0)
    hess = jnp.where(valid, rho * (1.0 - rho), 0.0)
    return grad, hess


class RankXENDCG(ObjectiveFunction):
    """reference: rank_objective.hpp:385 (XE-NDCG, arxiv 1911.09798)."""
    name = "rank_xendcg"
    is_ranking = True
    jit_safe_gradients = False   # fresh host RNG draw every iteration

    def init(self, label, weight, query_boundaries=None, position=None, n=0):
        super().init(label, weight)
        if query_boundaries is None:
            raise LightGBMError("rank_xendcg requires query information (set group)")
        c = self.config
        self.qb = np.asarray(query_boundaries, np.int64)
        self.buckets = _bucketize(self.qb, np.asarray(label),
                                  default_label_gain(
                                      max(int(np.max(label)) if len(label) else 1, 31)),
                                  c.lambdarank_truncation_level)
        self.n = n
        self._label_np = np.asarray(label)
        self._dev_idx = [jnp.asarray(np.maximum(ix, 0)) for ix in self.buckets.doc_index]
        self._dev_valid = [jnp.asarray(ix >= 0) for ix in self.buckets.doc_index]
        self._spans = [_contiguous_span(ix) for ix in self.buckets.doc_index]
        self._iter = 0
        self._rng = np.random.RandomState(c.objective_seed)

    def get_gradients(self, score):
        n = score.shape[0]
        grad = jnp.zeros(n, jnp.float32)
        hess = jnp.zeros(n, jnp.float32)
        # fresh gammas each iteration (reference: rands_ per query)
        gamma = self._rng.rand(n)
        phi_flat = np.power(2.0, self._label_np.astype(np.int64)) - gamma
        self._iter += 1
        for bi in range(len(self.buckets.sizes)):
            idx = self._dev_idx[bi]
            span = self._spans[bi]
            s = _bucket_scores(score, idx, span)
            phi = jnp.asarray(
                phi_flat[np.maximum(self.buckets.doc_index[bi], 0)], jnp.float32)
            g, h = _xendcg_bucket(s, phi, self._dev_valid[bi])
            grad = _bucket_scatter_add(grad, g, idx, self._dev_valid[bi],
                                       span, n)
            hess = _bucket_scatter_add(hess, h, idx, self._dev_valid[bi],
                                       span, n)
        return self._apply_weight(grad, hess)

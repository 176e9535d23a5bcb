"""Row sampling strategies: bagging and GOSS.

Reference: src/boosting/sample_strategy.cpp (factory), bagging.hpp:15, goss.hpp:19.
TPU design: strategies return a dense {0,1} mask (and possibly re-weighted
gradients), which feeds the histogram count channel directly.  Making tree
cost actually SCALE with the sampled row count is the grower's job: when the
mask is sparse enough, the engine hands ops/grow a static row capacity and
one stable partition per tree compacts the in-bag rows into the view every
histogram pass streams (ops/compact — the reference's bag_data_indices_
prefix, device-side: prefix counts through pallas/compact_kernel.py on the
stream engine, a sorted permutation on the others).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config


# bits of the key a pass of kth_largest decides: 8 passes of 15 compares an
# element over float32 keys; 2 bits (16 passes) and 1 bit (32) measured
# slower on the v5e (PERF.md section 6, PR 40)
SELECT_BITS = 4
THRESHOLD_PASSES = 32 // SELECT_BITS


def kth_largest(mag: jax.Array, k: int) -> jax.Array:
    """The k-th largest element of the non-negative float array ``mag``
    (1-based, ties counted), exactly: the value ``jnp.sort(mag)[n - k]``
    holds, found without sorting.  A non-negative float's bit pattern, read
    as an unsigned integer, orders as the float does (+inf and positive NaN
    at the top), so the answer is the largest key ``t`` with
    ``count(key >= t) >= k``; it is built SELECT_BITS bits a pass from the
    top, each pass counting the keys at or above every candidate digit in
    one multi-output reduction (no (N, digits) array) and keeping the
    largest digit whose count still reaches k."""
    nbits = mag.dtype.itemsize * 8
    udt = jnp.dtype(f"uint{nbits}")
    keys = jax.lax.bitcast_convert_type(mag, udt)

    def one_pass(i, prefix):
        shift = (nbits - SELECT_BITS * (i + 1)).astype(udt)
        counts = jnp.stack([
            jnp.sum(keys >= (prefix | (udt.type(d) << shift)),
                    dtype=jnp.int32)
            for d in range(1, 1 << SELECT_BITS)])
        # counts fall as the digit rises: the digit is how many reach k
        digit = jnp.sum(counts >= k, dtype=udt)
        return prefix | (digit << shift)

    top = jax.lax.fori_loop(0, nbits // SELECT_BITS, one_pass, udt.type(0))
    return jax.lax.bitcast_convert_type(top, mag.dtype)


class SampleStrategy:
    """Returns (mask, grad, hess) per iteration; mask==1 means in-bag."""

    def __init__(self, config: Config, num_data: int,
                 query_boundaries: Optional[np.ndarray] = None,
                 label: Optional[np.ndarray] = None):
        self.config = config
        self.num_data = num_data
        self.query_boundaries = query_boundaries
        self.label = label

    def is_active(self) -> bool:
        return False

    def mask_key(self, iteration: int) -> int:
        """Cache key under which this iteration's mask is reused: two
        iterations with the same key are guaranteed the same mask, so
        per-mask derived state (the in-bag counts the row-compaction
        capacity choice reads back, gbdt._row_compaction_capacity) can be
        cached on it instead of re-synced every iteration."""
        return iteration

    def sample(self, iteration: int, grad: jax.Array, hess: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        mask = jnp.ones(grad.shape[0], jnp.float32)
        return mask, grad, hess

    # ---- fused-iteration support (docs/DISTRIBUTED.md "fused iteration
    # & sharded state"): the one-launch training step cannot run the
    # eager sample() host logic mid-program, so each strategy declares
    # how the fused caller gets its mask ----
    def fused_mode(self, iteration: int) -> str:
        """How the fused program obtains this iteration's mask:
        ``none`` (no sampling), ``mask_arg`` (the eager epoch-cached mask
        is passed in as a jit argument — bagging), or ``traced`` (the
        mask is a pure in-program function of ``traced_key`` and the
        gradients — GOSS)."""
        return "none"

    def traced_key(self, iteration: int) -> Optional[jax.Array]:
        """PRNG key for ``sample_traced`` (host-derived per iteration so
        fused and eager paths draw the identical mask)."""
        return None

    def sample_traced(self, key, grad, hess):
        """Pure jit-safe form of :meth:`sample` (fused_mode='traced')."""
        raise NotImplementedError

    def expected_fraction(self, iteration: int) -> float:
        """Expected in-bag row fraction of this iteration's mask — the
        analytic input to the fused path's compaction capacity (which
        cannot read the count back mid-pipeline)."""
        return 1.0


class BaggingSampleStrategy(SampleStrategy):
    """reference: bagging.hpp — fraction/freq bagging, pos/neg balanced, by-query."""

    def __init__(self, config: Config, num_data: int, query_boundaries=None,
                 label=None):
        super().__init__(config, num_data, query_boundaries, label)
        c = config
        self.use_posneg = (c.pos_bagging_fraction < 1.0 or c.neg_bagging_fraction < 1.0)
        self.active = (c.bagging_freq > 0 and
                       (c.bagging_fraction < 1.0 or self.use_posneg))
        if self.active and label is not None and self.use_posneg:
            self._is_pos = jnp.asarray(np.asarray(label) > 0)
        if self.active and c.bagging_by_query and query_boundaries is not None:
            from ..ranking import query_spans
            starts, sizes = query_spans(query_boundaries)
            nq = len(starts)
            # rows outside any query (padding, incl. distributed shard gaps)
            # get the out-of-range id nq, whose mask entry is always 0
            qid = np.full(num_data, nq, np.int64)
            for qi in range(nq):
                qid[starts[qi]:starts[qi] + sizes[qi]] = qi
            self._qid = jnp.asarray(qid)
            self._nq = nq
        self._mask = None
        self._mask_iter = -1

    def is_active(self) -> bool:
        return self.active

    def mask_key(self, iteration: int) -> int:
        # the mask is a pure function of the bagging epoch (see sample)
        return iteration // max(self.config.bagging_freq, 1)

    def sample(self, iteration: int, grad, hess):
        if not self.active:
            return super().sample(iteration, grad, hess)
        c = self.config
        freq = max(c.bagging_freq, 1)
        # iteration-keyed cache: the old `iteration % freq == 0` refresh left
        # a STALE mask whenever iterations were not visited consecutively
        # (rollback_one_iter, checkpoint resume mid-epoch) — e.g. freq=2,
        # sample(4) then rollback to sample(3) reused epoch-2's mask for an
        # epoch-1 iteration.  Keying the cache on the bagging epoch makes
        # the mask a pure function of `iteration`, which is what lets
        # robustness snapshots skip the RNG stream entirely: the stream
        # position IS the iteration counter the checkpoint already stores.
        epoch = iteration // freq
        if self._mask is None or epoch != self._mask_iter:
            key = jax.random.PRNGKey(c.bagging_seed * 131071 + epoch)
            self._mask_iter = epoch
            n = self.num_data
            if c.bagging_by_query and self.query_boundaries is not None:
                u = jax.random.uniform(key, (self._nq,))
                qmask = jnp.concatenate([u < c.bagging_fraction,
                                         jnp.zeros(1, bool)])
                self._mask = qmask[self._qid].astype(jnp.float32)
            elif self.use_posneg:
                u = jax.random.uniform(key, (n,))
                frac = jnp.where(self._is_pos, c.pos_bagging_fraction,
                                 c.neg_bagging_fraction)
                self._mask = (u < frac).astype(jnp.float32)
            else:
                u = jax.random.uniform(key, (n,))
                self._mask = (u < c.bagging_fraction).astype(jnp.float32)
        m = self._mask
        if grad.ndim == 2:
            return m, grad * m[:, None], hess * m[:, None]
        return m, grad * m, hess * m

    def fused_mode(self, iteration: int) -> str:
        # the bagging mask is a pure function of the epoch (cached, one
        # small draw per bagging_freq iterations), so the fused program
        # takes it as an argument instead of re-deriving it in-trace
        return "mask_arg" if self.active else "none"

    def epoch_mask(self, iteration: int) -> jax.Array:
        """This iteration's (cached) in-bag mask without touching grads —
        the fused caller passes it as a jit argument (and sizes compaction
        from its cached count readback, so the analytic
        ``expected_fraction`` path is GOSS-only)."""
        m, _, _ = self.sample(iteration, jnp.zeros(1, jnp.float32),
                              jnp.zeros(1, jnp.float32))
        return m


class GOSSStrategy(SampleStrategy):
    """Gradient-based one-side sampling (reference: goss.hpp:19; Ke et al.
    2017, Algorithm 2): keep the top_rate * N rows of largest |grad*hess|,
    sample other_rate * N of the REST (both rates are shares of all N rows,
    which is why Config holds top_rate + other_rate <= 1) and amplify the
    sampled rest by (1 - top_rate) / other_rate, so that the rest's gradient
    sum is unbiased.  The reference draws exactly other_rate * N rows; here a
    row of the rest is kept with probability other_rate / (1 - top_rate), the
    same number in expectation: an exact count would take a second
    device-wide selection a tree."""

    def __init__(self, config: Config, num_data: int, query_boundaries=None,
                 label=None):
        super().__init__(config, num_data, query_boundaries, label)

    def is_active(self) -> bool:
        return True

    def _is_warmup(self, iteration: int) -> bool:
        # reference warms up GOSS: no sampling for the first 1/lr
        # iterations (goss.hpp) — the ONE predicate sample() and
        # mask_key() must agree on (a desync would let the engine reuse
        # warmup in-bag counts for a sampled mask)
        return iteration < 1.0 / max(self.config.learning_rate, 1e-12)

    def mask_key(self, iteration: int) -> int:
        # every warmup iteration returns the SAME all-ones mask — one
        # shared key keeps the engine's count cache warm instead of
        # paying a device sync per warmup iteration; sampled iterations
        # draw a fresh mask each time (key never repeats)
        return -1 if self._is_warmup(iteration) else iteration

    def sample(self, iteration: int, grad, hess):
        if self._is_warmup(iteration):
            return SampleStrategy.sample(self, iteration, grad, hess)
        return self.sample_traced(self.traced_key(iteration), grad, hess)

    def fused_mode(self, iteration: int) -> str:
        # the GOSS mask depends on the CURRENT iteration's gradients, so
        # the fused program derives it in-trace (sample_traced); warmup
        # iterations are unsampled and trace the plain program
        return "none" if self._is_warmup(iteration) else "traced"

    def traced_key(self, iteration: int):
        return jax.random.PRNGKey(
            self.config.bagging_seed * 524287 + iteration)

    def expected_fraction(self, iteration: int) -> float:
        if self._is_warmup(iteration):
            return 1.0
        c = self.config
        return min(1.0, c.top_rate + c.other_rate)

    def sample_traced(self, key, grad, hess):
        """Pure jit-safe GOSS draw — shared by the eager path and the
        fused one-launch program (identical key -> identical mask)."""
        c = self.config
        n = self.num_data
        g2 = grad * hess if grad.ndim == 1 else jnp.sum(jnp.abs(grad * hess), axis=1)
        mag = jnp.abs(g2) if g2.ndim == 1 else g2
        k_top = max(1, int(c.top_rate * n))
        # the k_top-th largest |grad*hess| by an exact select of count passes
        # (kth_largest; 3.7 ms at 31.4M rows on the v5e where a sort took
        # 97.7: PERF.md section 6, PR 40).  Under a row-sharded mesh each
        # pass's counts are a GLOBAL psum, so the threshold is a global
        # statistic across row shards and data-parallel GOSS trees are
        # well-defined: every shard keeps its rows against the same cut
        # (docs/DISTRIBUTED.md).
        thresh = kth_largest(mag, k_top)
        is_top = mag >= thresh
        u = jax.random.uniform(key, (n,))
        # other_rate is a share of ALL rows: other_rate / (1 - top_rate) of
        # the rest, which the amplification below makes unbiased
        keep_rest = (~is_top) & (
            u < min(1.0, c.other_rate / max(1.0 - c.top_rate, 1e-12)))
        amp = (1.0 - c.top_rate) / max(c.other_rate, 1e-12)
        mask = (is_top | keep_rest).astype(jnp.float32)
        scale = jnp.where(keep_rest, amp, 1.0) * mask
        if grad.ndim == 2:
            return mask, grad * scale[:, None], hess * scale[:, None]
        return mask, grad * scale, hess * scale


def create_sample_strategy(config: Config, num_data: int, query_boundaries=None,
                           label=None) -> SampleStrategy:
    """reference: SampleStrategy::CreateSampleStrategy (sample_strategy.h:30)."""
    # case-insensitive, matching Config's GOSS conflict validation — a
    # spelling accepted there ('GOSS') must select the same strategy here
    if (str(config.data_sample_strategy).strip().lower() == "goss"
            or str(config.boosting).strip().lower() == "goss"):
        return GOSSStrategy(config, num_data, query_boundaries, label)
    return BaggingSampleStrategy(config, num_data, query_boundaries, label)

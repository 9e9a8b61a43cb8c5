"""Learning-to-rank objectives: lambdarank and rank_xendcg.

Counterpart of src/objective/rank_objective.hpp: RankingObjective (per-query
gradient computation, :25-100), LambdarankNDCG (:138-290: |ΔNDCG|-weighted
pairwise lambdas with truncation, sigmoid scaling, and lambda normalization)
and RankXENDCG (:300+).

TPU design: the reference parallelizes with one OpenMP task per query over
ragged boundaries. Here queries are padded into dense [Q, L] blocks bucketed
by length (powers of two). Lambdarank's whole gradient pass is ONE jitted
program an iteration (`LambdarankNDCG._build_program`), under
`lgbm.gradients`: per bucket the scores are sliced into the layout and
ranked by two stable sorts (`lgbm.rank_sort`), the pair terms are evaluated
on a `[Q, min(T, L), L]` block (the top-`T` documents of a query against
all of its documents: the truncation level admits no other pair) and
reduced to per-document sums (`lgbm.rank_pairs`), and one gather brings
every bucket's sums back to row order (`lgbm.rank_scatter`). rank_xendcg
keeps one jitted program a bucket.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .registry import ObjectiveFunction, register_objective
from .. import tracing
from ..utils.log import Log
from ..utils.timer import (SCOPE_GRADIENTS, SCOPE_RANK_PAIRS,
                           SCOPE_RANK_SCATTER, SCOPE_RANK_SORT, global_timer)

def default_label_gain(max_label: int = 31) -> np.ndarray:
    """DCGCalculator::DefaultLabelGain (dcg_calculator.cpp:33-42): 2^i - 1."""
    g = [0.0]
    for i in range(1, max_label):
        g.append(float((1 << i) - 1))
    return np.array(g)


class QueryLayout:
    """Padded per-bucket query layout shared by ranking objectives/metrics.

    For each power-of-two length bucket: doc_idx [Qb, Lb] (global row ids,
    pad = num_data), labels [Qb, Lb], valid mask, the query ids, and each
    query's first row `starts` [Qb] and length `lengths` [Qb] (a query's
    rows are contiguous, so its padded row is one slice of the scores).
    `flat_pos` [num_data] is every row's place in the concatenation of the
    buckets' flattened [Qb, Lb] blocks: one gather by it brings per-bucket
    values back to row order. `spread(per_row)` lays a per-row host array
    out bucket by bucket (0 in the padding).
    """

    def __init__(self, query_boundaries: np.ndarray, labels: np.ndarray,
                 num_data: int, min_bucket: int = 8) -> None:
        self.num_data = num_data
        self.num_queries = len(query_boundaries) - 1
        qb = np.asarray(query_boundaries, dtype=np.int64)
        lengths = np.diff(qb)
        self.lengths = lengths
        width = np.maximum(min_bucket, 1 << np.ceil(np.log2(np.maximum(
            lengths, 1))).astype(np.int64))
        labels_ext = np.concatenate([np.asarray(labels, dtype=np.float32),
                                     np.zeros(1, dtype=np.float32)])
        flat_pos = np.zeros(num_data, dtype=np.int64)
        self.buckets = []
        self._rows = []  # each bucket's doc_idx, on the host
        base = 0
        for L in np.unique(width):
            L = int(L)
            qids = np.nonzero(width == L)[0]
            starts, lens = qb[qids], lengths[qids]
            within = np.arange(L)[None, :]
            valid = within < lens[:, None]
            doc_idx = np.where(valid, starts[:, None] + within, num_data)
            flat_pos[doc_idx[valid]] = (
                base + np.arange(len(qids))[:, None] * L + within)[valid]
            base += len(qids) * L
            self._rows.append(doc_idx)
            self.buckets.append({
                "L": L,
                "qids": qids,
                "doc_idx": jnp.asarray(doc_idx.astype(np.int32)),
                "labels": jnp.asarray(labels_ext[doc_idx]),
                "valid": jnp.asarray(valid),
                "starts": jnp.asarray(starts.astype(np.int32)),
                "lengths": jnp.asarray(lens.astype(np.int32)),
            })
        self.max_len = max((b["L"] for b in self.buckets), default=min_bucket)
        self.flat_pos = jnp.asarray(flat_pos.astype(np.int32))

    def spread(self, per_row: np.ndarray) -> List[jax.Array]:
        """A per-row array [num_data] as float32 [Qb, Lb] blocks, one a
        bucket, 0 where the layout is padding."""
        ext = np.concatenate([np.asarray(per_row, dtype=np.float32),
                              np.zeros(1, dtype=np.float32)])
        return [jnp.asarray(ext[rows]) for rows in self._rows]


def slice_queries(score_padded: jax.Array, starts: jax.Array, L: int
                  ) -> jax.Array:
    """[Qb, L]: row q is `score_padded[starts[q]: starts[q] + L]`, one
    contiguous slice a query (the scores carry `max_len` spare elements so
    no slice is clamped); what lies past a query's length is its
    neighbours' and is masked by the caller."""
    return jax.vmap(
        lambda at: jax.lax.dynamic_slice(score_padded, (at,), (L,)))(starts)


def discounts(n: int) -> jax.Array:
    """DCGCalculator's discounts of the ranks 0..n-1, 1 / log2(2 + rank),
    worked out on the host in float64 and rounded once: a constant of the
    program that uses it (the v5e's `jnp.log2` is off by up to 5.7e-5 and
    read a whole set's NDCG 1.6e-5 off the float64 count; PERF.md, PR 34)."""
    return jnp.asarray(1.0 / np.log2(np.arange(n) + 2.0), dtype=jnp.float32)


def rank_documents(key: jax.Array, *carried: jax.Array) -> tuple:
    """Documents [Qb, L] by `key`, ascending and stably along each row
    (equal keys keep row order, as `std::stable_sort` leaves them):
    (the sorted key, each of `carried` in that order, every document's
    rank: the inverse of the sort's permutation; every document's
    discount: `discounts(L)` carried back through that inverse)."""
    within = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    *ordered, order = jax.lax.sort((key,) + carried + (within,), dimension=1,
                                   num_keys=1, is_stable=True)
    by_rank = jnp.broadcast_to(discounts(key.shape[1])[None, :], key.shape)
    _, rank, disc = jax.lax.sort((order, within, by_rank), dimension=1,
                                 num_keys=1)
    return (*ordered, rank, disc)


def max_dcg_at_k(labels_sorted_desc: np.ndarray, k: int, gains: np.ndarray) -> float:
    """DCGCalculator::CalMaxDCGAtK."""
    n = min(len(labels_sorted_desc), k)
    disc = 1.0 / np.log2(np.arange(n) + 2.0)
    return float(np.sum(gains[labels_sorted_desc[:n].astype(int)] * disc))


def pair_positions(lengths: np.ndarray, truncation_level: int) -> int:
    """The pairs LambdarankNDCG::GetGradientsForOneQuery visits: for a query
    of n documents and m = min(T, n), every (i, j) with i < m and j > i:
    m * n - m * (m + 1) / 2. Which of them differ in grade depends on the
    scores' order and is not counted."""
    n = np.asarray(lengths, dtype=np.int64)
    m = np.minimum(int(truncation_level), n)
    return int(np.sum(m * n - m * (m + 1) // 2))


@register_objective("lambdarank")
class LambdarankNDCG(ObjectiveFunction):
    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            Log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        self.norm = config.lambdarank_norm
        self.truncation_level = int(config.lambdarank_truncation_level)
        gains = np.array(config.label_gain, dtype=np.float64) if config.label_gain \
            else default_label_gain()
        self.label_gain = gains

    jit_gradients = False  # one jitted program of its own, and bias state

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Ranking tasks require query information")
        qb = metadata.query_boundaries
        label = metadata.label
        if label.max() >= len(self.label_gain):
            Log.fatal("Label %d is not less than the number of label mappings (%d)",
                      int(label.max()), len(self.label_gain))
        self.layout = QueryLayout(qb, label, num_data)
        # per-query 1/maxDCG@trunc
        inv = np.zeros(self.layout.num_queries)
        for q in range(self.layout.num_queries):
            lo, hi = qb[q], qb[q + 1]
            srt = np.sort(label[lo:hi])[::-1]
            mx = max_dcg_at_k(srt, self.truncation_level, self.label_gain)
            inv[q] = 1.0 / mx if mx > 0 else 0.0
        gains = self.layout.spread(self.label_gain[label.astype(np.int64)])
        # the program's arguments, a bucket: the layout is not a constant of it
        self._per_bucket = [
            (b["starts"], b["lengths"], b["labels"], gain,
             jnp.asarray(inv[b["qids"]], dtype=jnp.float32))
            for b, gain in zip(self.layout.buckets, gains)]
        self._w = (jnp.asarray(metadata.weights) if metadata.weights is not None else None)
        # what one pass visits and what the layout evaluates (the
        # `rank_gradients` note, counters `rank_queries`, `rank_pair_slots`)
        self.pair_positions = pair_positions(self.layout.lengths,
                                             self.truncation_level)
        self.pair_slots = int(sum(
            len(b["qids"]) * min(self.truncation_level, b["L"]) * b["L"]
            for b in self.layout.buckets))
        # position debias state (rank_objective.hpp:43-90, 296-340): per-
        # position-id bias factors, Newton-updated from the lambdas each
        # iteration; gradients are computed on bias-adjusted scores
        self._positions = None
        self._pos_biases = None
        self._pos_counts = None
        if metadata.positions is not None:
            self._positions = jnp.asarray(metadata.positions)
            P = len(metadata.position_ids)
            self._num_positions = P
            self._pos_biases = jnp.zeros(P, dtype=jnp.float32)
            self._pos_counts = jnp.zeros(P, jnp.float32).at[self._positions].add(1.0)
        self._program = self._build_program()

    def _pair_sums(self, s, lab, gain, lengths, inv_max_dcg):
        """One bucket. s, lab, gain [Qb, L] in row order (what lies past a
        query's length is masked), lengths, inv_max_dcg [Qb] -> the
        per-document lambda and hessian sums [Qb, L] in row order.

        The pairs of GetGradientsForOneQuery are (i, j) with i < min(T, n)
        and j > i by rank: here the T best documents of a query (`_t`, in
        rank order) against every document (`_k`, in row order, with its
        rank); a pair counts where the column's rank is the larger."""
        sigmoid = jnp.float32(self.sigmoid)
        Qb, L = s.shape
        T = min(self.truncation_level, L)
        with jax.named_scope(SCOPE_RANK_SORT):
            within = jax.lax.broadcasted_iota(jnp.int32, (Qb, L), 1)
            valid = within < lengths[:, None]
            # descending by score; what is no document sorts last
            key_s, lab_s, gain_s, rank, disc = rank_documents(
                jnp.where(valid, -s, jnp.inf), lab, gain)
        with jax.named_scope(SCOPE_RANK_PAIRS):
            top = jax.lax.broadcasted_iota(jnp.int32, (Qb, T), 1)
            valid_t = top < lengths[:, None]
            s_t = jnp.where(valid_t, -key_s[:, :T], 0.0)[:, :, None]
            lab_t = lab_s[:, :T, None]
            gain_t = gain_s[:, :T, None]
            disc_t = discounts(T)[None, :, None]
            s_k = jnp.where(valid, s, 0.0)[:, None, :]
            rank_k = rank[:, None, :]
            disc_k = disc[:, None, :]
            ok = (valid_t[:, :, None] & valid[:, None, :]
                  & (rank_k > top[:, :, None]))
            ds = s_t - s_k            # >= 0 wherever ok
            sign = jnp.sign(lab_t - lab[:, None, :])
            delta_ndcg = (jnp.abs(gain_t - gain[:, None, :])
                          * jnp.abs(disc_t - disc_k)
                          * inv_max_dcg[:, None, None])
            if self.norm:
                best = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
                worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
                delta_ndcg = jnp.where((best != worst)[:, None, None],
                                       delta_ndcg / (0.01 + jnp.abs(ds)),
                                       delta_ndcg)
            p = 1.0 / (1.0 + jnp.exp(sign * ds * sigmoid))
            p_lambda = jnp.where(ok, sigmoid * delta_ndcg * p, 0.0)
            p_hess = jnp.where(ok, sigmoid * sigmoid * delta_ndcg * p * (1.0 - p), 0.0)
            signed = sign * p_lambda  # the higher grade's lambda falls by it
            lam = jnp.sum(signed, axis=1)           # to the column document
            hes = jnp.sum(p_hess, axis=1)
            lam_t = -jnp.sum(signed, axis=2)        # to the top document
            hes_t = jnp.sum(p_hess, axis=2)
            # the top documents' sums, from rank order to their rows
            mine = rank_k == top[:, :, None]
            lam = lam + jnp.sum(jnp.where(mine, lam_t[:, :, None], 0.0), axis=1)
            hes = hes + jnp.sum(jnp.where(mine, hes_t[:, :, None], 0.0), axis=1)
            if self.norm:
                sum_lambdas = 2.0 * jnp.sum(p_lambda, axis=(1, 2))
                factor = jnp.where(
                    sum_lambdas > 0,
                    jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-20),
                    1.0)[:, None]
                lam, hes = lam * factor, hes * factor
            return jnp.where(valid, lam, 0.0), jnp.where(valid, hes, 0.0)

    def _build_program(self):
        """The gradient pass as one jitted program: (score [N], the
        position biases or None) -> (grad [N], hess [N], the biases after
        their Newton step or None). The layout's arrays are arguments, not
        constants of the program."""
        layout = self.layout
        spare = layout.max_len
        widths = [b["L"] for b in layout.buckets]
        bias_reg = jnp.float32(
            self.config.lambdarank_position_bias_regularization)
        bias_lr = jnp.float32(self.config.learning_rate)

        def program(score, biases, per_bucket, flat_pos, weights, positions,
                    counts):
            with jax.named_scope(SCOPE_GRADIENTS):
                if positions is not None:
                    # lambdas come from bias-adjusted scores; the model
                    # score itself is untouched (rank_objective.hpp:66-74)
                    score = score + biases[positions]
                with jax.named_scope(SCOPE_RANK_SORT):
                    padded = jnp.concatenate(
                        [score, jnp.zeros(spare, score.dtype)])
                sums = []
                for L, (starts, lengths, lab, gain, inv) in zip(widths,
                                                                per_bucket):
                    with jax.named_scope(SCOPE_RANK_SORT):
                        s = slice_queries(padded, starts, L)
                    lam, hes = self._pair_sums(s, lab, gain, lengths, inv)
                    sums.append(jnp.stack([lam.reshape(-1), hes.reshape(-1)]))
                with jax.named_scope(SCOPE_RANK_SCATTER):
                    both = jnp.take(jnp.concatenate(sums, axis=1), flat_pos,
                                    axis=1)
                    grad, hess = both[0], both[1]
                if weights is not None:
                    grad, hess = grad * weights, hess * weights
                if positions is not None:
                    fd = -(jnp.zeros_like(biases).at[positions].add(grad))
                    sd = -(jnp.zeros_like(biases).at[positions].add(hess))
                    fd = fd - biases * bias_reg * counts
                    sd = sd - bias_reg * counts
                    biases = biases + bias_lr * fd / (jnp.abs(sd) + 0.001)
                return grad, hess, biases

        return jax.jit(program)

    def get_gradients(self, score):
        layout = self.layout
        grad, hess, self._pos_biases = self._program(
            score, self._pos_biases, self._per_bucket, layout.flat_pos,
            self._w, self._positions, self._pos_counts)
        global_timer.add_count("rank_queries", layout.num_queries)
        global_timer.add_count("rank_pair_slots", self.pair_slots)
        tracing.note("rank_gradients", queries=layout.num_queries,
                     rows=self.num_data, pair_positions=self.pair_positions,
                     pair_slots=self.pair_slots)
        return grad, hess

    def to_string(self):
        return "lambdarank"


@register_objective("rank_xendcg")
class RankXENDCG(ObjectiveFunction):
    """XE-NDCG (Bruch et al. 2019, 'An Alternative Cross Entropy Loss for
    Learning-to-Rank'): listwise softmax cross-entropy with randomly
    perturbed relevance gains (rank_objective.hpp RankXENDCG)."""

    jit_gradients = False  # stateful per-iteration RNG + per-bucket jits

    def __init__(self, config):
        super().__init__(config)
        self.seed = config.objective_seed

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Ranking tasks require query information")
        self.layout = QueryLayout(metadata.query_boundaries, metadata.label, num_data)
        self._w = (jnp.asarray(metadata.weights) if metadata.weights is not None else None)
        self._iter = 0
        self._fns = {}

    def _bucket_fn(self, L: int):
        if L in self._fns:
            return self._fns[L]

        def per_query(s, lab, valid, seed):
            s_masked = jnp.where(valid, s, -jnp.inf)
            key = jax.random.PRNGKey(seed.astype(jnp.uint32))
            # phi: gumbel-perturbed gains, normalized (the paper's sampling)
            gumbel = jax.random.uniform(key, (L,), minval=1e-6, maxval=1.0)
            gain = jnp.where(valid, (2.0 ** lab - 1.0) - jnp.log(-jnp.log(gumbel)), 0.0)
            gain = jnp.maximum(gain, 0.0)
            rho = jax.nn.softmax(s_masked)
            rho = jnp.where(valid, rho, 0.0)
            gsum = jnp.maximum(gain.sum(), 1e-20)
            phi = gain / gsum
            lam = rho - phi
            hes = jnp.maximum(rho * (1.0 - rho), 1e-16)
            return jnp.where(valid, lam, 0.0), jnp.where(valid, hes, 0.0)

        def bucket(score_ext, doc_idx, lab, valid, seeds):
            s = score_ext[doc_idx]
            return jax.vmap(per_query)(s, lab, valid, seeds)

        fn = jax.jit(bucket)
        self._fns[L] = fn
        return fn

    def get_gradients(self, score):
        n = self.num_data
        score_ext = jnp.concatenate([score, jnp.zeros(1, score.dtype)])
        grad = jnp.zeros(n, dtype=jnp.float32)
        hess = jnp.zeros(n, dtype=jnp.float32)
        self._iter += 1
        for b in self.layout.buckets:
            fn = self._bucket_fn(b["L"])
            seeds = jnp.asarray(
                (b["qids"].astype(np.int64) * 9973 + self._iter * 31 + self.seed)
                % (2 ** 31), dtype=jnp.int32)
            lam, hes = fn(score_ext, b["doc_idx"], b["labels"], b["valid"], seeds)
            grad = grad.at[b["doc_idx"].ravel()].set(lam.ravel(), mode="drop")
            hess = hess.at[b["doc_idx"].ravel()].set(hes.ravel(), mode="drop")
        if self._w is not None:
            grad = grad * self._w
            hess = hess * self._w
        return grad, hess

    def to_string(self):
        return "rank_xendcg"

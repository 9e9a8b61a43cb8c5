"""Ranking metrics: NDCG@k and MAP@k.

Counterpart of src/metric/rank_metric.hpp (NDCGMetric with eval_at positions,
DCGCalculator + label-gain table, per-query parallel evaluation, query-weight
support; queries with no relevant docs count as 1.0) and src/metric/
map_metric.hpp (MapMetric).

Device design: queries use the same padded [Q, L] bucket layout as the
ranking objectives; NDCG at every `eval_at` over all of a set's buckets is
one jitted program (a slice, a stable sort and a masked dot a bucket) and
one host fetch of its [Q, n_ks] values, summed in float64 on the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import Metric, register_metric
from ..objectives.rank import (QueryLayout, default_label_gain, discounts,
                               max_dcg_at_k, slice_queries)
from ..utils.timer import SCOPE_EVAL_NDCG


class _RankMetricBase(Metric):
    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            from ..utils.log import Log

            Log.fatal("The NDCG metric requires query information")
        self.layout = QueryLayout(metadata.query_boundaries, metadata.label, num_data)
        self.query_weights = metadata.query_weights
        self.eval_at = [int(k) for k in (self.config.eval_at or [1, 2, 3, 4, 5])]


@register_metric("ndcg")
class NDCGMetric(_RankMetricBase):
    greater_is_better = True

    @property
    def name(self):
        return [f"ndcg@{k}" for k in self.eval_at]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        gains = (np.array(self.config.label_gain, dtype=np.float64)
                 if self.config.label_gain else default_label_gain())
        self.gains = gains
        qb = metadata.query_boundaries
        label = metadata.label
        # per (query, k): 1/maxDCG@k ; 0 marks "no relevant docs" -> ndcg 1
        inv = np.zeros((self.layout.num_queries, len(self.eval_at)))
        for q in range(self.layout.num_queries):
            srt = np.sort(label[qb[q]: qb[q + 1]])[::-1]
            for j, k in enumerate(self.eval_at):
                mx = max_dcg_at_k(srt, k, gains)
                inv[q, j] = 1.0 / mx if mx > 0 else 0.0
        qw = (np.asarray(self.query_weights, dtype=np.float64)
              if self.query_weights is not None
              else np.ones(self.layout.num_queries))
        self._sum_weights = float(qw.sum())
        self._per_bucket = [
            (b["starts"], b["lengths"], gain,
             jnp.asarray(inv[b["qids"]], dtype=jnp.float32),
             jnp.asarray(qw[b["qids"]], dtype=jnp.float32))
            for b, gain in zip(self.layout.buckets, self.layout.spread(
                gains[label.astype(np.int64)]))]
        self._program = self._build_program()

    def _build_program(self):
        """NDCG at every `eval_at` of one set as ONE jitted program an
        evaluation, under `lgbm.eval_ndcg`: (score [N], per-bucket arrays)
        -> every query's weighted NDCG values [Q, n_ks], bucket after
        bucket. The caller fetches that one array (one host transfer a set)
        and sums it in float64, as the per-bucket fetches were summed."""
        ks = tuple(self.eval_at)
        widths = [b["L"] for b in self.layout.buckets]
        spare = self.layout.max_len

        def program(score, per_bucket):
            with jax.named_scope(SCOPE_EVAL_NDCG):
                padded = jnp.concatenate([score, jnp.zeros(spare, score.dtype)])
                values = []
                for L, (starts, lengths, gain, inv, w) in zip(widths,
                                                              per_bucket):
                    within = jax.lax.broadcasted_iota(
                        jnp.int32, (starts.shape[0], L), 1)
                    valid = within < lengths[:, None]
                    # descending by score, stably; no document sorts last
                    key = jnp.where(valid, -slice_queries(padded, starts, L),
                                    jnp.inf)
                    _, g = jax.lax.sort((key, jnp.where(valid, gain, 0.0)),
                                        dimension=1, num_keys=1,
                                        is_stable=True)
                    top = min(max(ks), L)
                    gd = g[:, :top] * discounts(top)
                    dcg = jnp.stack([jnp.sum(gd[:, :min(k, L)], axis=1)
                                     for k in ks], axis=1)
                    ndcg = jnp.where(inv > 0, dcg * inv, 1.0)
                    values.append(ndcg * w[:, None])
                return jnp.concatenate(values, axis=0)

        return jax.jit(program)

    def eval(self, score, objective):
        values = np.asarray(self._program(score, self._per_bucket))
        totals = values.sum(axis=0, dtype=np.float64)
        return [float(t / max(self._sum_weights, 1e-20)) for t in totals]


@register_metric("map", "mean_average_precision")
class MapMetric(_RankMetricBase):
    greater_is_better = True

    @property
    def name(self):
        return [f"map@{k}" for k in self.eval_at]

    def eval(self, score, objective):
        """MAP@k per map_metric.hpp: labels > 0 are relevant."""
        ks = self.eval_at
        totals = np.zeros(len(ks))
        sumw = 0.0
        score_np = np.asarray(score)
        for b in self.layout.buckets:
            doc = np.asarray(b["doc_idx"])
            lab = np.asarray(b["labels"])
            valid = np.asarray(b["valid"])
            s = np.where(valid, score_np[np.minimum(doc, len(score_np) - 1)], -np.inf)
            order = np.argsort(-s, axis=1, kind="stable")
            rel = np.take_along_axis((lab > 0) & valid, order, axis=1)
            cum_rel = np.cumsum(rel, axis=1)
            prec = cum_rel / (np.arange(rel.shape[1]) + 1.0)
            w = (self.query_weights[b["qids"]] if self.query_weights is not None
                 else np.ones(len(b["qids"])))
            for j, k in enumerate(ks):
                ap_num = (prec[:, :k] * rel[:, :k]).sum(axis=1)
                denom = np.minimum(cum_rel[:, -1], k)
                ap = np.where(denom > 0, ap_num / np.maximum(denom, 1), 1.0)
                totals[j] += (ap * w).sum()
            sumw += w.sum()
        return [float(t / max(sumw, 1e-20)) for t in totals]

"""Binary metrics — counterpart of src/metric/binary_metric.hpp: logloss,
error rate, AUC, average precision. AUC/AP are device sort-based (jnp.argsort
then a weighted rank accumulation) — the analog of the reference's sorted-scan
(binary_metric.hpp AUCMetric::Eval)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.timer import SCOPE_EVAL_AUC
from .registry import Metric, register_metric


@register_metric("binary_logloss", "binary")
class BinaryLoglossMetric(Metric):
    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._is_pos = jnp.asarray((metadata.label > 0).astype(np.float32))
        self._w = (jnp.asarray(metadata.weights) if metadata.weights is not None else None)
        self._sumw = (float(np.sum(metadata.weights)) if metadata.weights is not None
                      else float(num_data))

    def eval(self, score, objective):
        prob = objective.convert_output(score) if objective is not None else score
        eps = 1e-15
        prob = jnp.clip(prob, eps, 1.0 - eps)
        loss = -(self._is_pos * jnp.log(prob) + (1.0 - self._is_pos) * jnp.log(1.0 - prob))
        if self._w is not None:
            loss = loss * self._w
        return [float(jnp.sum(loss)) / self._sumw]


@register_metric("binary_error")
class BinaryErrorMetric(BinaryLoglossMetric):
    def eval(self, score, objective):
        prob = objective.convert_output(score) if objective is not None else score
        pred_pos = prob > 0.5
        err = (pred_pos.astype(jnp.float32) != self._is_pos).astype(jnp.float32)
        if self._w is not None:
            err = err * self._w
        return [float(jnp.sum(err)) / self._sumw]


@jax.jit
def _auc(score, is_pos, weights):
    """Weighted AUC via ranks: for each positive, count the fraction of
    negatives scored below it (ties get half credit). One program (the
    sort, the tie segments and the sums) under lgbm.eval_auc, one fetch."""
    with jax.named_scope(SCOPE_EVAL_AUC):
        return _auc_body(score, is_pos, weights)


def _auc_body(score, is_pos, weights):
    w = jnp.ones_like(score) if weights is None else weights
    # one stable sort carries the labels and weights with the scores: no
    # row-sized gather or scatter anywhere in the program
    s, y, w = jax.lax.sort((score, is_pos, w), num_keys=1, is_stable=True)
    wneg = w * (1.0 - y)
    wpos = w * y
    cum_neg = jnp.cumsum(wneg)  # negatives with score <= s_i (inclusive)
    # tie handling: within a run of equal scores (a segment) a positive
    # gets the negatives before the run and half of those in it; both are
    # prefix sums read at the run's ends, carried over the run by scans
    below = jnp.concatenate([jnp.zeros(1, cum_neg.dtype), cum_neg[:-1]])
    is_new = jnp.concatenate([jnp.array([True]), s[1:] > s[:-1]])
    is_end = jnp.concatenate([s[1:] > s[:-1], jnp.array([True])])
    neg_before_seg = jax.lax.cummax(jnp.where(is_new, below, 0.0))
    neg_in_seg = jax.lax.cummin(jnp.where(is_end, cum_neg, jnp.inf),
                                reverse=True) - neg_before_seg
    credit = neg_before_seg + 0.5 * neg_in_seg
    total_pos = jnp.sum(wpos)
    total_neg = jnp.sum(wneg)
    auc = jnp.sum(wpos * credit) / jnp.maximum(total_pos * total_neg, 1e-30)
    return auc


@register_metric("auc")
class AUCMetric(Metric):
    greater_is_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._is_pos = jnp.asarray((metadata.label > 0).astype(np.float32))
        self._w = (jnp.asarray(metadata.weights) if metadata.weights is not None else None)

    def eval(self, score, objective):
        return [float(_auc(score, self._is_pos, self._w))]


@register_metric("average_precision")
class AveragePrecisionMetric(AUCMetric):
    def eval(self, score, objective):
        order = jnp.argsort(-score)
        y = self._is_pos[order]
        w = self._w[order] if self._w is not None else jnp.ones_like(y)
        wpos = w * y
        cum_pos = jnp.cumsum(wpos)
        cum_all = jnp.cumsum(w)
        precision = cum_pos / jnp.maximum(cum_all, 1e-30)
        total_pos = jnp.maximum(jnp.sum(wpos), 1e-30)
        return [float(jnp.sum(precision * wpos) / total_pos)]

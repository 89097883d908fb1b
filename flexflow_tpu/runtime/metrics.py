"""PerfMetrics: per-batch training metrics, accumulated across iterations.

Reference: include/metrics_functions.h:28-44 PerfMetrics{train_all,
train_correct, cce_loss, sparse_cce_loss, mse_loss, rmse_loss, mae_loss,
start_time}; computed on-GPU per shard (metrics_functions.cu:57-230) and
reduced through chained Legion futures into a CPU UPDATE_METRICS_TASK
(model.cc:1827-1850). On TPU the per-shard compute + cross-shard reduction is
just sharded jnp reductions inside the jitted step; accumulation across steps
happens on host from the step's returned scalars.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import LossType, MetricsType


@dataclasses.dataclass
class PerfMetrics:
    train_all: int = 0
    train_correct: int = 0
    # denominator for accuracy: number of PREDICTIONS scored (== train_all
    # for per-sample classification; batch x seq for token-level tasks)
    train_pred_total: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0
    start_time: float = dataclasses.field(default_factory=time.time)

    def update(self, batch_metrics: Dict[str, float], batch_size: int):
        self.train_all += batch_size
        if "accuracy_count" in batch_metrics:
            self.train_correct += int(batch_metrics["accuracy_count"])
            self.train_pred_total += int(
                batch_metrics.get("accuracy_total", batch_size))
        for k in ("cce_loss", "sparse_cce_loss", "mse_loss", "rmse_loss", "mae_loss"):
            if k in batch_metrics:
                setattr(self, k, getattr(self, k) + float(batch_metrics[k]) * batch_size)

    def report(self, loss_type: LossType, metrics: Sequence[MetricsType]) -> str:
        """Epoch summary in the reference's print style (model.cc:1827-1850)."""
        parts = [f"train_all={self.train_all}"]
        denom = self.train_pred_total or self.train_all
        if MetricsType.METRICS_ACCURACY in metrics and denom:
            acc = 100.0 * self.train_correct / denom
            parts.append(f"accuracy={acc:.2f}% ({self.train_correct}/{denom})")
        n = max(self.train_all, 1)
        if self.sparse_cce_loss:
            parts.append(f"sparse_cce_loss={self.sparse_cce_loss / n:.4f}")
        if self.cce_loss:
            parts.append(f"cce_loss={self.cce_loss / n:.4f}")
        for m in metrics:
            if m == MetricsType.METRICS_MEAN_SQUARED_ERROR and self.mse_loss:
                parts.append(f"mse={self.mse_loss / n:.4f}")
        return "[Metrics] " + " ".join(parts)

    @property
    def accuracy(self) -> float:
        return self.train_correct / max(self.train_pred_total
                                        or self.train_all, 1)


# what a train step says of its dropless MoE layers' routing, per step and
# summed over the layers (the most rows any expert got: the max over them)
ROUTING_COUNTS = ("moe_assignments_total", "moe_experts_hit_total",
                  "moe_rows_max")


def routing_counts(group_sizes) -> Dict[str, jnp.ndarray]:
    """From each dropless MoE op's int32 (held experts,) rows per expert:
    the (token, expert) assignments that landed on experts held here, the
    held experts with at least one row, and the most rows any of them got.
    Computed inside the jitted step and carried out with its metrics."""
    return {
        "moe_assignments_total": sum(jnp.sum(s) for s in group_sizes),
        "moe_experts_hit_total": sum(jnp.sum(s > 0, dtype=jnp.int32)
                                     for s in group_sizes),
        "moe_rows_max": jnp.max(jnp.stack([jnp.max(s)
                                           for s in group_sizes])),
    }


def batch_metrics(loss_type: LossType, metric_types: Sequence[MetricsType],
                  logits, labels,
                  ignore_index: int = None) -> Dict[str, jnp.ndarray]:
    """Per-batch metric values, computed inside the jitted step (sharded).

    ignore_index (FFConfig.metrics_ignore_index): label value excluded
    from token-level accuracy — both the correct count AND the
    denominator — so padded causal-LM batches aren't diluted by pad
    positions. None = count every position."""
    out: Dict[str, jnp.ndarray] = {}
    lab = labels
    for m in metric_types:
        if m == MetricsType.METRICS_ACCURACY:
            # accuracy_total carries the PREDICTION count: for token-level
            # tasks (labels per position, e.g. causal-LM training) it is
            # batch x seq, not batch — without it the epoch report divides
            # token-correct counts by sample counts and prints >100%
            if loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
                li = lab.astype(jnp.int32)
                if li.ndim == logits.ndim:
                    li = li[..., 0]
                pred = jnp.argmax(logits, axis=-1)
                if ignore_index is not None:
                    live = li != ignore_index
                    out["accuracy_count"] = jnp.sum((pred == li) & live)
                    out["accuracy_total"] = jnp.sum(live).astype(jnp.int32)
                else:
                    out["accuracy_count"] = jnp.sum(pred == li)
                    out["accuracy_total"] = jnp.asarray(pred.size, jnp.int32)
            elif loss_type == LossType.LOSS_CATEGORICAL_CROSSENTROPY:
                pred = jnp.argmax(logits, axis=-1)
                out["accuracy_count"] = jnp.sum(pred == jnp.argmax(lab, axis=-1))
                out["accuracy_total"] = jnp.asarray(pred.size, jnp.int32)
            else:
                # regression "accuracy": |err| < 0.5 (metrics_functions.cu MSE path)
                out["accuracy_count"] = jnp.sum(
                    jnp.all(jnp.abs(logits - lab) < 0.5,
                            axis=tuple(range(1, logits.ndim))))
                out["accuracy_total"] = jnp.asarray(logits.shape[0], jnp.int32)
        elif m == MetricsType.METRICS_CATEGORICAL_CROSSENTROPY:
            logp = jax.nn.log_softmax(logits, axis=-1)
            out["cce_loss"] = -jnp.mean(jnp.sum(lab * logp, axis=-1))
        elif m == MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY:
            logp = jax.nn.log_softmax(logits, axis=-1)
            li = lab.astype(jnp.int32)
            if li.ndim == logits.ndim:
                li = li[..., 0]
            out["sparse_cce_loss"] = jnp.mean(
                -jnp.take_along_axis(logp, li[..., None], axis=-1))
        elif m == MetricsType.METRICS_MEAN_SQUARED_ERROR:
            out["mse_loss"] = jnp.mean(jnp.square(logits - lab))
        elif m == MetricsType.METRICS_ROOT_MEAN_SQUARED_ERROR:
            out["rmse_loss"] = jnp.sqrt(jnp.mean(jnp.square(logits - lab)))
        elif m == MetricsType.METRICS_MEAN_ABSOLUTE_ERROR:
            out["mae_loss"] = jnp.mean(jnp.abs(logits - lab))
    return out


_KERAS_METRIC_NAMES = {
    "accuracy": MetricsType.METRICS_ACCURACY,
    "categorical_crossentropy": MetricsType.METRICS_CATEGORICAL_CROSSENTROPY,
    "sparse_categorical_crossentropy": MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY,
    "mean_squared_error": MetricsType.METRICS_MEAN_SQUARED_ERROR,
    "mse": MetricsType.METRICS_MEAN_SQUARED_ERROR,
    "root_mean_squared_error": MetricsType.METRICS_ROOT_MEAN_SQUARED_ERROR,
    "mean_absolute_error": MetricsType.METRICS_MEAN_ABSOLUTE_ERROR,
}


def metrics_from_names(names) -> List[MetricsType]:
    out = []
    for n in names:
        out.append(n if isinstance(n, MetricsType) else _KERAS_METRIC_NAMES[n])
    return out

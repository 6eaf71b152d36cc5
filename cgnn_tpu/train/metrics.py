"""Meters and eval metrics (SURVEY.md §2 component 9).

Console-visible quantities match the reference's operator experience: loss,
MAE (regression) or accuracy/AUC/F1 (classification), batch/data timing.
sklearn is not installed; AUC/F1 are implemented in-tree on numpy.
"""

from __future__ import annotations

import numpy as np


# jitted whole-dict add, cached per key-structure: K per-key `a + b`
# dispatches per chunk become ONE fused dispatch (the scan driver's
# per-chunk host fixed cost: every dispatch is host work on the critical
# path)
_ACCUM_FNS: dict = {}


def _accum_fn(keys: tuple):
    fn = _ACCUM_FNS.get(keys)
    if fn is None:
        import jax

        fn = _ACCUM_FNS[keys] = jax.jit(
            lambda a, b: {k: a[k] + b[k] for k in keys}
        )
    return fn


def accumulate_on_device(dev_sums: dict | None, metrics: dict) -> dict:
    """Add a step's metric dict into device-side running sums.

    The adds are dispatched asynchronously — no host<->device round trip
    per step (which would throttle dispatch pipelining). The
    steady-state case (same key set chunk after chunk) goes through one
    jitted dict-add — one dispatch instead of one per key. Tolerates
    keys appearing mid-epoch (mixed step bodies) via the per-key
    fallback."""
    if dev_sums is None:
        return dict(metrics)
    if dev_sums.keys() == metrics.keys():
        try:
            return _accum_fn(tuple(sorted(metrics)))(dev_sums, metrics)
        except TypeError:
            pass  # non-jittable values (python floats mid-migration)
    for k, v in metrics.items():
        dev_sums[k] = dev_sums[k] + v if k in dev_sums else v
    return dev_sums


def fetch_device_sums(dev_sums: dict | None) -> dict:
    """One blocking fetch of the accumulated sums -> python floats.

    The scalars are PACKED into a single device array first (one stack
    dispatch) so the fetch is ONE transfer: a dict device_get moves each
    scalar separately, and each scalar is then its own host-device
    round trip (~17 chunk dicts x 4 keys per epoch in the scan driver).
    """
    import jax
    import jax.numpy as jnp

    if dev_sums is None:
        return {}
    keys = sorted(dev_sums)
    packed = jnp.stack(
        [jnp.asarray(dev_sums[k], jnp.float32) for k in keys]
    )
    # np.array, not asarray: device_get ALIASES device buffers on CPU
    # (graftcheck GC-ALIAS) and these sums outlive the next dispatch
    vals = np.array(jax.device_get(packed))
    return dict(zip(keys, (float(v) for v in vals)))


def means_from_sums(sums: dict, steps: int) -> dict:
    """Epoch metric means from '<name>_sum' totals: each sum averages by
    its matching '<name>_count' when present (e.g. force MAE counts atom
    components, not graphs), else by the global 'count'."""
    count = max(sums.get("count", 1.0), 1.0)
    out = {
        k[: -len("_sum")]: v
        / max(sums.get(k[: -len("_sum")] + "_count", count), 1.0)
        for k, v in sums.items()
        if k.endswith("_sum")
    }
    out["count"] = sums.get("count", 0.0)
    out["steps"] = steps
    return out


class AverageMeter:
    """Running (value, average) meter — the reference's training display."""

    def __init__(self, name: str = ""):
        self.name = name
        self.reset()

    def reset(self):
        self.val = 0.0
        self.sum = 0.0
        self.count = 0.0
        self.avg = 0.0

    def update(self, val: float, n: float = 1):
        self.val = float(val)
        self.sum += float(val) * n
        self.count += n
        self.avg = self.sum / max(self.count, 1e-12)


def mae(pred: np.ndarray, target: np.ndarray) -> float:
    return float(np.mean(np.abs(np.asarray(pred) - np.asarray(target))))


def _binary_auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Rank-based AUC (Mann-Whitney), ties handled by midranks."""
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty_like(order, dtype=np.float64)
    sorted_scores = scores[order]
    i = 0
    while i < len(sorted_scores):
        j = i
        while j + 1 < len(sorted_scores) and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        ranks[order[i : j + 1]] = 0.5 * (i + j) + 1.0
        i = j + 1
    pos = labels == 1
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def class_eval(log_probs: np.ndarray, labels: np.ndarray) -> dict:
    """accuracy / precision / recall / F1 / AUC for binary classification.

    Mirrors the reference's ``class_eval`` metric set (computed there with
    sklearn, which is unavailable in this image).
    """
    log_probs = np.asarray(log_probs)
    labels = np.asarray(labels).astype(int)
    pred = log_probs.argmax(axis=-1)
    acc = float((pred == labels).mean()) if len(labels) else float("nan")
    out = {"accuracy": acc}
    if log_probs.shape[-1] == 2:
        tp = float(((pred == 1) & (labels == 1)).sum())
        fp = float(((pred == 1) & (labels == 0)).sum())
        fn = float(((pred == 0) & (labels == 1)).sum())
        precision = tp / (tp + fp) if tp + fp else float("nan")
        recall = tp / (tp + fn) if tp + fn else float("nan")
        f1 = (
            2 * precision * recall / (precision + recall)
            if precision == precision and recall == recall and precision + recall
            else float("nan")
        )
        out.update(
            precision=precision,
            recall=recall,
            f1=f1,
            auc=_binary_auc(np.exp(log_probs[:, 1]), labels),
        )
    return out

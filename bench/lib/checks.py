"""The numbers that decide ``correct``, each compared with its limit.

Norm gaps follow one rule: per leaf, the gap between the two sides'
norms, over the larger of the reference's norm of that leaf and the
median of the reference's leaf norms in the same set. Leaves whose
reference first moment is under a thousandth of the median leaf's are
left out of the gradient and change sets (enable masks, which never
move): the rule reads the reference, not names.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

EXCLUDE_BELOW = 1e-3


def rel_gap(prog: Iterable[np.ndarray], ref: Iterable[np.ndarray]) -> float:
    """Largest |p − r| / |r| over all elements."""
    worst = 0.0
    for p, r in zip(prog, ref):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        worst = max(worst, _finite(np.max(np.abs(p - r) / np.abs(r))))
    return worst


def _finite(x) -> float:
    """A number that fails every limit where it is not finite."""
    x = float(x)
    return x if np.isfinite(x) else float("inf")


def norm_gap(pairs: List[Tuple[np.ndarray, np.ndarray]]) -> float:
    """Worst per-leaf norm gap over (program leaf, reference leaf) pairs."""
    if not pairs:
        return 0.0
    np_ = [float(np.linalg.norm(np.asarray(p, np.float64))) for p, _ in pairs]
    nr = [float(np.linalg.norm(np.asarray(r, np.float64))) for _, r in pairs]
    med = float(np.median(nr))
    return max(_finite(abs(a - b) / max(b, med)) for a, b in zip(np_, nr))


def moving_paths(ref_mu: Dict[int, Dict[str, np.ndarray]]) -> List[str]:
    """Leaf paths whose reference first moment (largest over clients) is
    at least a thousandth of the median leaf's."""
    paths = sorted(next(iter(ref_mu.values())))
    top = {p: max(float(np.linalg.norm(m[p])) for m in ref_mu.values())
           for p in paths}
    med = float(np.median(list(top.values())))
    return [p for p in paths if top[p] >= EXCLUDE_BELOW * med]


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Compare the program's first rounds with the reference's.

    ``loss_gap``: every client's every local-step loss, relative.
    ``grad_gap``: each first-round client's AdamW first moment per leaf
    after the round, as the optimizer holds it.
    ``change_gap``: after the last compared round, each touched client's
    trainable row and pending upload, and the global, as changes from the
    start, per leaf.
    ``eval_miss``: held-out rows scored differently, summed over clients
    and rounds."""
    for a, b in zip(prog["ids"], ref["ids"]):
        if not np.array_equal(a, b):
            raise AssertionError(f"cohorts differ: {a} vs {b}")
    keep = moving_paths(ref["mu1"])
    grad = [(prog["mu1"][c][p], ref["mu1"][c][p])
            for c in ref["mu1"] for p in keep]
    s0 = ref["shared0"]
    change = []
    for c, row in ref["rows"].items():
        row0 = ref["rows0"][c]
        for p in keep:
            if p in row and p.startswith("local/"):
                change.append((prog["rows"][c][p] - row0[p], row[p] - row0[p]))
        for p, v in ref["pending"].get(c, {}).items():
            if p in keep:
                change.append((prog["pending"][c][p] - s0[p], v - s0[p]))
    for p, v in ref["global"].items():
        if p in keep:
            change.append((prog["global"][p] - s0[p], v - s0[p]))
    miss = sum(int(np.abs(np.asarray(a) - np.asarray(b)).sum())
               for a, b in zip(prog["eval_correct"], ref["eval_correct"]))
    return {"loss_gap": rel_gap(prog["losses"], ref["losses"]),
            "grad_gap": norm_gap(grad),
            "change_gap": norm_gap(change),
            "eval_miss": float(miss)}


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position. ``ref_logits`` (..., V), ``served``
    (...) token ids."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, np.asarray(served)[..., None],
                             -1)[..., 0]
    return _finite(np.max(best - got))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [[name, value, limit], ...]): every number at or under its
    limit."""
    rows = [[k, float(numbers[k]), float(limits[k])] for k in limits]
    return all(v <= lim for _, v, lim in rows), rows

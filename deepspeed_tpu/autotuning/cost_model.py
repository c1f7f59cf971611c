"""Cost model for model-based autotuning.

Counterpart of ``deepspeed/autotuning/tuner/cost_model.py`` — the reference
fits an XGBoost ranking model over numeric config features and uses it to
order unevaluated candidates. xgboost is not in this image (and is overkill
for the small spaces the tuner explores), so the same role is filled by a
ridge regression over standardized numeric features plus their logs and
pairwise products — enough capacity to rank monotone-ish throughput
landscapes (micro-batch scaling, ZeRO-stage overhead) from a handful of
measurements, with deterministic behavior.
"""

from typing import Dict, List, Sequence

import numpy as np


def config_features(flat_config: Dict[str, float]) -> List[float]:
    """Numeric feature vector from a flattened config (reference
    ``model_based_tuner.py:find_estimated_top_configs``: every numeric field
    becomes a feature, in key order)."""
    vals = [float(v) for k, v in sorted(flat_config.items())
            if isinstance(v, (int, float)) and not isinstance(v, bool)]
    return vals


def flatten_config(cfg: Dict, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in cfg.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten_config(v, key + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def rank_by_cost_model(measured, cand_feats, min_measured: int = 6):
    """Order candidate indices predicted-best-first, or None when the model
    has too few measurements to rank (callers keep declaration order).
    ``measured``: [(features, score)]; ``mfu_tuner``'s ranking core."""
    if len(measured) < min_measured or len(cand_feats) <= 1:
        return None
    model = RidgeCostModel().fit([m[0] for m in measured],
                                 [m[1] for m in measured])
    preds = model.predict(cand_feats)
    return [i for _, i in sorted(
        zip(preds, range(len(cand_feats))), key=lambda t: -t[0])]


class RidgeCostModel:
    """fit(X, y) / predict(X) with the expanded feature map; y is normalized
    to its max (the reference does the same before fitting)."""

    def __init__(self, l2: float = 1e-3):
        self.l2 = l2
        self._w = None
        self._mu = None
        self._sigma = None

    def _expand(self, X: np.ndarray) -> np.ndarray:
        logs = np.log2(np.maximum(np.abs(X), 1e-9))
        feats = [X, logs]
        n = X.shape[1]
        for i in range(n):
            for j in range(i, n):
                feats.append((X[:, i] * X[:, j])[:, None])
        return np.concatenate([np.ones((X.shape[0], 1))] +
                              [np.asarray(f).reshape(X.shape[0], -1)
                               for f in feats], axis=1)

    def fit(self, xs: Sequence[Sequence[float]], ys: Sequence[float]):
        X = np.asarray(xs, np.float64)
        y = np.asarray(ys, np.float64)
        y = y / max(float(np.max(np.abs(y))), 1e-9)
        self._mu = X.mean(axis=0)
        self._sigma = np.where(X.std(axis=0) > 0, X.std(axis=0), 1.0)
        Phi = self._expand((X - self._mu) / self._sigma)
        A = Phi.T @ Phi + self.l2 * np.eye(Phi.shape[1])
        self._w = np.linalg.solve(A, Phi.T @ y)
        return self

    def predict(self, xs: Sequence[Sequence[float]]) -> np.ndarray:
        X = np.asarray(xs, np.float64)
        Phi = self._expand((X - self._mu) / self._sigma)
        return Phi @ self._w

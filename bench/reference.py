"""Plain float64 reference of the benchmark's problem, on the host.

ℓ2-regularised logistic regression (paper Eq. 11) over the rows the clients
hold: f(w) = mean_j log(1 + exp(-y_j x_j·w)) + γ/2 ‖w‖². With equal IID
blocks the federated objective Σ_k (n_k/N) f_k is this mean. Newton's method
with the exact Hessian from w = 0 gives w* to float64 rounding in a few
steps. Nothing here imports the program under test.
"""
from __future__ import annotations

import numpy as np

NEWTON_MAX_STEPS = 50


def _sigmoid(z: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def objective(w: np.ndarray, X: np.ndarray, y: np.ndarray,
              gamma: float) -> float:
    z = y * (X @ w)
    return float(np.mean(np.logaddexp(0.0, -z)) + 0.5 * gamma * w @ w)


def gradient(w: np.ndarray, X: np.ndarray, y: np.ndarray,
             gamma: float) -> np.ndarray:
    z = y * (X @ w)
    return -(X.T @ (y * _sigmoid(-z))) / X.shape[0] + gamma * w


def newton_solve(X: np.ndarray, y: np.ndarray, gamma: float) -> np.ndarray:
    """w* of the objective over rows X [N, d], labels y [N], in float64."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    n, d = X.shape
    w = np.zeros(d)
    for _ in range(NEWTON_MAX_STEPS):
        z = y * (X @ w)
        s = _sigmoid(-z)
        g = -(X.T @ (y * s)) / n + gamma * w
        H = (X.T * (s * (1.0 - s))) @ X / n + gamma * np.eye(d)
        step = np.linalg.solve(H, g)
        w = w - step
        if np.linalg.norm(step) <= 1e-15 * max(np.linalg.norm(w), 1.0):
            break
    return w


def rel_error(w: np.ndarray, w_star: np.ndarray) -> float:
    """‖w − w*‖ / ‖w*‖ in float64."""
    w_star = np.asarray(w_star, np.float64)
    return float(np.linalg.norm(np.asarray(w, np.float64) - w_star)
                 / np.linalg.norm(w_star))

"""Inputs of a benchmark run: the dataset and its split over clients.

The generator is a copy of the synthetic LIBSVM stand-in that
``repro.data.synthetic`` uses (correlated features with a decaying
spectrum, a random separator, class rebalancing and 2% label noise), so the
yardstick stays fixed when the program's copy changes. The IID split is the
paper's (App. D.2): a random permutation dealt into K equal blocks, with the
remainder rows dropped.
"""
from __future__ import annotations

import numpy as np


def make_dataset(n: int, d: int, pos_frac: float, scale: float,
                 seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(X [n, d] float32, y [n] in {-1, +1} float32) drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    spectrum = (1.0 / np.sqrt(1.0 + np.arange(d))).astype(np.float32)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    latent = rng.standard_normal((n, d)).astype(np.float32)
    X = (latent * spectrum) @ basis.T * scale
    w_true = rng.standard_normal(d).astype(np.float32)
    logits = X @ w_true / np.sqrt(d)
    thresh = np.quantile(logits, 1.0 - pos_frac)
    y = np.where(logits > thresh, 1.0, -1.0).astype(np.float32)
    flip = rng.random(n) < 0.02
    y[flip] = -y[flip]
    return X, y


def iid_split(X: np.ndarray, y: np.ndarray, num_clients: int,
              seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Client blocks (X [K, n_k, d], y [K, n_k]) of an IID split."""
    perm = np.random.default_rng(seed).permutation(X.shape[0])
    n_k = X.shape[0] // num_clients
    keep = perm[:num_clients * n_k]
    return (X[keep].reshape(num_clients, n_k, X.shape[1]),
            y[keep].reshape(num_clients, n_k))

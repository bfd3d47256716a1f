"""Clustered unit vectors for angular search (a NYTimes-like corpus).

ANN-Benchmarks' ``nytimes-256-angular`` holds 290,000 dense 256-dimensional
document vectors compared by angle; topic structure makes the set skewed:
a few large clusters and many small ones. This generator keeps those
properties: cluster centres on the unit sphere with power-law weights
(Pareto, the skew convention of the paper's surrogates), within-cluster
spread in a ``latent_dim``-dimensional subspace shared by all clusters, a
small isotropic noise in all 256 dimensions, and unit normalisation. The
centres, weights and subspace come from ``model_seed``; the rows come from
the caller's generator.
"""
from __future__ import annotations

import numpy as np


def _model(spec: dict):
    rng = np.random.default_rng(spec["model_seed"])
    k, m, d = spec["clusters"], spec["latent_dim"], spec["dim"]
    centers = rng.normal(0.0, 1.0, (k, d))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    weights = rng.pareto(spec["pareto_shape"], k) + 1.0
    weights /= weights.sum()
    basis = rng.normal(0.0, 1.0 / np.sqrt(d), (m, d))
    return centers, weights, basis


def make(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unit rows, float32 [n, dim]."""
    centers, weights, basis = _model(spec)
    d = spec["dim"]
    comp = rng.choice(centers.shape[0], size=n, p=weights)
    z = rng.normal(0.0, spec["local_scale"], (n, basis.shape[0]))
    x = (centers[comp] + z @ basis
         + rng.normal(0.0, spec["noise_scale"] / np.sqrt(d), (n, d)))
    x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x.astype(np.float32)

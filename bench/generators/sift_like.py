"""SIFT-like descriptors: non-negative, integer-valued, low intrinsic dimension.

A SIFT descriptor is a 4x4x8 histogram of gradient orientations (128 bins),
normalised to unit length, clamped at 0.2 and normalised again, then scaled
by 512 and rounded to integers in 0..255 (Lowe 2004; the SIFT1M base set of
ANN-Benchmarks ``sift-128-euclidean`` stores these integers as float32).
Real descriptors of natural images lie near a low-dimensional set, which is
what lets graph indices reach high recall on them.

This generator keeps those documented properties and nothing else: a
Gaussian mixture in ``latent_dim`` dimensions, lifted to 128 bins by one
fixed linear map, plus a small full-dimensional noise, rectified (histogram
bins are non-negative, many are zero), then put through Lowe's
normalise-clamp-normalise-scale-round pipeline. The mixture and the map come
from ``model_seed``, so every run seed draws from the same distribution; the
rows themselves come from the caller's generator.
"""
from __future__ import annotations

import numpy as np


def _model(spec: dict):
    rng = np.random.default_rng(spec["model_seed"])
    m, d = spec["latent_dim"], spec["dim"]
    centers = rng.normal(0.0, spec["center_scale"], (spec["clusters"], m))
    lift = rng.normal(0.0, 1.0 / np.sqrt(m), (m, d))
    return centers, lift


def make(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` rows of float32 integers in [0, 255], shape [n, dim]."""
    centers, lift = _model(spec)
    d = spec["dim"]
    comp = rng.integers(0, centers.shape[0], n)
    z = centers[comp] + rng.normal(0.0, spec["local_scale"], (n, lift.shape[0]))
    h = z @ lift + rng.normal(0.0, spec["noise_scale"], (n, d))
    x = np.maximum(h + spec["shift"], 0.0)
    return lowe_quantize(x, spec["clamp"], spec["scale"])


def lowe_quantize(x: np.ndarray, clamp: float, scale: float) -> np.ndarray:
    """Lowe's descriptor normalisation: unit length, clamp, unit length,
    scale, round to integers in [0, 255]."""
    x = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    x = np.minimum(x, clamp)
    x = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return np.clip(np.rint(x * scale), 0, 255).astype(np.float32)

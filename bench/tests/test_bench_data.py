"""The seeded generators and the plain reference (CPU, tiny sizes)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench_tiny import BENCH
from harness import reference, registry

CONFIGS = ("sift-l2", "nytimes-cos")


def _gen(config: str):
    spec = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    g = spec["generator"]
    return registry.plugin("generators", g["kind"], BENCH), g


@pytest.mark.parametrize("config", CONFIGS)
def test_generator_is_deterministic_per_seed(config):
    mod, g = _gen(config)
    a = mod.make(g, 300, np.random.default_rng(2**31 + 5))
    b = mod.make(g, 300, np.random.default_rng(2**31 + 5))
    c = mod.make(g, 300, np.random.default_rng(2**31 + 6))
    assert a.dtype == np.float32 and a.shape == (300, g["dim"])
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("config", CONFIGS)
def test_generator_model_is_fixed_across_seeds(config):
    """Seeds draw rows from one distribution: the model comes from
    ``model_seed`` alone, so per-bin second moments agree across seeds."""
    mod, g = _gen(config)
    a = (mod.make(g, 4000, np.random.default_rng(1)) ** 2).mean(axis=0)
    b = (mod.make(g, 4000, np.random.default_rng(2)) ** 2).mean(axis=0)
    assert np.abs(a - b).max() < 0.2 * a.max()


def test_sift_like_rows_are_lowe_descriptors():
    mod, g = _gen("sift-l2")
    x = mod.make(g, 2000, np.random.default_rng(3))
    assert (x >= 0).all() and (x <= 255).all()
    np.testing.assert_array_equal(x, np.rint(x))
    assert 0.2 < (x == 0).mean() < 0.6
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 512.0, rtol=0.02)


def test_clustered_sphere_rows_are_unit():
    mod, g = _gen("nytimes-cos")
    x = mod.make(g, 2000, np.random.default_rng(3))
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)


def _numpy_scores(x, q, metric):
    x = x.astype(np.float64)
    q = q.astype(np.float64)
    if metric == "cos":
        x = x / np.linalg.norm(x, axis=1, keepdims=True)
    s = q @ x.T
    if metric == "l2":
        s = 2.0 * s - (x * x).sum(axis=1)[None, :]
    return s


@pytest.mark.parametrize("metric", ["l2", "cos", "ip"])
def test_reference_topk_matches_numpy(metric):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 24)).astype(np.float32)
    q = rng.normal(size=(37, 24)).astype(np.float32)
    live = rng.random(500) < 0.7
    s, i = reference.exact_topk(x, live, q, 10, metric, block=16)
    ref = np.where(live[None, :], _numpy_scores(x, q, metric), -np.inf)
    want = np.argsort(-ref, axis=1, kind="stable")[:, :10]
    np.testing.assert_array_equal(i, want)
    np.testing.assert_allclose(s, np.take_along_axis(ref, want, 1),
                               rtol=1e-5, atol=1e-4)
    got = reference.scores_of(x, q, i, metric, block=16)
    np.testing.assert_allclose(got, s, rtol=1e-5, atol=1e-4)


def test_reference_scores_of_null_is_nan():
    x = np.ones((8, 4), np.float32)
    out = reference.scores_of(x, x[:2], np.array([[0, -1], [-1, 3]]), "l2")
    assert np.isnan(out[0, 1]) and np.isnan(out[1, 0])
    assert out[0, 0] == pytest.approx(4.0)


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_lower_precision_changes_scores_of_continuous_rows(precision):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(200, 32)).astype(np.float32)
    q = rng.normal(size=(8, 32)).astype(np.float32)
    rows = np.tile(np.arange(10), (8, 1))
    hi = reference.scores_of(x, q, rows, "l2")
    lo = reference.scores_of(x, q, rows, "l2", precision=precision)
    assert np.abs(hi - lo).max() > 1e-4 * np.abs(hi).max()


def test_bf16_is_exact_on_sift_integers():
    """Integers up to 256 are exact in bfloat16 and their products sum
    exactly in float32, so a bf16 control cannot fail on SIFT's own
    queries; the sift-l2 control therefore steps down to int8 (PERF.md)."""
    mod, g = _gen("sift-l2")
    x = mod.make(g, 300, np.random.default_rng(4))
    rows = np.tile(np.arange(10), (8, 1))
    hi = reference.scores_of(x, x[:8], rows, "l2")
    lo = reference.scores_of(x, x[:8], rows, "l2", precision="bf16")
    np.testing.assert_array_equal(hi, lo)
    q8 = reference.scores_of(x, x[:8], rows, "l2", precision="int8")
    assert np.abs(hi - q8).max() > 0

"""int8 gradient compression: quantization error bounds + exact reduction.

Also covers the *deterministic* per-row vector-code quantizer (DESIGN.md
§10) re-exported here next to the stochastic gradient quantizer.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import (
    dequantize,
    dequantize_rows,
    quantize_int8,
    quantize_rows,
    wire_bytes_saved,
)


def test_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = quantize_int8(x, jax.random.PRNGKey(0))
    err = np.asarray(jnp.abs(dequantize(q, s) - x))
    assert err.max() <= float(s) * 1.01  # ≤ one quantization bin


def test_stochastic_rounding_unbiased():
    x = jnp.full((20000,), 0.3)
    q, s = quantize_int8(x, jax.random.PRNGKey(1))
    mean = float(jnp.mean(dequantize(q, s)))
    assert abs(mean - 0.3) < 2e-3


def test_stochastic_rounding_unbiased_over_keys():
    """E_key[dequantize(quantize(x, key))] == x elementwise: the mean over
    many independent keys of a FIXED vector must converge to the vector
    (the per-key test above only checks the mean over elements)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.uniform(-1.0, 1.0, size=(64,)).astype(np.float32))
    n_keys = 4000
    keys = jax.random.split(jax.random.PRNGKey(7), n_keys)
    deq = jax.vmap(lambda k: dequantize(*quantize_int8(x, k)))(keys)
    mean = np.asarray(jnp.mean(deq, axis=0))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    # CLT: per-element sd ≤ scale/2, so 5·scale/(2·√n) is a ~5σ band
    tol = 5.0 * scale / (2.0 * np.sqrt(n_keys))
    np.testing.assert_allclose(mean, np.asarray(x), atol=tol)


def test_compressed_psum_vs_fp32_psum_small_trees():
    """compressed_psum == fp32 psum-mean up to the local quantization error
    (the int8 reduction itself is exact), on a small multi-leaf tree."""
    from repro.distributed.compression import compressed_psum

    mesh = jax.make_mesh((1,), ("d",))
    rng = np.random.default_rng(3)
    tree = {
        "w": jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(16,)).astype(np.float32) * 1e-3),
    }

    def f(grads):
        comp = compressed_psum(grads, jax.random.PRNGKey(0), "d")
        exact = jax.tree.map(
            lambda g: jax.lax.psum(g, "d") / jax.lax.psum(1, "d"), grads)
        return comp, exact

    P = jax.sharding.PartitionSpec
    comp, exact = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False,
    ))(tree)
    for name in tree:
        scale = float(jnp.max(jnp.abs(tree[name]))) / 127.0
        np.testing.assert_allclose(
            np.asarray(comp[name]), np.asarray(exact[name]),
            atol=1.01 * scale,
        )


def test_quantize_rows_roundtrip_and_determinism():
    """Row quantizer: error ≤ scale/2 per element, deterministic (no key),
    zero rows → (zero codes, positive sentinel scale) — distinct from the
    freed-slot (0, 0.0) scrub (DESIGN.md §10, scheme v2)."""
    from repro.core.quantize import ZERO_ROW_SCALE

    x = np.random.default_rng(4).normal(size=(50, 24)).astype(np.float32)
    x[7] = 0.0
    xj = jnp.asarray(x)
    c1, s1 = quantize_rows(xj)
    c2, s2 = quantize_rows(xj)
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    err = np.abs(np.asarray(dequantize_rows(c1, s1)) - x)
    assert (err <= np.asarray(s1)[:, None] * 0.5 + 1e-7).all()
    assert (np.asarray(c1)[7] == 0).all()
    assert float(s1[7]) == float(ZERO_ROW_SCALE) > 0.0
    # stacked leading dims (the ShardedSession layout) quantize identically
    cs, ss = quantize_rows(jnp.asarray(x.reshape(2, 25, 24)))
    assert np.array_equal(np.asarray(cs).reshape(50, 24), np.asarray(c1))
    assert np.array_equal(np.asarray(ss).reshape(50), np.asarray(s1))


def test_wire_bytes():
    g = {"a": jnp.zeros((100,)), "b": jnp.zeros((50,))}
    fp32, int8 = wire_bytes_saved(g)
    assert fp32 == 600 and int8 < fp32 / 3


def test_compressed_psum_multi_device():
    """Single-device psum (axis of size 1) must be ≈ identity."""
    from repro.distributed.compression import compressed_psum

    mesh = jax.make_mesh((1,), ("d",))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .normal(size=(64,)).astype(np.float32))}

    def f(grads):
        return compressed_psum(grads, jax.random.PRNGKey(0), "d")

    out = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False,
    ))(g)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(
        np.asarray(out["w"]), np.asarray(g["w"]), atol=1.01 * scale
    )

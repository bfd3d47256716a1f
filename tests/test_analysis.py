"""Jaxpr cost analyzer: closed-form checks + agreement with XLA on loop-free
graphs (the basis of the §Roofline numbers)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.analysis import cost_of


def test_matmul_exact():
    A = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    B = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = cost_of(lambda a, b: a @ b, A, B, io_bytes=False)
    assert c.flops == 2 * 256 * 512 * 128
    assert c.hbm_bytes == 4 * (256 * 512 + 512 * 128 + 256 * 128)


def test_scan_multiplies_trip_count():
    A = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a):
        def body(c, _):
            return c @ a, None
        c, _ = jax.lax.scan(body, a, None, length=7)
        return c

    c = cost_of(f, A, io_bytes=False)
    assert c.flops == 7 * 2 * 128**3


def test_agrees_with_xla_on_loop_free():
    """Sanity: analyzer within 2% of XLA cost_analysis for a plain matmul
    chain (no loops — the regime where XLA's number is trustworthy)."""
    A = jax.ShapeDtypeStruct((384, 384), jnp.float32)

    def f(a, b):
        return jnp.tanh(a @ b) @ b

    ours = cost_of(f, A, A, io_bytes=False).flops
    xla = jax.jit(f).lower(A, A).compile().cost_analysis()["flops"]
    assert abs(ours - xla) / xla < 0.02


def test_gather_counts_bytes_not_flops():
    T = jax.ShapeDtypeStruct((1000, 64), jnp.float32)
    I = jax.ShapeDtypeStruct((32,), jnp.int32)
    c = cost_of(lambda t, i: t[i], T, I, io_bytes=False)
    assert c.gather_bytes == 32 * 64 * 4
    assert c.flops < 1e4


def test_lm_train_flops_close_to_6nd():
    """End-to-end: analyzer FLOPs for a smoke LM train step ≈ 6·N·D + attn."""
    from repro.configs import registry as reg
    from repro.models import transformer as tfm
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.steps import make_lm_train_step

    spec = reg.get_arch("qwen3-1.7b")
    cfg = spec.smoke_config()
    B, S = 4, 64
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)
    )
    opt = jax.eval_shape(lambda: adamw_init(params))
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "mask": jax.ShapeDtypeStruct((B, S), jnp.bool_),
    }
    c = cost_of(make_lm_train_step(cfg, AdamWConfig()), params, opt, batch)
    n_params = cfg.n_params()
    model_flops = 6 * n_params * B * S
    # causal blockwise attention wastes ≤2× on masked tiles; remat recomputes
    # ≤1 extra fwd; so expect 1× ≤ ratio ≤ ~3.5×
    ratio = c.flops / model_flops
    assert 0.9 < ratio < 4.0, ratio

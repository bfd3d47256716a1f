"""Similarity measures f(x, q) — paper §3 (higher is better, Eq. 1).

All scoring is expressed as a *similarity* (argmax form):
  l2  : f(x,q) = -||x-q||^2      (squared L2 — monotone in L2)
  ip  : f(x,q) = <x, q>          (MIPS)
  cos : f(x,q) = <x, q>/(|x||q|) (vectors are pre-normalized at insert, so
                                  this reduces to ip at query time)

The L2 form is computed as 2<x,q> - ||x||^2 (dropping the query-constant
||q||^2) so the batched path is a pure matmul against the cached sqnorms —
this is what makes the TPU port MXU-bound instead of VPU-bound.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)
# fp32 scores mean fp32 products: a TPU's default matmul precision would
# round the operands to bf16 (CPU backends ignore the flag)
HIGHEST = jax.lax.Precision.HIGHEST


def sqnorm(x: jax.Array) -> jax.Array:
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)


def normalize(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    n = jnp.sqrt(jnp.maximum(sqnorm(x), eps))
    return x / n[..., None].astype(x.dtype)


def pair_score(x: jax.Array, q: jax.Array, metric: str) -> jax.Array:
    """Score between broadcastable batches of vectors. fp32 accumulate."""
    x32, q32 = x.astype(jnp.float32), q.astype(jnp.float32)
    dot = jnp.sum(x32 * q32, axis=-1)
    if metric == "l2":
        return 2.0 * dot - sqnorm(x32)  # + const(||q||^2), dropped
    if metric in ("ip", "cos"):
        return dot
    raise ValueError(metric)


def scores_vs_rows(
    rows: jax.Array,       # f32[n, dim] gathered candidate vectors
    row_sqnorms: jax.Array,  # f32[n]
    q: jax.Array,          # f32[dim]
    metric: str,
) -> jax.Array:
    """Scores of one query against n gathered rows (beam-expansion path)."""
    dot = jnp.matmul(rows.astype(jnp.float32), q.astype(jnp.float32),
                     precision=HIGHEST)
    if metric == "l2":
        return 2.0 * dot - row_sqnorms
    return dot


def score_matrix(
    x: jax.Array,          # f32[m, dim] database block
    x_sqnorms: jax.Array,  # f32[m]
    q: jax.Array,          # f32[b, dim] query block
    metric: str,
) -> jax.Array:
    """[b, m] score matrix — the MXU-form bulk path (ground truth, rebuild,
    DLRM retrieval_cand)."""
    dots = jnp.matmul(q.astype(jnp.float32), x.astype(jnp.float32).T,
                      precision=HIGHEST)
    if metric == "l2":
        return 2.0 * dots - x_sqnorms[None, :]
    return dots


def true_l2(score: jax.Array, q_sqnorm: jax.Array) -> jax.Array:
    """Recover ||x-q||^2 >= 0 from the l2 score (for reporting only)."""
    return jnp.maximum(q_sqnorm - score, 0.0)

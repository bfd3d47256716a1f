"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth the kernels are allclose-checked against in
``tests/test_kernels.py`` across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")
HIGHEST = jax.lax.Precision.HIGHEST  # exact fp32 products on every backend


def ref_score_matrix(
    x: jax.Array,      # [M, d] database
    xsq: jax.Array,    # [M]    ||x||^2 (used for l2)
    q: jax.Array,      # [B, d] queries
    metric: str = "l2",
) -> jax.Array:
    """[B, M] similarity scores (2<q,x> - ||x||^2 for l2; <q,x> otherwise)."""
    dots = jnp.matmul(q.astype(jnp.float32), x.astype(jnp.float32).T,
                      precision=HIGHEST)
    if metric == "l2":
        return 2.0 * dots - xsq.astype(jnp.float32)[None, :]
    return dots


def ref_score_topk(
    x: jax.Array, xsq: jax.Array, q: jax.Array, k: int, metric: str = "l2"
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k of the score matrix: (scores f32[B,k], ids i32[B,k])."""
    s = ref_score_matrix(x, xsq, q, metric)
    top_s, top_i = jax.lax.top_k(s, k)
    return top_s, top_i.astype(jnp.int32)


def ref_gather_scores(
    table: jax.Array,   # [N, d] full vector table
    tsq: jax.Array,     # [N]
    ids: jax.Array,     # i32[B, C] candidate ids (assumed in-range)
    q: jax.Array,       # [B, d]
    metric: str = "l2",
) -> jax.Array:
    """[B, C] scores of each query against its own gathered candidates."""
    rows = table[ids]                       # [B, C, d]
    dots = jnp.einsum(
        "bcd,bd->bc", rows.astype(jnp.float32), q.astype(jnp.float32),
        precision=HIGHEST,
    )
    if metric == "l2":
        return 2.0 * dots - tsq[ids].astype(jnp.float32)
    return dots


def ref_gather_scores_q8(
    codes: jax.Array,   # i8[N, d] per-row int8 vector codes
    scales: jax.Array,  # f32[N]   per-row dequant scales
    ids: jax.Array,     # i32[B, C] candidate ids (assumed in-range)
    q: jax.Array,       # [B, d] uncompressed queries
    metric: str = "l2",
) -> jax.Array:
    """[B, C] asymmetric scores of each query vs its gathered int8 rows:
    l2 → s·(2·<codes,q> − s·Σcodes²), ip/cos → s·<codes,q> (DESIGN.md §10)."""
    rows = codes[ids].astype(jnp.float32)   # [B, C, d]
    s = scales[ids].astype(jnp.float32)     # [B, C]
    dots = jnp.einsum("bcd,bd->bc", rows, q.astype(jnp.float32),
                      precision=HIGHEST)
    if metric == "l2":
        return s * (2.0 * dots - s * jnp.sum(rows * rows, axis=-1))
    return s * dots

"""Tiled score-matrix + fused streaming top-k Pallas kernels (TPU target).

Two kernels:

``_score_kernel`` — the MXU workhorse: grid (B/bB, M/bM, d/bD), fp32
accumulation in the output block, L2 norm correction folded into the last
d-tile. Block shapes default to (128, 256, 128): q-block 64KB + x-block
128KB + out-block 128KB ≈ 0.3MB of VMEM per step, well under the ~16MB/core
budget with double buffering.

``_topk_kernel`` — fused scoring + streaming top-k: grid (B/bB, M/bM) with
the full (padded) feature dim in VMEM; a scratch-carried running top-k is
merged per M-tile with an iterative max-extract (k compile-time steps of
elementwise max/min reductions — no sort/top_k primitive needed, so it
lowers on TPU). Avoids materializing the [B, M] matrix in HBM entirely:
bytes written drop from O(B·M) to O(B·k).

Used by: brute-force ground truth, ReBuild bulk kNN, DLRM retrieval_cand
(1M-candidate scoring), and the distributed result merge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# score matrix
# ---------------------------------------------------------------------------

def _score_kernel(x_ref, xsq_ref, q_ref, o_ref, *, n_d_tiles: int, metric: str):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] += 2.0 * dots if metric == "l2" else dots

    @pl.when(kd == n_d_tiles - 1)
    def _finish():
        if metric == "l2":
            o_ref[...] -= xsq_ref[...].astype(jnp.float32)


def score_matrix_pallas(
    x: jax.Array,     # [M, d]
    xsq: jax.Array,   # [M]
    q: jax.Array,     # [B, d]
    *,
    metric: str = "l2",
    block_b: int = 128,
    block_m: int = 256,
    block_d: int = 128,
    interpret: bool,
) -> jax.Array:
    """[B, M] scores. Caller pads B/M/d to block multiples (see ops.py)."""
    B, d = q.shape
    M = x.shape[0]
    assert B % block_b == 0 and M % block_m == 0 and d % block_d == 0
    grid = (B // block_b, M // block_m, d // block_d)
    return pl.pallas_call(
        functools.partial(_score_kernel, n_d_tiles=grid[2], metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_d), lambda b, m, kd: (m, kd)),
            pl.BlockSpec((1, block_m), lambda b, m, kd: (0, m)),
            pl.BlockSpec((block_b, block_d), lambda b, m, kd: (b, kd)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda b, m, kd: (b, m)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        interpret=interpret,
    )(x, xsq.reshape(1, M), q)


# ---------------------------------------------------------------------------
# fused score + streaming top-k
# ---------------------------------------------------------------------------

def _iter_topk(scores: jax.Array, ids: jax.Array, k: int):
    """k-step max-extract top-k over the last axis (TPU-lowerable: only
    elementwise ops + max/min reductions, no sort)."""
    n = scores.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, len(scores.shape) - 1)
    out_s, out_i = [], []
    cur = scores
    for _ in range(k):
        m = jnp.max(cur, axis=-1, keepdims=True)                    # [B,1]
        is_max = cur == m
        pos = jnp.min(jnp.where(is_max, iota, n), axis=-1, keepdims=True)
        sel = iota == pos                                           # first max
        picked_id = jnp.sum(jnp.where(sel, ids, 0), axis=-1)
        out_s.append(m[..., 0])
        out_i.append(picked_id)
        cur = jnp.where(sel, NEG_INF, cur)
    return jnp.stack(out_s, axis=-1), jnp.stack(out_i, axis=-1)


def _topk_kernel(
    x_ref, xsq_ref, q_ref, os_ref, oi_ref, rs_ref, ri_ref,
    *, k: int, block_m: int, n_m_tiles: int, n_valid: int, metric: str,
):
    m_idx = pl.program_id(1)

    @pl.when(m_idx == 0)
    def _init():
        rs_ref[...] = jnp.full_like(rs_ref, NEG_INF)
        ri_ref[...] = jnp.full_like(ri_ref, -1)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    scores = 2.0 * dots - xsq_ref[...] if metric == "l2" else dots
    local_ids = (
        jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + m_idx * block_m
    )
    scores = jnp.where(local_ids < n_valid, scores, NEG_INF)  # padded rows lose

    comb_s = jnp.concatenate([rs_ref[...], scores], axis=1)
    comb_i = jnp.concatenate([ri_ref[...], local_ids], axis=1)
    top_s, top_i = _iter_topk(comb_s, comb_i, k)
    rs_ref[...] = top_s
    ri_ref[...] = top_i

    @pl.when(m_idx == n_m_tiles - 1)
    def _flush():
        os_ref[...] = rs_ref[...]
        oi_ref[...] = ri_ref[...]


def score_topk_pallas(
    x: jax.Array,
    xsq: jax.Array,
    q: jax.Array,
    k: int,
    *,
    metric: str = "l2",
    block_b: int = 64,
    block_m: int = 256,
    n_valid: int | None = None,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused (scores f32[B,k], ids i32[B,k]) without the [B,M] HBM matrix."""
    B, d = q.shape
    M = x.shape[0]
    assert B % block_b == 0 and M % block_m == 0
    grid = (B // block_b, M // block_m)
    return pl.pallas_call(
        functools.partial(
            _topk_kernel, k=k, block_m=block_m, n_m_tiles=grid[1],
            n_valid=n_valid if n_valid is not None else M, metric=metric,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda b, m: (m, 0)),
            pl.BlockSpec((1, block_m), lambda b, m: (0, m)),
            pl.BlockSpec((block_b, d), lambda b, m: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda b, m: (b, 0)),
            pl.BlockSpec((block_b, k), lambda b, m: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, k), jnp.float32),
            pltpu.VMEM((block_b, k), jnp.int32),
        ],
        interpret=interpret,
    )(x, xsq.reshape(1, M), q)

"""jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, invalid-id fixup, dtype policy (bf16/f32
inputs, fp32 accumulation), and the interpret-mode switch: interpret mode on
the CPU backend only, compiled kernels everywhere else.

Capacity-tier contract (DESIGN.md §9): the growth engine produces table
sizes that are NOT powers of two (geometric tiers, ``max_capacity`` clips),
so every wrapper must stay exact for arbitrary M. The padded-tail story,
audited per kernel and pinned by the {2^k, 2^k+1, 3·2^k} sweep in
``tests/test_kernels.py``:

  · ``score_matrix`` — rows/cols padded up to block multiples, output
    cropped ``[:B, :M]``; tail blocks compute garbage that is never read.
  · ``score_topk``   — the kernel masks row ids ≥ ``n_valid`` to -inf
    (authoritative for every metric) AND ``xsq`` is padded with +inf (l2
    belt-and-braces), so a padded tail row can never win a top-k slot.
  · ``gather_scores`` — ids are validated against the true M here and
    clamped before the kernel; the row DMAs address exact rows, so no
    tail row is ever read, and invalid lanes resolve to -inf outside.
  · ``gather_scores_q8`` — identical id-validation/clamp/-inf contract as
    ``gather_scores``, over int8 codes + per-row scales (DESIGN.md §10);
    the dim pad value 0 is inert in both the dot and the Σcodes² term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import distance_matrix as _dm
from repro.kernels import gather_distance as _gd

NEG_INF = float("-inf")


def on_tpu() -> bool:
    """True when the default backend is a real TPU."""
    return jax.devices()[0].platform == "tpu"


def _interpret(flag: bool | None) -> bool:
    """Interpret mode is the CPU backend's emulator and nothing else: on any
    other backend the kernel is compiled (and raises where it cannot be)."""
    return jax.default_backend() == "cpu" if flag is None else flag


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("metric", "block_b", "block_m", "block_d", "interpret")
)
def score_matrix(
    x: jax.Array,
    xsq: jax.Array,
    q: jax.Array,
    *,
    metric: str = "l2",
    block_b: int = 128,
    block_m: int = 256,
    block_d: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """[B, M] fp32 scores via the tiled Pallas kernel (padded + cropped)."""
    interpret = _interpret(interpret)
    B, M = q.shape[0], x.shape[0]
    block_b = min(block_b, max(8, B))
    block_m = min(block_m, max(8, M))
    xp = _pad_to(_pad_to(x, 0, block_m), 1, block_d)
    qp = _pad_to(_pad_to(q, 0, block_b), 1, block_d)
    xsqp = _pad_to(xsq, 0, block_m)
    out = _dm.score_matrix_pallas(
        xp, xsqp, qp, metric=metric, block_b=block_b, block_m=block_m,
        block_d=block_d, interpret=interpret,
    )
    return out[:B, :M]


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "block_b", "block_m", "interpret")
)
def score_topk(
    x: jax.Array,
    xsq: jax.Array,
    q: jax.Array,
    k: int,
    *,
    metric: str = "l2",
    block_b: int = 64,
    block_m: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused brute-force top-k: (scores f32[B,k], ids i32[B,k])."""
    interpret = _interpret(interpret)
    B, M = q.shape[0], x.shape[0]
    block_b = min(block_b, max(8, B))
    block_m = min(block_m, max(k, 8, M))
    # Padded-row masking happens in TWO places, both required:
    #   1. the kernel masks rows with id >= n_valid to -inf (authoritative —
    #      covers every metric, including ip/cos where xsq is unused and a
    #      zero-padded row would otherwise score 0 and beat negative scores);
    #   2. xsq is padded with +inf so l2 scores (2<q,x> - ||x||^2) of padded
    #      rows are -inf even before the n_valid mask.
    xp = _pad_to(_pad_to(x, 0, block_m), 1, 128)
    qp = _pad_to(_pad_to(q, 0, block_b), 1, 128)
    xsqp = _pad_to(xsq, 0, block_m, value=jnp.inf)
    s, i = _dm.score_topk_pallas(
        xp, xsqp, qp, k, metric=metric, block_b=block_b, block_m=block_m,
        n_valid=M, interpret=interpret,
    )
    s, i = s[:B], i[:B]
    ok = (i >= 0) & (i < M)
    return jnp.where(ok, s, NEG_INF), jnp.where(ok, i, -1)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_scores(
    table: jax.Array,
    tsq: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    *,
    metric: str = "l2",
    interpret: bool | None = None,
) -> jax.Array:
    """[B, C] fused gather+distance; invalid ids (< 0 or >= N) → -inf."""
    interpret = _interpret(interpret)
    N = table.shape[0]
    valid = (ids >= 0) & (ids < N)
    safe = jnp.where(valid, ids, 0).astype(jnp.int32)
    # rows are DMA'd whole: the lane dim is padded to the 128-lane tile
    tp = _pad_to(table, 1, 128)
    qp = _pad_to(q, 1, 128)
    s = _gd.gather_scores_pallas(
        tp, tsq.astype(jnp.float32)[safe], safe, qp, metric=metric,
        interpret=interpret,
    )
    return jnp.where(valid, s, NEG_INF)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_scores_q8(
    codes: jax.Array,   # i8[N, d] per-row int8 vector codes
    scales: jax.Array,  # f32[N]   per-row dequant scales
    ids: jax.Array,     # i32[B, C] candidate ids (any value; validated here)
    q: jax.Array,       # f32[B, d] uncompressed queries
    *,
    metric: str = "l2",
    interpret: bool | None = None,
) -> jax.Array:
    """[B, C] fused gather+asymmetric-distance over int8 codes; invalid ids
    (< 0 or >= N) → -inf. Same contract as ``gather_scores`` with the fp32
    row read replaced by a d-byte code row dequantized in-register."""
    interpret = _interpret(interpret)
    N = codes.shape[0]
    valid = (ids >= 0) & (ids < N)
    safe = jnp.where(valid, ids, 0).astype(jnp.int32)
    cp = _pad_to(codes, 1, 128, value=0)
    qp = _pad_to(q, 1, 128)
    s = _gd.gather_scores_q8_pallas(
        cp, scales.astype(jnp.float32)[safe], safe, qp, metric=metric,
        interpret=interpret,
    )
    return jnp.where(valid, s, NEG_INF)

"""Per-slot int8 vector codes — the compressed-scoring storage scheme.

The hot loop (beam expansion) reads fp32 rows from ``vectors[capacity, d]``;
serving the walk on int8 codes instead moves ~4x fewer bytes per candidate
(FreshDiskANN's compressed-first/exact-rerank split — DESIGN.md §10). The
scheme is the simplest one that keeps a checkable transactional invariant:

  · per-row symmetric max-abs scaling: ``scale = max|x| / 127``,
    ``code = round(x / scale)`` (round-half-even, the IEEE default) — a pure
    deterministic function of the row, unlike the *stochastic* gradient
    quantizer in ``distributed/compression.py`` (which trades determinism
    for unbiasedness; vector codes need the opposite trade so the invariant
    ``codes == quantize(vectors)`` is exactly re-checkable at any barrier);
  · a *present* all-zero row maps to (zero codes, ``ZERO_ROW_SCALE``) — a
    positive sentinel scale — while freed/never-used slots are scrubbed to
    (zero codes, ``0.0``) by the delete/consolidate/grow paths. The v1
    scheme mapped zero rows to scale 0.0 too, which made a legitimately
    inserted zero vector byte-identical to a freed slot: invariant I5
    became unable to distinguish live from dead, and any tooling keying on
    the scrub pattern would treat the row as deleted. The sentinel breaks
    the collision without perturbing a single score — the codes are all
    zero, so every metric's similarity below is exactly 0.0 no matter the
    scale (ip/cos: scale·0; l2: scale·(0 − scale·0));
  · asymmetric distance against an uncompressed fp32 query ``q``:
        ip/cos:  scale · <codes, q>
        l2:      scale · (2·<codes, q> − scale · Σ codes²)
    i.e. every metric's similarity evaluated on the dequantized row without
    materializing it (the ``Σ codes²`` term replaces the ``sqnorms`` cache).

``VECTOR_CODE_SCHEME`` names this scheme; it is folded into the checkpoint
fingerprint so a state whose codes were produced under a different scheme
can never be silently restored into an engine that scores them differently.
(The zero-row sentinel bumped it v1 → v2: v1 checkpoints hold codes whose
zero rows this engine would re-encode differently, failing I5's re-check.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VECTOR_CODE_SCHEME = "int8-rowmax-rne-v2"

# Scale stamped on present all-zero rows: positive (distinguishes them from
# the freed-slot 0.0 scrub) and the smallest normal f32, so even an
# (impossible) nonzero code under it would contribute ~nothing to a score.
ZERO_ROW_SCALE = jnp.float32(2.0 ** -126)


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Deterministic per-row int8 quantization over the last axis.

    Returns ``(codes i8[..., d], scales f32[...])`` with
    ``codes · scale ≈ x`` (error ≤ scale/2 per element). Any leading batch
    shape is accepted — ``[capacity, d]`` states and the stacked
    ``[shards, capacity, d]`` layout of ``ShardedSession`` both work.
    """
    x32 = x.astype(jnp.float32)
    maxabs = jnp.max(jnp.abs(x32), axis=-1)
    # multiply by the f32-rounded reciprocal instead of dividing: XLA's
    # simplifier rewrites division-by-constant into exactly this multiply
    # inside jit, so spelling it out keeps jit and eager bit-identical —
    # which the re-checkable invariant I5 requires
    scales = maxabs * jnp.float32(1.0 / 127.0)
    # zero rows take the positive sentinel scale so a present zero vector
    # can never collide with the freed-slot (0 codes, 0.0 scale) scrub
    scales = jnp.where(maxabs > 0, scales, ZERO_ROW_SCALE)
    safe = jnp.where(maxabs > 0, scales, 1.0)
    codes = jnp.clip(jnp.round(x32 / safe[..., None]), -127, 127)
    return codes.astype(jnp.int8), scales


def dequantize_rows(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """f32[..., d] reconstruction ``codes · scale`` (test/debug helper)."""
    return codes.astype(jnp.float32) * scales[..., None]


def scores_vs_codes(
    codes: jax.Array,   # i8[..., d] gathered candidate codes
    scales: jax.Array,  # f32[...]
    q: jax.Array,       # f32[d] uncompressed query
    metric: str,
) -> jax.Array:
    """Asymmetric similarity of ``q`` vs each compressed row (higher=better).

    Matches ``distances.scores_vs_rows`` on the dequantized rows exactly in
    math (l2 as ``2<x,q> − ||x||²``), with ``||x̂||² = scale²·Σcodes²``
    computed from the codes — no fp32 row or sqnorm cache is touched.
    """
    c = codes.astype(jnp.float32)
    dots = jnp.einsum("...d,d->...", c, q.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        return scales * (2.0 * dots - scales * jnp.sum(c * c, axis=-1))
    return scales * dots

"""Fused gather + distance Pallas kernel — the beam-expansion hot loop.

Greedy search expands ``C`` candidate ids per query per step; XLA's gather
materializes ``[B, C, d]`` in HBM before the dot. This kernel instead keeps
the table in HBM (``memory_space=pl.ANY``) and, for each query, copies its C
candidate rows straight into a VMEM block with one async DMA per row,
addressed by the scalar-prefetched ids (the paged-attention pattern). The
rows of query b+1 are in flight while query b is scored, so the grid runs
sequentially over a two-slot buffer. Scoring is one ``[C, d]·[d]`` product
in fp32 on the vector unit, plus the per-candidate norm term.

HBM traffic: ``B·C·d`` reads + ``B·C`` writes (vs ``2·B·C·d + B·C`` for the
unfused gather-then-einsum), and no ``[B, C, d]`` intermediate.

Layout: per-query blocks are ``(C, 1)`` columns of ``[B, C, 1]`` arrays and
the query is a ``(1, d)`` row of ``[B, 1, d]`` — the last two block dims
equal the array's, which is what the TPU compiler accepts for any C and d.

Caller contract (ops.py enforces): ids are pre-clamped to [0, N); the
per-candidate norm / scale column is gathered by the caller; invalid lanes
are fixed up outside (scores → -inf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_group(table: jax.Array) -> int:
    """Rows per DMA. A TPU HBM table is tiled (8, 128): one row is a
    contiguous tile row only for 32-bit rows of exactly 128 lanes; any other
    table is fetched as the aligned group of 8 rows holding the candidate,
    and the kernel keeps the one it needs."""
    return 1 if table.dtype.itemsize == 4 and table.shape[1] == 128 else 8


def _gather_dots(ids_ref, table_hbm, sub_ref, q_ref, buf, sem, *,
                 n_cand: int, group: int):
    """(⟨row, q⟩, Σrow²) columns [C, 1] of query b's C candidate rows.

    Scratch ``buf[b % 2]`` receives query b's row groups; the groups of
    query b+1 are started into the other slot before waiting on b's."""
    b = pl.program_id(0)

    def copy(qi, c, slot):
        i = ids_ref[qi * n_cand + c]
        src = (table_hbm.at[pl.ds(i, 1)] if group == 1
               else table_hbm.at[i // group])
        return pltpu.make_async_copy(src, buf.at[slot, c], sem.at[slot])

    def start_all(qi, slot):
        def body(c, carry):
            copy(qi, c, slot).start()
            return carry
        jax.lax.fori_loop(0, n_cand, body, 0)

    @pl.when(b == 0)
    def _():
        start_all(0, 0)

    @pl.when(b + 1 < pl.num_programs(0))
    def _():
        start_all(b + 1, (b + 1) % 2)

    slot = b % 2

    def wait(c, carry):
        copy(b, c, slot).wait()
        return carry

    jax.lax.fori_loop(0, n_cand, wait, 0)
    rows = buf[slot].astype(jnp.float32)                 # [C, group, d]
    q = q_ref[...].astype(jnp.float32)[None]             # [1, 1, d]
    # keep the candidate's row of its group: a one-hot sum is exact
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_cand, group), 1)
    pick = lane == sub_ref[...]

    def col(x):
        return jnp.sum(jnp.where(pick, x, 0.0), axis=1, keepdims=True)

    return col(jnp.sum(rows * q, axis=2)), col(jnp.sum(rows * rows, axis=2))


def _gather_call(kernel, ids, table, col, q, *, interpret: bool):
    B, C = ids.shape
    N, d = table.shape
    group = row_group(table)
    if group > 1:
        if N % group:
            table = jnp.pad(table, ((0, -N % group), (0, 0)))
        # [N, d] → [N/group, group, d] is a bitcast of the tiled layout
        table = table.reshape(-1, group, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, C, 1), lambda b, ids_ref: (b, 0, 0)),
            pl.BlockSpec((None, C, 1), lambda b, ids_ref: (b, 0, 0)),
            pl.BlockSpec((None, 1, d), lambda b, ids_ref: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, C, 1), lambda b, ids_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, C, group, d), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, n_cand=C, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, 1), jnp.float32),
        # the prefetch of query b+1 carries across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids.reshape(-1), table, (ids % group).reshape(B, C, 1),
      col.reshape(B, C, 1), q.reshape(B, 1, d))
    return out.reshape(B, C)


def _gd_kernel(ids_ref, x_hbm, sub_ref, xsq_ref, q_ref, o_ref, buf, sem, *,
               n_cand: int, group: int, metric: str):
    dot, _ = _gather_dots(ids_ref, x_hbm, sub_ref, q_ref, buf, sem,
                          n_cand=n_cand, group=group)
    if metric == "l2":
        o_ref[...] = 2.0 * dot - xsq_ref[...]
    else:
        o_ref[...] = dot


def gather_scores_pallas(
    table: jax.Array,   # [N, d]
    tsq: jax.Array,     # f32[B, C]  ||x||² of each candidate
    ids: jax.Array,     # i32[B, C]  pre-clamped to [0, N)
    q: jax.Array,       # [B, d]
    *,
    metric: str = "l2",
    interpret: bool,
) -> jax.Array:
    return _gather_call(
        functools.partial(_gd_kernel, metric=metric), ids, table, tsq, q,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Compressed variant — int8 codes + per-row scale, dequantized in-register
# (DESIGN.md §10). Same DMA pattern, but each gathered row moves d bytes
# instead of 4·d: the beam expansion's HBM traffic drops ~4x.
# ---------------------------------------------------------------------------

def _gdq_kernel(ids_ref, c_hbm, sub_ref, s_ref, q_ref, o_ref, buf, sem, *,
                n_cand: int, group: int, metric: str):
    dot, csq = _gather_dots(ids_ref, c_hbm, sub_ref, q_ref, buf, sem,
                            n_cand=n_cand, group=group)
    s = s_ref[...]
    if metric == "l2":
        # asymmetric l2 on the dequantized row x̂ = s·codes:
        #   2<x̂,q> − ||x̂||² = s·(2·<codes,q> − s·Σcodes²)
        o_ref[...] = s * (2.0 * dot - s * csq)
    else:
        o_ref[...] = s * dot


def gather_scores_q8_pallas(
    codes: jax.Array,   # i8[N, d]
    scales: jax.Array,  # f32[B, C]  dequant scale of each candidate
    ids: jax.Array,     # i32[B, C]  pre-clamped to [0, N)
    q: jax.Array,       # [B, d] uncompressed queries
    *,
    metric: str = "l2",
    interpret: bool,
) -> jax.Array:
    return _gather_call(
        functools.partial(_gdq_kernel, metric=metric), ids, codes, scales, q,
        interpret=interpret)

"""The plain reference: exact top-k by brute force, in blocks, in jnp.

It imports nothing of the program and takes nothing the program made: the
corpus rows are the benchmark's own (generated from the seed), and the live
set is the benchmark's own record of which rows it inserted and deleted.

Scores follow the similarity the configuration's metric defines (higher is
better): ``l2`` -> 2<q,x> - ||x||^2 (the squared distance less the query's
constant ||q||^2), ``cos`` -> <q, x/||x||>, ``ip`` -> <q,x>.

``precision`` names the arithmetic: ``highest`` is float32 at
``Precision.HIGHEST`` (the reference). The lower rungs exist for the control
(bench/calibrate.py): ``high`` (three bf16 passes), ``bf16`` (operands
rounded to bfloat16, one pass), ``int8`` (each row rounded to int8 codes
with a per-row scale, the query kept in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32. ``reduce_precision`` is
    an explicit op: XLA may drop a float32->bfloat16->float32 convert pair
    as excess precision, but not this rounding."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot(spec: str, q, x, precision: str):
    """einsum(spec, q, x) in float32, in the named precision. The bf16 rungs
    round the operands explicitly and multiply at HIGHEST, which is exact
    for bfloat16 operands with float32 sums: ``high`` is the three passes
    hi*hi + hi*lo + lo*hi, ``bf16`` the one pass, the same on every
    backend."""
    def ein(a, b):
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    if precision == "highest":
        return ein(q, x)
    if precision == "high":
        qh, xh = _bf16(q), _bf16(x)
        ql, xl = _bf16(q - qh), _bf16(x - xh)
        return ein(qh, xh) + ein(qh, xl) + ein(ql, xh)
    if precision == "bf16":
        return ein(_bf16(q), _bf16(x))
    if precision == "int8":
        return ein(q, _int8(x))
    raise ValueError(f"unknown precision {precision!r}")


def _int8(x):
    """Each row rounded to int8 codes times a per-row scale."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _rows(x, metric: str):
    if metric == "cos":
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-30)
    return x


@functools.partial(jax.jit, static_argnames=("metric", "precision"))
def _sq(x, metric: str, precision: str):
    xr = _rows(x, metric)
    return _dot("nd,nd->n", xr if precision != "int8" else _int8(xr), xr,
                precision)


@functools.partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _topk_block(x, sq, live, q, k: int, metric: str, precision: str):
    s = _dot("bd,nd->bn", q, _rows(x, metric), precision)
    if metric == "l2":
        s = 2.0 * s - sq[None, :]
    s = jnp.where(live[None, :], s, -jnp.inf)
    return jax.lax.top_k(s, k)


@functools.partial(jax.jit, static_argnames=("metric", "precision"))
def _scores_of_block(x, q, rows, metric: str, precision: str):
    xr = _rows(x[jnp.maximum(rows, 0)], metric)         # [b, k, d]
    dot = _dot("bd,bkd->bk", q, xr, precision)
    if metric == "l2":
        xq = xr if precision != "int8" else _int8(xr)
        dot = 2.0 * dot - _dot("bkd,bkd->bk", xq, xr, precision)
    return jnp.where(rows >= 0, dot, jnp.nan)


def _blocks(n: int, block: int):
    return range(0, n, block)


def exact_topk(corpus, live, queries, k: int, metric: str, *,
               precision: str = "highest", block: int = 256):
    """(scores f32[Q, k], rows i64[Q, k]) of the k best live rows.

    ``corpus`` f32[N, d] (host or device), ``live`` bool[N], ``queries``
    f32[Q, d]. Queries go in blocks of ``block`` so the [block, N] score
    matrix fits beside the corpus."""
    x = jnp.asarray(corpus, jnp.float32)
    sq = _sq(x, metric, precision)
    lv = jnp.asarray(live, bool)
    q_all = np.asarray(queries, np.float32)
    out_s, out_i = [], []
    for lo in _blocks(q_all.shape[0], block):
        q = q_all[lo:lo + block]
        pad = block - q.shape[0]
        qb = jnp.asarray(np.pad(q, ((0, pad), (0, 0))))
        s, i = _topk_block(x, sq, lv, qb, k, metric, precision)
        out_s.append(np.asarray(s)[:q.shape[0]])
        out_i.append(np.asarray(i)[:q.shape[0]])
    if not out_s:
        return np.zeros((0, k), np.float32), np.zeros((0, k), np.int64)
    return np.concatenate(out_s), np.concatenate(out_i).astype(np.int64)


def scores_of(corpus, queries, rows, metric: str, *,
              precision: str = "highest", block: int = 256) -> np.ndarray:
    """f32[Q, m] similarity of each query to each of its ``rows`` (-1 ->
    NaN), computed from the corpus alone."""
    x = jnp.asarray(corpus, jnp.float32)
    q_all = np.asarray(queries, np.float32)
    r_all = np.asarray(rows, np.int64)
    out = []
    for lo in _blocks(q_all.shape[0], block):
        q, r = q_all[lo:lo + block], r_all[lo:lo + block]
        pad = block - q.shape[0]
        qb = jnp.asarray(np.pad(q, ((0, pad), (0, 0))))
        rb = jnp.asarray(np.pad(r, ((0, pad), (0, 0)), constant_values=-1)
                         .astype(np.int32))
        out.append(np.asarray(_scores_of_block(x, qb, rb, metric,
                                               precision))[:q.shape[0]])
    if not out:
        return np.zeros((0, r_all.shape[1]), np.float32)
    return np.concatenate(out)


def rounding_scale(corpus, queries, rows, metric: str) -> np.ndarray:
    """f32[Q, m]: the magnitude of the terms a score sums — 2|q||x| + |x|^2
    for l2, |q||x| for the others — which bounds its rounding error."""
    x = np.asarray(corpus, np.float32)
    r = np.maximum(np.asarray(rows, np.int64), 0)
    xn = np.linalg.norm(x, axis=1)[r]
    if metric == "cos":
        xn = np.ones_like(xn)
    qn = np.linalg.norm(np.asarray(queries, np.float32), axis=1)[:, None]
    if metric == "l2":
        return 2.0 * qn * xn + xn * xn
    return np.maximum(qn * xn, 1e-30)

"""The plain reference: exact top-k by brute force, in float32.

Imports nothing of the program under test and takes nothing it made: it
sees the benchmark's own database and queries.  Scores follow the
program's value contract (``l2``: the relaxed distance ``||x||²/2 - <q,x>``,
ascending; ``cosine``: the cosine of the angle, descending).

``precision="highest"`` is the reference itself: every dot product at
``jax.lax.Precision.HIGHEST`` (full float32 on a TPU).  ``precision="high"``
is the control, one step below: each float32 operand split into a high
and a low part of bfloat16 precision, and the three products
``hi·hi + hi·lo + lo·hi`` summed in float32, which is what
``Precision.HIGH`` computes on a TPU.  It is written out so that a CPU,
which ignores the precision flag, computes the same control.  The parts
are rounded with ``lax.reduce_precision`` and kept in float32: a
round trip through the bfloat16 type may be folded away by XLA, which
then computes one pass where three were asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["exact_scores", "top_k"]

# Bytes of one (query chunk x database) score tile.
TILE_BYTES = 1 << 30


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _split(x):
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dots(q, db, precision: str):
    if precision == "highest":
        return jnp.einsum("md,nd->mn", q, db,
                          precision=jax.lax.Precision.HIGHEST)
    qh, ql = _split(q)
    dh, dl = _split(db)

    def dot(a, b):  # operands exact in bfloat16: one pass is exact
        return jnp.einsum("md,nd->mn", a, b,
                          precision=jax.lax.Precision.DEFAULT)

    return dot(qh, dh) + dot(qh, dl) + dot(ql, dh)


@functools.partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _chunk_top_k(q, db, *, k, metric, precision):
    if metric == "cosine":
        q, db = _normalize(q), _normalize(db)
    scores = _dots(q, db, precision)
    if metric == "l2":
        half = 0.5 * jnp.sum(db * db, axis=-1)
        vals, ids = jax.lax.top_k(scores - half[None, :], k)
        return -vals, ids
    if metric == "cosine":
        return jax.lax.top_k(scores, k)
    raise ValueError(f"no reference for metric {metric!r}")


def top_k(queries, db, k: int, metric: str, precision: str = "highest"):
    """(values, ids) as host arrays, (M, k), in query chunks whose score
    tile stays under ``TILE_BYTES``."""
    if precision not in ("highest", "high"):
        raise ValueError(f"precision must be 'highest' or 'high', got "
                         f"{precision!r}")
    queries = jnp.asarray(queries, jnp.float32)
    chunk = max(1, TILE_BYTES // (4 * db.shape[0]))
    vals, ids = [], []
    for start in range(0, queries.shape[0], chunk):
        v, i = _chunk_top_k(queries[start:start + chunk], db, k=k,
                            metric=metric, precision=precision)
        vals.append(np.asarray(v))
        ids.append(np.asarray(i))
    return np.concatenate(vals), np.concatenate(ids)


def exact_scores(queries, rows, metric: str):
    """Float64 scores of ``rows`` (M, k, D) for ``queries`` (M, D), and the
    float32 magnitude of each: the sum of the absolute terms that a
    float32 evaluation adds, which scales its rounding error."""
    q = np.asarray(queries, np.float64)
    x = np.asarray(rows, np.float64)
    if metric == "cosine":
        q = q / np.linalg.norm(q, axis=-1, keepdims=True)
        x = x / np.linalg.norm(x, axis=-1, keepdims=True)
        prod = q[:, None, :] * x
        return prod.sum(-1), np.abs(prod).sum(-1)
    if metric == "l2":
        half = 0.5 * np.sum(x * x, axis=-1)
        prod = q[:, None, :] * x
        return half - prod.sum(-1), half + np.abs(prod).sum(-1)
    raise ValueError(f"no reference for metric {metric!r}")

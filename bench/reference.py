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

A database sharded by rows over several devices is searched where it
lies: each shard's top-k on its own device, then the candidates of all
shards merged on the host by value, the lower global id first among
equal values, which is the order a single device's ``lax.top_k`` gives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["exact_scores", "sharded", "top_k"]

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


def sharded(db) -> bool:
    """Whether ``db`` is split by rows over several devices."""
    sharding = getattr(db, "sharding", None)
    return (isinstance(sharding, NamedSharding)
            and len(sharding.device_set) > 1)


@functools.lru_cache(maxsize=None)
def _shard_top_k(mesh, axis, k: int, metric: str, precision: str):
    """(queries, db) -> each shard's top-k, (shards, M, k), ids global."""

    def local(q, rows):
        v, i = _chunk_top_k(q, rows, k=k, metric=metric, precision=precision)
        offset = jax.lax.axis_index(axis) * rows.shape[0]
        return v[None], (i + offset)[None]

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P(axis, None)),
                                 out_specs=(P(axis), P(axis))))


def _merge(vals, ids, k: int, metric: str):
    """The best ``k`` of candidate rows (M, C), by value (``l2`` ascending,
    ``cosine`` descending), the lower id first among equal values."""
    primary = vals if metric == "l2" else -vals
    order = np.lexsort((ids, primary), axis=-1)[:, :k]
    return (np.take_along_axis(vals, order, axis=-1),
            np.take_along_axis(ids, order, axis=-1))


def top_k(queries, db, k: int, metric: str, precision: str = "highest"):
    """(values, ids) as host arrays, (M, k), in query chunks whose score
    tile stays under ``TILE_BYTES`` on each device."""
    if precision not in ("highest", "high"):
        raise ValueError(f"precision must be 'highest' or 'high', got "
                         f"{precision!r}")
    queries = jnp.asarray(queries, jnp.float32)
    if sharded(db):
        spec = db.sharding
        program = _shard_top_k(spec.mesh, spec.spec[0], k, metric, precision)
        rows = db.addressable_shards[0].data.shape[0]
        replicated = NamedSharding(spec.mesh, P())

        def search(q):
            v, i = program(jax.device_put(q, replicated), db)
            v, i = np.asarray(v), np.asarray(i)   # (shards, m, k)
            return _merge(np.concatenate(v, axis=-1),
                         np.concatenate(i, axis=-1), k, metric)
    else:
        rows = db.shape[0]

        def search(q):
            v, i = _chunk_top_k(q, db, k=k, metric=metric,
                                precision=precision)
            return np.asarray(v), np.asarray(i)
    chunk = max(1, TILE_BYTES // (4 * rows))
    vals, ids = [], []
    for start in range(0, queries.shape[0], chunk):
        v, i = search(queries[start:start + chunk])
        vals.append(v)
        ids.append(i)
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

"""The comparison that decides ``correct``: window outputs against the reference.

Each number is compared with its limit from the configuration's file
(``limits``), where ``PERF.md`` gives the readings each limit was set from:

- ``recall``: recall@k of the sampled outputs against the reference's ids,
  at least the configuration's recall target;
- ``value_err``: the widest gap between a returned value and the float64
  score of the id returned beside it, in float32 rounding units of that
  score's magnitude (``reference.exact_scores``), at most its limit;
- ``bad_ids``: sampled rows holding an id outside ``[0, N)`` or the same
  id twice, at most 0;
- ``unanswered``: requests due in the window that never got an answer, at
  most 0 (served traffic only; late answers count in the latency).
"""
from __future__ import annotations

import numpy as np

import reference

__all__ = ["compare", "gather_rows", "judge"]

EPS32 = float(np.finfo(np.float32).eps)


def compare(cfg: dict, queries, vals, ids, db, ref_ids) -> dict:
    """The numbers compared, for sampled rows: ``queries`` (S, D), the
    program's ``vals``/``ids`` (S, k) and the reference's ``ref_ids``."""
    k, n = cfg["k"], cfg["n"]
    ids = np.asarray(ids)
    vals = np.asarray(vals, np.float64)
    in_range = (ids >= 0) & (ids < n)
    dup = np.array([len(set(r.tolist())) < len(r) for r in ids])
    bad_rows = (~in_range).any(axis=1) | dup | (ids.shape[1] != k)
    hits = [len(set(a.tolist()) & set(b.tolist())) for a, b in zip(ids, ref_ids)]
    safe = np.where(in_range, ids, 0)
    rows = gather_rows(db, safe.ravel()).reshape(*ids.shape, -1)
    exact, mag = reference.exact_scores(queries, rows, cfg["metric"])
    err = np.abs(vals - exact) / (EPS32 * np.maximum(mag, 1e-30))
    err = np.where(in_range, err, 0.0)
    return {
        "recall": float(np.sum(hits) / (k * len(ids))),
        "value_err": float(err.max()) if err.size else 0.0,
        "bad_ids": int(bad_rows.sum()),
    }


def gather_rows(db, ids):
    """Host copy of the rows ``ids`` of ``db``; a database sharded by rows
    gives each id from the shard that holds it, on that shard's device."""
    import jax.numpy as jnp

    if not reference.sharded(db):
        return np.asarray(jnp.take(db, jnp.asarray(ids), axis=0))
    out = np.empty((len(ids), db.shape[1]), np.float32)
    for shard in db.addressable_shards:
        rows = shard.index[0]
        lo = rows.start or 0
        hi = db.shape[0] if rows.stop is None else rows.stop
        mine = (ids >= lo) & (ids < hi)
        if mine.any():
            out[mine] = np.asarray(jnp.take(
                shard.data, jnp.asarray(ids[mine] - lo), axis=0))
    return out


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every number that has a limit;
    a limit is ``{"min": x}`` or ``{"max": x}``."""
    out = {}
    for name, value in numbers.items():
        limit = limits[name]
        if "min" in limit:
            ok, bound = value >= limit["min"], limit["min"]
        else:
            ok, bound = value <= limit["max"], limit["max"]
        out[name] = {"value": value, "limit": bound, "ok": bool(ok)}
    return out

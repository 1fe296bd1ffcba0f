"""The four-chip cell and the sharded reference on four virtual CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/four_devices.py

``test_sharded.py`` runs this in a process of its own, since the device
count is fixed when JAX starts, and asserts on the one JSON object it
prints last: tiny runs of ``deep50m-f32.sharded4`` traced and not, the
same run with each fault a sharded search can have planted underneath,
and the sharded reference against the one-device reference.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import checks  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.search import Index  # noqa: E402
from repro.search.index import SearchResult  # noqa: E402
from test_correctness import FAULTS  # noqa: E402

CELL = "deep50m-f32.sharded4"
TINY = {"config": {"n": 4096, "queries": 256}}


def tiny_run(seed: int, traced: bool) -> dict:
    shards = {}

    def on_sample(cfg, queries, db, ref_ids):
        shards["rows"] = sorted(s.data.shape[0] for s in db.addressable_shards)
        shards["devices"] = len(db.sharding.device_set)

    result = run.run(CELL, seed, 1.0, traced, require_chip=False,
                     overrides=TINY, on_sample=on_sample)
    return {"correct": result["correct"], "checks": result["checks"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": sorted(result["metrics"]),
            "count": result["device"]["count"], "last_key": list(result)[-1],
            "db": shards}


def broken_run(fault: str) -> dict:
    """A tiny run with ``fault`` planted in the timed path."""
    search, all_gather = Index.search, jax.lax.all_gather
    if fault == "exchange_left_out":
        # Each chip merges its own winners only.
        jax.lax.all_gather = lambda x, *args, **kwargs: x
    else:
        def broken(self, queries):
            vals, ids = FAULTS[fault](lambda s, q: tuple(search(s, q)))(
                self, queries)
            return SearchResult(vals, ids)
        Index.search = broken
    try:
        result = run.run(CELL, 5, 1.0, False, require_chip=False,
                         overrides=TINY)
    finally:
        Index.search, jax.lax.all_gather = search, all_gather
    return {"correct": result["correct"], "checks": result["checks"]}


def reference_parity(metric: str) -> dict:
    """Every row four times, once in each shard, and some twice within a
    shard: the top 10 holds ties across shards, which both references
    must break by the lower global id.  Small integers keep every l2
    score exact, and equal rows score alike under any metric."""
    rng = np.random.default_rng(7)
    quarter = rng.integers(-4, 5, size=(64, 8)).astype(np.float32)
    quarter[32:48] = quarter[:16]
    db = np.concatenate([quarter] * 4)
    queries = rng.integers(-3, 4, size=(40, 8)).astype(np.float32)
    mesh = make_mesh((4,), ("db",))
    split = jax.device_put(db, NamedSharding(mesh, P("db", None)))
    one_v, one_i = reference.top_k(queries, jnp.asarray(db), 10, metric)
    four_v, four_i = reference.top_k(queries, split, 10, metric)
    picked = np.asarray([3, 70, 129, 255, 64, 191])
    return {
        "sharded": reference.sharded(split),
        "ids_equal": bool(np.array_equal(one_i, four_i)),
        "values_equal": bool(np.array_equal(one_v, four_v)),
        "ties_across_shards": int(sum(len(set(r % 64)) < len(r)
                                      for r in one_i)),
        "rows_equal": bool(np.array_equal(
            checks.gather_rows(split, picked), db[picked])),
    }


def sharded_control() -> dict:
    """The reference at ``high`` in the program's place, on a sharded
    database of the cell's width, judged by the cell's limits."""
    cfg = {**run.load_json(run.BENCH, "configs", "deep50m-f32.json"),
           "n": 65536}
    mesh = make_mesh((4,), (run.DB_AXIS,))
    draw = run.make_draw(11)
    db = draw("database", [(cfg["n"], cfg["d"])],
              sharding=NamedSharding(mesh, P(run.DB_AXIS, None)))[0]
    queries = np.asarray(draw("queries", [(512, cfg["d"])])[0])
    _, ref_ids = reference.top_k(queries, db, cfg["k"], cfg["metric"])
    out = {}
    for precision in ("highest", "high"):
        vals, ids = reference.top_k(queries, db, cfg["k"], cfg["metric"],
                                    precision=precision)
        numbers = checks.compare(cfg, queries, vals, ids, db, ref_ids)
        judged = checks.judge(numbers, {n: cfg["limits"][n] for n in numbers})
        out[precision] = {"ok": all(j["ok"] for j in judged.values()),
                          "value_err": numbers["value_err"]}
    return out


def main() -> int:
    if len(jax.devices()) != 4:
        raise SystemExit(f"needs 4 devices, found {len(jax.devices())}: set "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count=4")
    out = {
        "untraced": tiny_run(2**33 + 17, False),
        "traced": tiny_run(2**33 + 17, True),
        "faults": {f: broken_run(f)
                   for f in sorted(FAULTS) + ["exchange_left_out"]},
        "parity": {m: reference_parity(m) for m in ("l2", "cosine")},
        "control": sharded_control(),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

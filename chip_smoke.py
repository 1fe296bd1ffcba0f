#!/usr/bin/env python3
"""Bring-up check: the main search path, end to end, on one TPU chip.

Runs the paper's Table 2 sift1m workload (N = 1,000,000 rows of D = 128,
l2, k = 10, recall target 0.95, M = 10,000 queries, ``plan="model"``,
``cluster="off"``) through the public entry points, in this one process:

  1. batch search, f32 tier: ``Index.build`` + ``search``;
  2. batch search, int8 tier (scan + exact rescore);
  3. updates on the f32 index: ``add`` 10,000 rows, ``delete`` 10,000
     ids, search again;
  4. served: the f32 index behind ``SearchServer(warmup=True)`` on the
     wall clock, ~64 concurrent requests of 1-64 rows from 4 threads.

Every phase checks its results against a float32 brute-force reference
(``repro.search.metrics.exact_search`` at highest matmul precision, in
query chunks): recall@10 meets the target within the binomial (Hoeffding)
margin of ``tests/test_recall_guarantee.py``, returned values equal the
exact scores of the returned ids, deleted ids never come back, served
results are bit-identical to ``Index.search``.  Data is generated on the
device from ``--seed``; nothing outside the repository is read.

Each phase prints one ``phase <name>: {...}`` JSON line; its times, on
a TPU only, are wall seconds ending in ``block_until_ready``.
``first_search`` is the first dispatched search, its compile included;
``search`` is the mean of three more.  ``aot_compile`` is the compile of
``jax.jit(Index.search)`` in which the kernel is looked for: there the
index's arrays are constants of the program, which makes it slower to
compile than the dispatched search, which takes them as arguments.  The
last line of standard output is the result, printed only when every
phase passed on a TPU::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage::

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # only the sharded phase, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

``--chips 4`` shards an f32 index of 4,000,000 sift-shaped rows over a
four-chip mesh (``db_axis="model"``), searches 10,000 queries, checks
recall and values against the chunked exact reference, and checks that
each chip holds a quarter of the packed database.  ``--tiny`` runs the
same phases at a small N on the CPU, Pallas kernels in interpret mode;
it, and any run that finds no TPU, exits nonzero without the result line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SIFT1M = "sift1m"
# Hoeffding false-failure budget, as in tests/test_recall_guarantee.py.
DELTA = 1e-6
# Bytes of one reference score tile (query chunk x database rows, f32).
REF_TILE_BYTES = 1 << 30


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(name: str, record: dict) -> None:
    print(f"phase {name}: {json.dumps(record, sort_keys=True)}", flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded phase")
    ap.add_argument("--tiny", action="store_true",
                    help="small-N rehearsal (CPU, interpret mode); "
                         "never prints the result line")
    return ap.parse_args(argv)


def sizes(tiny: bool) -> dict:
    from repro.configs.knn_workloads import KNN_WORKLOADS

    w = KNN_WORKLOADS[SIFT1M]
    full = dict(n=w.n, d=w.d, m=w.m, k=w.k, metric=w.metric,
                recall_target=w.recall_target, ref_queries=1000,
                updates=10_000, requests=64, threads=4, max_batch=256,
                request_rows=(1, 5, 16, 40, 64), n_sharded=4_000_000)
    if tiny:
        full.update(n=4096, m=512, ref_queries=256, updates=256,
                    requests=16, max_batch=64, request_rows=(1, 5, 16),
                    n_sharded=8192)
    return full


class Run:
    """One smoke run: the device it found, its sizes and its checks."""

    def __init__(self, args):
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        self.args = args
        self.cache_dir = enable_compile_cache()
        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        self.on_chip = self.platform == "tpu" and not args.tiny
        self.cfg = sizes(args.tiny)
        self.key = jax.random.PRNGKey(args.seed)

    # -- data ----------------------------------------------------------------

    def draw(self, tag: int, rows: int, sharding=None):
        """(rows, D) f32 Gaussian rows, generated on the device."""
        import jax
        import jax.numpy as jnp

        key = jax.random.fold_in(self.key, tag)
        gen = jax.jit(
            lambda k: jax.random.normal(k, (rows, self.cfg["d"]), jnp.float32),
            out_shardings=sharding,
        )
        return gen(key)

    # -- timing --------------------------------------------------------------

    def timed(self, fn):
        """(result, seconds) with the result ready on the device; an
        ``Index`` is ready when its packed operands are."""
        import jax

        from repro.search import Index

        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(
            out.pack().operands() if isinstance(out, Index) else out)
        return out, time.perf_counter() - t0

    def times(self, record: dict, **seconds) -> None:
        """Wall times ending in ``block_until_ready``, only from a TPU."""
        if self.platform == "tpu":
            record["device_wall_s"] = seconds
        else:
            record["device_wall_s"] = f"not measured ({self.platform})"

    # -- reference and checks ------------------------------------------------

    def reference(self, queries, rows):
        """Exact top-k at highest precision, in query chunks whose score
        tile stays under ``REF_TILE_BYTES``."""
        import jax
        import numpy as np

        from repro.search import exact_search

        k, metric = self.cfg["k"], self.cfg["metric"]
        chunk = max(1, REF_TILE_BYTES // (4 * rows.shape[0]))
        fn = jax.jit(lambda q, db: exact_search(q, db, k, metric=metric))
        vals, idxs = [], []
        with jax.default_matmul_precision("highest"):
            for start in range(0, queries.shape[0], chunk):
                v, i = fn(queries[start:start + chunk], rows)
                vals.append(np.asarray(v))
                idxs.append(np.asarray(i))
        return np.concatenate(vals), np.concatenate(idxs)

    def recall(self, got_ids, ref_ids, expected: float) -> dict:
        """Recall@k against the reference, held to the target and to the
        planner's E[recall], each less the Hoeffding margin."""
        import numpy as np

        k = self.cfg["k"]
        per_query = np.array([
            len(set(a.tolist()) & set(b.tolist())) / k
            for a, b in zip(got_ids, ref_ids)
        ])
        mean = float(per_query.mean())
        eps = math.sqrt(math.log(1.0 / DELTA) / (2.0 * per_query.size))
        target = self.cfg["recall_target"]
        check(mean >= target - eps,
              f"recall@{k} {mean:.4f} below target {target} - {eps:.4f}")
        check(mean >= expected - eps,
              f"recall@{k} {mean:.4f} below E[recall] {expected:.4f} "
              f"- {eps:.4f}")
        return {"recall": mean, "margin": eps, "target": target,
                "expected_recall": expected, "queries": int(per_query.size)}

    def exact_values(self, queries, rows, got_vals, got_ids) -> float:
        """Returned l2 values equal ||x||^2/2 - <q, x> of the returned
        rows, within the float32 dot-product error bound (D + 2) * eps *
        (||x||^2/2 + sum |q_i x_i|).  Returns the largest error."""
        import numpy as np

        q = np.asarray(queries, np.float64)
        ids = np.asarray(got_ids)
        check(ids.min() >= 0, "a masked (-1) id was returned")
        x = np.asarray(rows[ids.ravel()], np.float64).reshape(
            *ids.shape, -1)
        half = 0.5 * np.sum(x * x, axis=-1)
        prod = q[:, None, :] * x
        exact = half - prod.sum(-1)
        bound = (q.shape[1] + 2) * np.finfo(np.float32).eps * (
            half + np.abs(prod).sum(-1))
        err = np.abs(np.asarray(got_vals, np.float64) - exact)
        worst = float((err / bound).max())
        check(worst <= 1.0,
              f"returned values differ from exact scores by up to "
              f"{worst:.3g}x the float32 bound")
        return float(err.max())

    def require_chip_path(self, index, queries):
        """The chip path runs: Pallas backend, interpret off, and a Mosaic
        kernel (``tpu_custom_call``) in the compiled search program.
        Returns what was seen and the seconds the compile took."""
        import jax

        from repro.search import backends

        t0 = time.perf_counter()
        compiled = jax.jit(index.search).lower(queries).compile()
        compile_s = time.perf_counter() - t0
        seen = {
            "auto_backend": backends.default_backend(),
            "interpret": index.interpret,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        }
        if self.on_chip:
            check(seen["auto_backend"] == "pallas",
                  f"backend auto resolved to {seen['auto_backend']!r}")
            check(seen["interpret"] is False, "Pallas interpret mode is on")
            check(seen["tpu_custom_call"],
                  "no tpu_custom_call in the compiled search program")
        return seen, compile_s

    def dispatches(self) -> dict:
        from repro.search import backends

        return dict(backends.DISPATCH_COUNTS)

    # -- phases --------------------------------------------------------------

    def batch_phase(self, name, db, queries, ref, *, storage):
        import numpy as np

        from repro.search import Index, telemetry

        cfg = self.cfg
        backend = "auto" if self.on_chip else "pallas"
        index, build_s = self.timed(lambda: Index.build(
            db, metric=cfg["metric"], k=cfg["k"],
            recall_target=cfg["recall_target"], backend=backend,
            storage=storage, plan="model", cluster="off",
        ))
        path, compile_s = self.require_chip_path(index, queries)
        telemetry.reset_all()
        out, first_s = self.timed(lambda: index.search(queries))
        steady = []
        for _ in range(3):
            out, s = self.timed(lambda: index.search(queries))
            steady.append(s)
        nref = cfg["ref_queries"]
        vals, ids = np.asarray(out.values[:nref]), np.asarray(out.indices[:nref])
        p = index.kernel_plan
        record = {
            "storage": storage, "n": cfg["n"], "m": cfg["m"],
            **path,
            "plan": {"block_m": p.block_m, "block_n": p.block_n,
                     "bin_size": p.bin_size, "k_scan": p.k_scan or p.k,
                     "query_block": p.query_block, "device": p.device},
            **self.recall(ids, ref[1], index.expected_recall),
            "max_value_error": self.exact_values(
                queries[:nref], db, vals, ids),
            "dispatches": self.dispatches(),
        }
        search_s = sum(steady) / len(steady)
        self.times(record, build=build_s, aot_compile=compile_s,
                   first_search=first_s, search=search_s,
                   qps=cfg["m"] / search_s)
        emit(name, record)
        return index

    def update_phase(self, index, db, queries, ref_ids):
        import jax.numpy as jnp
        import numpy as np

        cfg = self.cfg
        n, a = cfg["n"], cfg["updates"]
        new_rows = self.draw(3, a)
        rng = np.random.default_rng(self.args.seed)
        # The exact nearest row of every reference query, then random
        # original and appended rows, up to ``updates`` distinct ids.
        dead = np.unique(ref_ids[:, 0])
        pool = np.setdiff1d(np.arange(n + a), dead)
        dead = np.concatenate(
            [dead, rng.choice(pool, a - dead.size, replace=False)])
        _, update_s = self.timed(lambda: index.add(new_rows).delete(dead))
        out, first_s = self.timed(lambda: index.search(queries))
        out, search_s = self.timed(lambda: index.search(queries))
        ids = np.asarray(out.indices)
        check(not np.isin(ids, dead).any(), "a deleted id came back")
        check(ids.min() >= 0 and ids.max() < n + a, "id outside the index")
        rows = jnp.concatenate([db, new_rows])
        live = np.setdiff1d(np.arange(n + a), dead)
        nref = cfg["ref_queries"]
        _, ref_live = self.reference(
            queries[:nref], jnp.take(rows, jnp.asarray(live), axis=0))
        got = ids[:nref]
        record = {
            "added": a, "deleted": int(dead.size), "size": index.size,
            **self.recall(got, live[ref_live], index.expected_recall),
            "max_value_error": self.exact_values(
                queries[:nref], rows, np.asarray(out.values[:nref]), got),
            "dispatches": self.dispatches(),
        }
        self.times(record, add_delete=update_s, first_search=first_s,
                   search=search_s)
        emit("updates", record)

    def served_phase(self, index, queries):
        import numpy as np

        from repro.search import SearchServer, ServeConfig

        cfg = self.cfg
        rng = np.random.default_rng(self.args.seed + 1)
        qhost = np.asarray(queries)
        requests = []
        for _ in range(cfg["requests"]):
            rows = int(rng.choice(cfg["request_rows"]))
            start = int(rng.integers(0, qhost.shape[0] - rows))
            requests.append((qhost[start:start + rows],
                             int(rng.integers(1, cfg["k"] + 1))))
        results = [None] * len(requests)
        errors = []

        def client(worker: int, server) -> None:
            try:
                mine = range(worker, len(requests), cfg["threads"])
                tickets = [(i, server.submit(requests[i][0],
                                             k=requests[i][1]))
                           for i in mine]
                for i, t in tickets:
                    results[i] = t.result(timeout=600)
            except Exception as e:  # reported and failed below
                errors.append(repr(e))

        config = ServeConfig(max_batch=cfg["max_batch"])
        t0 = time.perf_counter()
        with SearchServer(index, config, warmup=True) as server:
            warmup_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(w, server))
                       for w in range(cfg["threads"])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            served_s = time.perf_counter() - t0
            check(not any(t.is_alive() for t in threads),
                  "a client thread did not finish")
            check(not errors, f"client errors: {errors}")
            health = server.health()
            stats = server.stats()
        check(health["status"] == "ok", f"health is {health['status']!r}")
        identical = 0
        for (rows, k), res in zip(requests, results):
            want = index.search(rows)
            check(np.array_equal(res.values, np.asarray(want.values)[:, :k])
                  and np.array_equal(res.indices,
                                     np.asarray(want.indices)[:, :k]),
                  "a served result differs from Index.search")
            identical += 1
        record = {
            "requests": len(requests), "bit_identical": identical,
            "rows": int(sum(r.shape[0] for r, _ in requests)),
            "health": health["status"], "batches": stats["batches"],
            "buckets": list(stats["buckets"]),
            "dispatches": self.dispatches(),
        }
        self.times(record, warmup=warmup_s, served=served_s)
        emit("served", record)

    def sharded_phase(self):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.search import Index

        cfg = self.cfg
        count = len(self.devices)
        check(count == 4, f"--chips 4 needs 4 devices, found {count}")
        mesh = make_mesh((4,), ("model",))
        n = cfg["n_sharded"]
        db = self.draw(4, n, NamedSharding(mesh, P("model", None)))
        queries = self.draw(2, cfg["m"])
        nref = cfg["ref_queries"]
        ref = self.reference(queries[:nref], db)
        sharded, build_s = self.timed(lambda: Index.build(
            db, metric=cfg["metric"], k=cfg["k"],
            recall_target=cfg["recall_target"], backend="sharded",
            plan="model", cluster="off",
        ).shard(mesh, db_axis="model"))
        packed_db = sharded.pack().db
        shards = packed_db.addressable_shards
        rows_per = sorted(s.data.shape[0] for s in shards)
        check(len({s.device for s in shards}) == 4
              and rows_per == [packed_db.shape[0] // 4] * 4,
              f"packed database is not split in quarters: {rows_per}")
        out, first_s = self.timed(lambda: sharded.search(queries))
        out, search_s = self.timed(lambda: sharded.search(queries))
        ids = np.asarray(out.indices[:nref])
        record = {
            "n": n, "m": cfg["m"], "chips": count,
            "rows_per_chip": rows_per,
            **self.recall(ids, ref[1], sharded.expected_recall),
            "max_value_error": self.exact_values(
                queries[:nref], db, np.asarray(out.values[:nref]), ids),
            "dispatches": self.dispatches(),
        }
        self.times(record, build=build_s, first_search=first_s,
                   search=search_s, qps=cfg["m"] / search_s)
        emit("sharded", record)

    def run(self) -> None:
        if self.args.chips == 4:
            self.sharded_phase()
            return
        cfg = self.cfg
        db = self.draw(1, cfg["n"])
        queries = self.draw(2, cfg["m"])
        ref = self.reference(queries[:cfg["ref_queries"]], db)
        index = self.batch_phase("batch_f32", db, queries, ref,
                                 storage="f32")
        self.batch_phase("batch_int8", db, queries, ref, storage="int8")
        self.update_phase(index, db, queries, ref[1])
        self.served_phase(index, queries)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"no TPU found: JAX's devices are {platform!r}; "
              f"nothing was run", flush=True)
        return 1
    run = Run(args)
    print(f"devices: {platform} {devices[0].device_kind!r} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {run.cache_dir}", flush=True)
    run.run()
    if not run.on_chip:
        print(f"no chip found: --tiny rehearsal on {platform}, not a chip "
              f"result", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

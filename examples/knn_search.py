"""Distributed KNN example (paper §7) through the unified search API: shard
an ``Index`` over a device mesh, PartialReduce per shard, all-gather the bin
winners, rescore globally.

Also demonstrates the kNN-LM retrieval integration, index-free updates on
the sharded index, and the tuning-free cluster-pruned front-end
(``cluster="auto"``) on a large clusterable corpus.  Uses 8 simulated
devices (safe to re-exec: this file sets XLA_FLAGS before importing jax).

  PYTHONPATH=src python examples/knn_search.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.pipeline import make_vector_dataset  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.retrieval.datastore import KNNDatastore, knn_lm_logits  # noqa: E402
from repro.search import Index, exact_search  # noqa: E402


def recall(a, e):
    return float(np.mean([
        len(set(x.tolist()) & set(y.tolist())) / len(y)
        for x, y in zip(np.asarray(a), np.asarray(e))
    ]))


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

    # Database + held-out queries split from one draw: clustered indexes
    # (cluster="auto" enables above the planner crossover — these builds
    # qualify) assume queries are drawn from the database distribution,
    # the same contract every IVF system carries.  For genuinely
    # out-of-distribution query streams, build with cluster="off".
    full = jnp.asarray(
        make_vector_dataset(65536 + 64, 64, metric="cosine", seed=0)
    )
    db, q = full[:65536], full[65536:]

    for metric in ("mips", "l2"):
        index = Index.build(db, metric=metric, k=10, recall_target=0.95)
        sharded = index.shard(mesh, db_axis="model", batch_axis="data")
        _, idx = sharded.search(q)
        _, exact = exact_search(q, db, 10, metric=metric)
        print(f"distributed {metric:4s} recall: {recall(idx, exact):.3f}  "
              f"({sharded!r})")

    # Index-free updates work sharded too: append rows, tombstone others.
    sharded = Index.build(db[:65024], k=10).shard(mesh, db_axis="model")
    sharded.add(db[65024:])
    _, idx = sharded.search(q)
    _, exact = exact_search(q, db, 10)
    print(f"after sharded add:   recall={recall(idx, exact):.3f}")

    # Cluster-pruned scan: on a large clusterable corpus (embeddings,
    # mixtures), cluster="auto" — the default — puts a planner-derived
    # k-means front-end before the scan.  No knobs: probe count and spill
    # come from (N, k, recall_target); below the planner crossover the
    # index is bit-identical to cluster="off".
    rng = np.random.default_rng(7)
    centers = 3.0 * rng.standard_normal((64, 32)).astype(np.float32)
    cdb = jnp.asarray(
        centers[rng.integers(0, 64, size=32768)]
        + rng.standard_normal((32768, 32)).astype(np.float32))
    cq = jnp.asarray(
        centers[rng.integers(0, 64, size=256)]
        + rng.standard_normal((256, 32)).astype(np.float32))
    clustered = Index.build(cdb, metric="l2", k=10, recall_target=0.9,
                            cluster="auto")
    info = clustered.explain()["cluster"]
    _, idx = clustered.search(cq)
    _, exact = exact_search(cq, cdb, 10, metric="l2")
    print(f"cluster-pruned l2:   recall={recall(idx, exact):.3f} "
          f"(expected {info['expected_recall']:.3f} = "
          f"{info['collision_term']:.3f} collision x "
          f"{info['miss_term']:.3f} miss), "
          f"scanned {info['scanned_fraction']:.1%} of N "
          f"with {info['probes']}/{info['num_clusters']} probes")

    # kNN-LM: retrieve neighbour tokens and interpolate with LM logits.
    value_tokens = jax.random.randint(jax.random.PRNGKey(2), (db.shape[0],), 0, 1000)
    store = KNNDatastore(db, value_tokens, mesh, k=16)
    scores, toks = store.lookup(q)
    lm_logits = jax.random.normal(jax.random.PRNGKey(3), (q.shape[0], 1000))
    mixed = knn_lm_logits(lm_logits, scores, toks, lam=0.25)
    print(f"kNN-LM mixed logits: {mixed.shape}, "
          f"finite={bool(jnp.all(jnp.isfinite(mixed)))}")


if __name__ == "__main__":
    main()

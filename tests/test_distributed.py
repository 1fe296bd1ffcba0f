"""Distributed KNN (paper §7) on 8 fake devices.

Runs via the ``fake_devices`` subprocess harness (tests/conftest.py) so
the main pytest process keeps a single CPU device.
"""
_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import sharded_mips, sharded_l2nns
from repro.retrieval.datastore import KNNDatastore, knn_lm_logits
from repro.search import Index, exact_search

mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (16, 64))
db = jax.random.normal(jax.random.PRNGKey(1), (4096, 64))
qs = jax.device_put(q, NamedSharding(mesh, P("data", None)))
dbs = jax.device_put(db, NamedSharding(mesh, P("model", None)))

def recall(a, e):
    return np.mean([len(set(x.tolist()) & set(y.tolist()))/len(y)
                    for x, y in zip(np.asarray(a), np.asarray(e))])

_, i = sharded_mips(qs, dbs, 10, mesh, batch_axis="data", recall_target=0.95)
_, ei = jax.lax.top_k(q @ db.T, 10)
r = recall(i, ei)
assert r >= 0.9, f"mips recall {r}"

_, i2 = sharded_l2nns(qs, dbs, 10, mesh, batch_axis="data", recall_target=0.95)
d = np.linalg.norm(np.asarray(q)[:,None]-np.asarray(db)[None], axis=-1)
ei2 = np.argsort(d, -1)[:, :10]
r2 = recall(i2, ei2)
assert r2 >= 0.9, f"l2 recall {r2}"

# kNN-LM datastore over the mesh
tokens = jax.random.randint(jax.random.PRNGKey(2), (4096,), 0, 1000)
ds = KNNDatastore(db, tokens, mesh, k=8)
scores, toks = ds.lookup(qs)
assert scores.shape == (16, 8) and toks.shape == (16, 8)
lm_logits = jax.random.normal(jax.random.PRNGKey(3), (16, 1000))
mixed = knn_lm_logits(lm_logits, scores, toks)
assert mixed.shape == (16, 1000)
assert bool(jnp.all(jnp.isfinite(mixed)))

# unified front door: sharded Index with add/delete on 8 real shards
for metric in ("mips", "l2", "cosine"):
    sharded = Index.build(db[:3072], metric=metric, k=10,
                          recall_target=0.95).shard(
        mesh, db_axis="model", batch_axis="data")
    sharded.add(db[3072:])
    _, si = sharded.search(q)
    _, ei = exact_search(q, db, 10, metric=metric)
    sr = recall(si, ei)
    assert sr >= sharded.expected_recall - 0.07, f"{metric} sharded {sr}"
sharded.delete(np.asarray(ei)[:, 0])
_, si2 = sharded.search(q)
assert not set(np.asarray(si2).ravel().tolist()) & set(
    np.asarray(ei)[:, 0].tolist())
publish({"mips_recall": r, "l2_recall": r2})
"""


def test_distributed_knn_8_devices(fake_devices):
    res = fake_devices(_SCRIPT, n=8)
    assert res["mips_recall"] >= 0.9 and res["l2_recall"] >= 0.9

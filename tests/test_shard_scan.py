"""The sharded backend's per-shard scan: the fused Pallas kernel over each
shard's block of the per-shard kernel layout, against the XLA scan in
each shard and the one-device Pallas search.

Four fake devices (one subprocess for every case), interpret mode, tiny
sizes: 4 x 1,003 rows, so each shard's block of ``block_n``-aligned rows
ends in its own masked tail, with rows tombstoned on two shards.  In the
high-recall regime every layout returns the exact top-k, so the three
searches agree id for id and value for value.  A second part plans bins
wider than the kernel tile and checks that both per-shard scans cap them.
"""
import numpy as np
import pytest

from repro.core.binning import expected_recall, plan_bins

CASES = [("l2", "f32"), ("mips", "f32"), ("cosine", "f32"),
         ("l2", "int8"), ("mips", "int8"), ("cosine", "int8")]

_CHILD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.search import Index, backends

SHARDS, N_LOCAL, D, M, K = 4, 1003, 24, 40, 7
N = SHARDS * N_LOCAL
DEAD = np.array([5, 17, 2 * N_LOCAL + 3, 2 * N_LOCAL + 900])  # shards 0, 2
rng = np.random.default_rng(3)
db = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
q = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
mesh = make_mesh((SHARDS,), ("model",))
out = {"db": np.asarray(db), "q": np.asarray(q), "dead": DEAD,
       "n_local": N_LOCAL}


def build(metric, storage, backend, **kw):
    return Index.build(db, metric=metric, k=K, recall_target=0.999,
                       storage=storage, backend=backend, cluster="off", **kw)


for metric, storage in @CASES@:
    one = build(metric, storage, "pallas", interpret=True)
    kernel = build(metric, storage, "sharded", interpret=True).shard(
        mesh, db_axis="model")
    xla = build(metric, storage, "sharded").shard(mesh, db_axis="model")
    case = {}
    for name, index in (("one", one), ("kernel", kernel), ("xla", xla)):
        index.delete(DEAD)
        backends.SHARD_SCAN_COUNTS.clear()
        vals, ids = index.search(q)
        pk = index.pack()
        case[name] = {
            "vals": np.asarray(vals), "ids": np.asarray(ids),
            "scans": dict(backends.SHARD_SCAN_COUNTS),
            "block_n": pk.block_n,
            "blocks": sorted(s.data.shape[0]
                             for s in pk.db.addressable_shards),
        }
    out[(metric, storage)] = case

# Appends into spare capacity (rows land in the blocks of the shards that
# own their ids), then past it (growth re-lays every block).
grown, kept = {}, {}
for name, kw in (("kernel", {"interpret": True}), ("xla", {})):
    index = Index.build(db[:3000], metric="l2", k=K, recall_target=0.999,
                        backend="sharded", cluster="off", capacity=3500,
                        capacity_block=100, **kw).shard(mesh, db_axis="model")
    index.add(db[3000:3400])
    index.delete(DEAD)
    index.add(db[3400:])
    vals, ids = index.search(q)
    grown[name] = {"vals": np.asarray(vals), "ids": np.asarray(ids),
                   "capacity": index.capacity,
                   "block_n": index.pack().block_n}
    kept[name] = index
out["grown"] = grown

# A snapshot of the kernel layout restores unmeshed, and shards again
# from its rows with each shard's tail dropped.
import tempfile, os
with tempfile.TemporaryDirectory() as tmp:
    kept["kernel"].save(os.path.join(tmp, "snap"))
    back = Index.restore(os.path.join(tmp, "snap"))
restored_pk = back._packed
vals, ids = back.shard(mesh, db_axis="model").search(q)
out["restored"] = {"vals": np.asarray(vals), "ids": np.asarray(ids),
                   "shards": restored_pk.shards,
                   "block_n": restored_pk.block_n}

# Bins wider than the tile: at k 2 and target 0.5 the recall plan lays
# 512-row bins, the pinned 256-row tile caps them, in the kernel's shards
# and in the XLA scan's alike.
for name, kw in (("capped", {"interpret": True}), ("capped_xla", {})):
    wide = Index.build(db, metric="l2", k=2, recall_target=0.5,
                       backend="sharded", max_block_n=256, block_m=8,
                       cluster="off", **kw).shard(mesh, db_axis="model")
    vals, ids = wide.search(q)
    pk = wide.pack()
    out[name] = {"vals": np.asarray(vals), "ids": np.asarray(ids),
                 "bin_size": pk.bin_size, "block_n": pk.block_n,
                 "expected": wide.expected_recall,
                 "plan_bin": wide.kernel_plan.bin_size,
                 "plan_expected": wide.kernel_plan.expected_recall}
publish(out)
"""


@pytest.fixture(scope="module")
def four(fake_devices):
    return fake_devices(_CHILD.replace("@CASES@", repr(CASES)), n=4)


def _scores(db, q, metric):
    """Float64 scores in the public convention of each metric."""
    db, q = db.astype(np.float64), q.astype(np.float64)
    if metric == "cosine":
        db = db / np.linalg.norm(db, axis=1, keepdims=True)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
    if metric == "l2":
        return 0.5 * (db * db).sum(1)[None, :] - q @ db.T
    return q @ db.T


@pytest.mark.parametrize("metric,storage", CASES)
def test_kernel_in_each_shard_matches_xla_shards_and_one_device(
        four, metric, storage):
    case = four[(metric, storage)]
    n_local, dead = four["n_local"], set(four["dead"].tolist())
    kernel, xla, one = case["kernel"], case["xla"], case["one"]
    # Each shard holds its own block, padded past its 1,003 rows.
    rows = kernel["blocks"]
    assert len(rows) == 4 and len(set(rows)) == 1 and rows[0] > n_local
    assert rows[0] % kernel["block_n"] == 0
    assert kernel["scans"] == {"pallas": 1} and xla["scans"] == {"xla": 1}
    ids = kernel["ids"]
    n = 4 * n_local
    assert ((ids >= 0) & (ids < n)).all()
    assert all(len(set(row)) == len(row) for row in ids.tolist())
    assert not dead & set(ids.ravel().tolist())
    assert len(set((ids // n_local).ravel().tolist())) == 4
    for other in (xla, one):
        np.testing.assert_array_equal(ids, other["ids"])
        np.testing.assert_allclose(kernel["vals"], other["vals"],
                                   rtol=1e-6, atol=1e-6)
    # Each id carries its shard's offset: its value is that row's score.
    want = np.take_along_axis(
        _scores(four["db"], four["q"], metric), ids, axis=1)
    np.testing.assert_allclose(kernel["vals"], want, rtol=1e-4, atol=1e-4)


def test_add_and_growth_on_the_kernel_layout(four):
    kernel, xla = four["grown"]["kernel"], four["grown"]["xla"]
    assert kernel["capacity"] == xla["capacity"] == 4100
    assert kernel["block_n"] and not xla["block_n"]
    np.testing.assert_array_equal(kernel["ids"], xla["ids"])
    np.testing.assert_allclose(kernel["vals"], xla["vals"], rtol=1e-6,
                               atol=1e-6)
    # Exact top-k over every appended row, tombstones left out.
    scores = _scores(four["db"], four["q"], "l2")
    scores[:, four["dead"]] = np.inf
    want = np.sort(scores, axis=1)[:, :7]
    np.testing.assert_allclose(kernel["vals"], want, rtol=1e-4, atol=1e-4)


def test_kernel_layout_snapshot_restores_and_shards_again(four):
    restored, kernel = four["restored"], four["grown"]["kernel"]
    assert restored["shards"] == 4 and restored["block_n"]
    np.testing.assert_array_equal(restored["ids"], kernel["ids"])
    np.testing.assert_allclose(restored["vals"], kernel["vals"], rtol=1e-6,
                               atol=1e-6)


def test_bins_are_capped_at_the_kernel_tile(four):
    n_local = four["n_local"]
    recall_bin = plan_bins(n_local, 2, 0.5,
                           reduction_input_size_override=4 * n_local)
    assert recall_bin.bin_size == 512
    capped = four["capped"]
    assert capped["block_n"] == 256
    assert capped["bin_size"] == capped["plan_bin"] == 256
    # 4 shards x 4 bins: more bins than the recall plan's 4 x 2.
    assert capped["expected"] == pytest.approx(expected_recall(16, 2))
    ids = capped["ids"]
    exact = np.argsort(_scores(four["db"], four["q"], "l2"), axis=1)[:, :2]
    recall = np.mean([len(set(a) & set(b)) / 2
                      for a, b in zip(ids.tolist(), exact.tolist())])
    assert recall >= capped["expected"] - 0.1
    want = np.take_along_axis(_scores(four["db"], four["q"], "l2"), ids, 1)
    np.testing.assert_allclose(capped["vals"], want, rtol=1e-4, atol=1e-4)


def test_xla_shards_scan_the_planned_bins(four):
    """Off the kernel, each shard's XLA scan lays the capped bins too: the
    index reports the E[recall] of the bins it scans, and below full recall
    it returns what the kernel's shards return."""
    xla, kernel = four["capped_xla"], four["capped"]
    assert xla["block_n"] == 0
    assert xla["bin_size"] == xla["plan_bin"] == 256
    assert xla["expected"] == xla["plan_expected"] == kernel["expected"]
    np.testing.assert_array_equal(xla["ids"], kernel["ids"])
    np.testing.assert_allclose(xla["vals"], kernel["vals"], rtol=1e-6,
                               atol=1e-6)

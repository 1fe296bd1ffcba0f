"""Unified repro.search API: backend parity, updates, compile cache.

Covers the acceptance contract of the front-door redesign:
  * per-metric parity across xla / pallas-interpret / sharded backends,
  * recall after Index.add / Index.delete meets BinPlan.expected_recall on
    all three backends,
  * no retrace on same-shape repeat searches (compile cache),
  * the L2 relaxed-distance value contract holds identically everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.search import (
    Index,
    SearchSpec,
    backends,
    exact_search,
    get_metric,
    l2nns,
)
from repro.search.backends import TRACE_COUNTS

METRICS = ("mips", "l2", "cosine")
K = 10


def _recall(approx_idx, exact_idx):
    r = []
    for a, e in zip(np.asarray(approx_idx), np.asarray(exact_idx)):
        r.append(len(set(a.tolist()) & set(e.tolist())) / len(e))
    return float(np.mean(r))


@pytest.fixture(scope="module")
def data():
    q = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    db = jax.random.normal(jax.random.PRNGKey(1), (4096, 32))
    return q, db


@pytest.fixture(scope="module")
def mesh1():
    """Single-device mesh: exercises the sharded code path in-process."""
    return make_mesh((1,), ("model",))


def _build(db, metric, backend, mesh=None, **kw):
    if backend == "sharded":
        return Index.build(
            db, metric=metric, k=K, recall_target=0.95, **kw
        ).shard(mesh, db_axis="model")
    return Index.build(
        db, metric=metric, k=K, recall_target=0.95, backend=backend, **kw
    )


# --- backend x metric parity ------------------------------------------------


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("backend", ["xla", "pallas", "sharded"])
def test_backend_meets_recall_target(data, mesh1, metric, backend):
    q, db = data
    index = _build(db, metric, backend, mesh1)
    vals, idxs = index.search(q)
    _, exact = exact_search(q, db, K, metric=metric)
    assert vals.shape == idxs.shape == (64, K)
    assert _recall(idxs, exact) >= index.expected_recall - 0.05


def test_sharded_scan_bounds_its_score_tile(mesh1):
    """A shard scans its rows in query sub-blocks sized like the xla
    path's query block, so its (queries, n_local) score tile stays under
    the planner's budget; results equal searches too small to sub-block."""
    from repro.search.plan import _plan_query_block

    n, m, d = 16384, 2500, 16
    db = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    q = jax.random.normal(jax.random.PRNGKey(3), (m, d))
    assert 1000 <= _plan_query_block(n, "xla") < m  # sub-blocking engages
    # Planned for the sharded backend, as the datastore builds it: the
    # query block stays 4096, so the sub-blocking happens inside the shard.
    index = Index.build(db, metric="l2", k=K, backend="sharded",
                        cluster="off").shard(mesh1, db_axis="model")
    assert index.spec.query_block >= m
    whole = index.search(q)
    parts = [index.search(q[i:i + 1000]) for i in range(0, m, 1000)]
    for got, want in zip(whole, zip(*parts)):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.concatenate(want))


_EXPLICIT_MESH_SCRIPT = r"""
import jax, numpy as np
from jax.sharding import AxisType, Mesh
from repro.launch.mesh import make_mesh
from repro.search import Index

auto = make_mesh((2, 4), ("data", "model"))
explicit = Mesh(auto.devices, auto.axis_names,
                axis_types=(AxisType.Explicit,) * 2)
db = jax.random.normal(jax.random.PRNGKey(1), (4096, 64))
q = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
new = jax.random.normal(jax.random.PRNGKey(2), (100, 64))
indexes = [Index.build(db, metric="l2", k=10, query_block=32)
           .shard(mesh, db_axis="model", batch_axis="data")
           for mesh in (explicit, auto)]


def same():
    got, want = (ix.search(q) for ix in indexes)
    return all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(got, want))


checks = [same()]  # 128 queries streamed in blocks of 32
for ix in indexes:
    ix.add(new)
checks.append(same())
for ix in indexes:
    ix.delete(np.arange(10))
checks.append(same())
publish({"same": checks})
"""


def test_shard_takes_explicit_axis_mesh_as_auto(fake_devices):
    """A mesh with Explicit axes (``jax.make_mesh``'s default) reaches
    ``Index.shard`` as the same devices with Auto axes: on 8 fake devices
    the streamed search, ``add`` and ``delete`` raise no sharding error and
    match an Auto mesh bit for bit."""
    assert fake_devices(_EXPLICIT_MESH_SCRIPT, n=8)["same"] == [True] * 3


@pytest.mark.parametrize("metric", METRICS)
def test_cross_backend_value_parity(data, mesh1, metric):
    """Same plan => same candidates; values agree in sign AND magnitude
    across all three backends wherever indices agree (satellite: one L2
    convention, asserted cross-backend)."""
    q, db = data
    results = {
        b: Index.build(
            db, metric=metric, k=K, recall_target=0.95, backend=b
        ).search(q)
        for b in ("xla", "pallas")
    }
    results["sharded"] = _build(db, metric, "sharded", mesh1).search(q)
    ref_v, ref_i = results["xla"]
    for b in ("pallas", "sharded"):
        v, i = results[b]
        agree = np.asarray(i) == np.asarray(ref_i)
        assert agree.mean() > 0.95  # near-ties may reorder
        np.testing.assert_allclose(
            np.asarray(v)[agree], np.asarray(ref_v)[agree], rtol=1e-4
        )
        if get_metric(metric).negate_output:
            # ascending best-first (distances)
            assert (np.diff(np.asarray(v), axis=-1) >= -1e-5).all()
        else:
            # descending best-first (similarities)
            assert (np.diff(np.asarray(v), axis=-1) <= 1e-5).all()


def test_l2_values_are_relaxed_distances(data):
    """The documented contract: ||x||^2/2 - <q,x> at the returned indices."""
    q, db = data
    vals, idxs = Index.build(db, metric="l2", k=K, backend="xla").search(q)
    hn = 0.5 * np.sum(np.asarray(db) ** 2, axis=-1)
    expect = hn[np.asarray(idxs)] - np.take_along_axis(
        np.asarray(q) @ np.asarray(db).T, np.asarray(idxs), axis=-1
    )
    np.testing.assert_allclose(np.asarray(vals), expect, rtol=1e-4, atol=1e-5)
    # legacy functional path agrees bit-for-bit in convention
    lv, li = l2nns(q, db, K, recall_target=0.95)
    np.testing.assert_array_equal(np.asarray(li), np.asarray(idxs))
    np.testing.assert_allclose(
        np.asarray(lv), np.asarray(vals), rtol=1e-5, atol=1e-6
    )


# --- frequent updates: add / delete -----------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas", "sharded"])
def test_recall_after_add_and_delete(data, mesh1, backend):
    """Index.add / Index.delete followed by .search meets the plan's
    expected recall on every backend (acceptance criterion)."""
    q, db = data
    index = _build(db[:2048], "mips", backend, mesh1)
    index.add(db[2048:])
    assert index.size == 4096

    _, exact = exact_search(q, db, K, metric="mips")
    _, idxs = index.search(q)
    assert _recall(idxs, exact) >= index.expected_recall - 0.05

    # tombstone each query's current top-1; they must vanish from results
    # and recall against the remaining rows must still meet the plan.
    top1 = np.unique(np.asarray(exact)[:, 0])
    index.delete(top1)
    assert index.size == 4096 - len(top1)
    _, idxs2 = index.search(q)
    assert not set(np.asarray(idxs2).ravel().tolist()) & set(top1.tolist())

    scores = np.asarray(q) @ np.asarray(db).T
    scores[:, top1] = -np.inf
    exact_live = np.argsort(-scores, axis=-1)[:, :K]
    assert _recall(idxs2, exact_live) >= index.expected_recall - 0.05


def test_delete_with_duplicate_ids_counts_once(data):
    _, db = data
    index = Index.build(db[:64], k=4)
    index.delete([5, 5, 5])
    assert index.size == 63
    index.delete([5, 6])  # 5 already dead: only 6 is newly removed
    assert index.size == 62


def test_add_grows_capacity_in_blocks(data):
    _, db = data
    index = Index.build(db[:1000], k=K, capacity_block=512)
    assert index.capacity == 1000
    index.add(db[1000:1100])
    assert index.capacity % 512 == 0 and index.capacity >= 1100
    assert index.size == 1100
    # padded rows are tombstoned: never returned
    q = jax.random.normal(jax.random.PRNGKey(7), (8, 32))
    _, idxs = index.search(q)
    assert int(np.asarray(idxs).max()) < 1100


# --- compile cache ----------------------------------------------------------


def test_no_retrace_on_same_shape_repeat(data):
    q, db = data
    index = Index.build(db, metric="mips", k=K, backend="xla")
    index.search(q)
    backends.reset_trace_counts()  # warmup traced; steady state must not
    for _ in range(3):
        index.search(q)
    assert not dict(TRACE_COUNTS)
    info = index.cache_info()
    assert info["hits"] >= 3 and info["entries"] == 1
    # a new query shape is a new entry, not a silent retrace of the old one
    index.search(q[:16])
    assert index.cache_info()["entries"] == 2


def test_delete_does_not_retrace(data):
    q, db = data
    index = Index.build(db, metric="l2", k=K, backend="xla")
    index.search(q)
    backends.reset_trace_counts()
    index.delete([0, 1, 2])
    index.search(q)  # same shapes: only the bias operand changed
    assert not dict(TRACE_COUNTS)


# --- API surface ------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError):
        SearchSpec(k=0)
    with pytest.raises(ValueError):
        SearchSpec(recall_target=1.5)
    with pytest.raises(ValueError):
        SearchSpec(backend="gpu")
    with pytest.raises(ValueError):
        Index.build(jnp.zeros((16, 4)), metric="manhattan")


def test_sharded_backend_requires_mesh():
    index = Index.build(jnp.zeros((64, 4)), backend="sharded")
    with pytest.raises(ValueError, match="mesh"):
        index.search(jnp.zeros((2, 4)))


def test_query_auto_tiling_matches_single_shot(data):
    q, db = data
    whole = Index.build(db, k=K, backend="xla").search(q)
    tiled = Index.build(db, k=K, backend="xla", query_block=24).search(q)
    np.testing.assert_array_equal(
        np.asarray(whole.indices), np.asarray(tiled.indices)
    )
    np.testing.assert_allclose(
        np.asarray(whole.values), np.asarray(tiled.values), rtol=1e-6
    )


def test_cosine_works_on_pallas_backend(data):
    """The old API had cosine only on the XLA path; the front door closes
    that gap (raw, unnormalized database in, normalized search out)."""
    q, db = data
    db_scaled = db * jnp.linspace(0.1, 5.0, db.shape[0])[:, None]  # wild norms
    index = Index.build(db_scaled, metric="cosine", k=K, backend="pallas")
    _, idxs = index.search(q)
    _, exact = exact_search(q, db_scaled, K, metric="cosine")
    assert _recall(idxs, exact) >= index.expected_recall - 0.05


def test_default_backend_resolution():
    assert backends.default_backend(None) in ("xla", "pallas")
    index = Index.build(jnp.ones((128, 8)))
    assert index._resolve_backend() in ("xla", "pallas")

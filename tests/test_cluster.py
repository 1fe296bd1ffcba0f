"""Tests for the cluster-pruned scan front-end (``repro.search.cluster``).

Covers the subsystem's contracts end to end:

  * the planner derives every parameter (C, rho, capacities, scan budget)
    from (N, k, recall_target) — there are no user knobs, and the spec
    rejects anything other than "auto"/"off";
  * below the cost crossover ``cluster="auto"`` builds nothing and is
    bit-identical to ``cluster="off"`` on every backend/storage combo;
  * above the crossover the pruned scan returns valid, live, exact-scored
    neighbours on xla/pallas/sharded, composes with the quantized storage
    tiers, and never leaks an empty table slot or a tombstoned row;
  * the packed-state contracts survive: add assigns incrementally, spill
    growth triggers a lazy recluster at add() time, and the steady state
    stays zero-retrace / one-dispatch / zero-db-sized-pads;
  * ``Index.explain()`` reports the scanned fraction and the
    collision x miss recall decomposition.

The correctness corpus is a mixture of Gaussians (queries drawn from the
same component centers): that is the regime the miss bound models.  On
i.i.d. Gaussian data all points are nearly equidistant and no coarse
quantizer can prune well — the planner's crossover still says "prune"
there (it prices FLOPs, not geometry), but the build-time sampled miss
check measures the geometry and rejects the tables, falling back to the
dense scan bit-identically (covered below).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.search import (
    ClusterPlan,
    Index,
    SearchServer,
    SearchSpec,
    ServeConfig,
    VirtualClock,
    exact_search,
    plan_clusters,
)
from repro.search import backends
from repro.search import cluster as clusterlib
from repro.search.backends import DISPATCH_COUNTS, TRACE_COUNTS
from repro.search.packed import PACK_EVENTS, reset_pack_events

N = 8192          # above the planner crossover
SMALL_N = 2048    # below it
D = 32
K = 10
TARGET = 0.95
COMPONENTS = 64


@pytest.fixture(autouse=True)
def _reset_counters():
    backends.reset_trace_counts()
    backends.reset_dispatch_counts()
    reset_pack_events()
    yield


def _mixture(seed, n=N, m=64, d=D):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(COMPONENTS, d)) * 2.5
    db = centers[rng.integers(0, COMPONENTS, n)] + rng.normal(size=(n, d))
    q = centers[rng.integers(0, COMPONENTS, m)] + rng.normal(size=(m, d))
    return jnp.asarray(db, jnp.float32), jnp.asarray(q, jnp.float32)


@pytest.fixture(scope="module")
def data():
    return _mixture(0)


def _recall(idxs, truth, k=K):
    a, b = np.asarray(idxs), np.asarray(truth)
    return np.mean(
        [len(set(r.tolist()) & set(t.tolist())) / k for r, t in zip(a, b)]
    )


# --- planner derivations -----------------------------------------------------


def test_plan_clusters_crossover():
    """Small N stays dense; large N enables pruning — the decision is the
    planner's cost model, never a user knob."""
    for n in (1024, SMALL_N, 4096):
        cp = plan_clusters(n=n, k_scan=K, recall_target=TARGET)
        assert isinstance(cp, ClusterPlan) and not cp.enabled
    for n in (N, 2 * N, 8 * N):
        cp = plan_clusters(n=n, k_scan=K, recall_target=TARGET)
        assert cp.enabled
        assert cp.num_clusters & (cp.num_clusters - 1) == 0  # power of two
        assert 1 <= cp.probes < cp.num_clusters
        assert cp.scan_rows < n
        assert 0.0 < cp.target_scan < 1.0
        assert cp.predicted_speedup >= 2.0


@pytest.mark.parametrize("target", [0.90, 0.95, 0.99])
@pytest.mark.parametrize("n", [N, 2 * N])
def test_plan_clusters_product_bound_meets_target(n, target):
    """collision x miss >= target for every derivation the planner emits."""
    cp = plan_clusters(n=n, k_scan=32, recall_target=target)
    assert cp.enabled
    decomp = cp.recall_decomposition(32)
    assert decomp["collision_term"] <= 1.0
    assert decomp["miss_term"] == 1.0 - cp.miss_budget
    assert decomp["expected_recall"] >= target
    assert decomp["expected_recall"] == pytest.approx(
        decomp["collision_term"] * decomp["miss_term"]
    )


def test_spec_rejects_cluster_knobs():
    with pytest.raises(ValueError, match="planner-derived"):
        SearchSpec(cluster="16-probes")
    assert SearchSpec().cluster == "auto"  # the default is auto


def test_capacity_slack_guarantees_table_space():
    """C * rows_per_cluster >= 1.25 N: the greedy fill can always place a
    row somewhere, so build never drops data."""
    for n in (N, 3 * N, 16 * N):
        cp = plan_clusters(n=n, k_scan=K, recall_target=TARGET)
        assert cp.num_clusters * cp.rows_per_cluster >= 1.25 * n


# --- off / below-crossover: bit-identical ------------------------------------


@pytest.mark.parametrize("backend,metric,storage", [
    ("xla", "mips", "f32"),
    ("xla", "l2", "int8"),
    ("xla", "cosine", "f32"),
    ("pallas", "l2", "f32"),
])
def test_below_crossover_auto_is_bit_identical_to_off(
    backend, metric, storage
):
    db, q = _mixture(1, n=SMALL_N)
    auto = Index.build(db, metric=metric, k=K, backend=backend,
                       storage=storage, cluster="auto")
    off = Index.build(db, metric=metric, k=K, backend=backend,
                      storage=storage, cluster="off")
    assert auto.pack().cluster is None  # nothing was built
    va, ia = auto.search(q)
    vo, io = off.search(q)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(io))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vo))


def test_cluster_off_never_builds_tables(data):
    db, _ = data
    index = Index.build(db, metric="l2", k=K, backend="xla", cluster="off")
    assert index.pack().cluster is None
    assert PACK_EVENTS["cluster_built"] == 0


# --- pruned scan correctness -------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
def test_pruned_scan_returns_exactly_scored_live_rows(data, backend, metric):
    """Every returned id is a real (live) row and its value is the exact
    metric score of that row — pruning changes WHICH rows are scanned,
    never how a scanned row is scored."""
    db, q = data
    index = Index.build(db, metric=metric, k=K, backend=backend,
                        recall_target=TARGET)
    assert index.pack().cluster is not None  # planner enabled pruning
    vals, idxs = index.search(q)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    assert ((idxs >= 0) & (idxs < N)).all()  # no EMPTY_SLOT leak
    ev, ei = exact_search(q, db, K, metric=metric)
    recall = _recall(idxs, ei)
    assert recall >= TARGET - 0.12, (
        f"{backend}/{metric}: pruned recall {recall:.3f} collapsed"
    )
    # exact-scoring check: recompute each returned score from raw data
    qn, dbn = np.asarray(q, np.float64), np.asarray(db, np.float64)
    for row in range(0, q.shape[0], 7):
        for j in range(K):
            rid = int(idxs[row, j])
            dot = float(qn[row] @ dbn[rid])
            if metric == "mips":
                ref = dot
            elif metric == "l2":
                # public values are ascending relaxed distances
                ref = -(dot - float(dbn[rid] @ dbn[rid]) / 2.0)
            else:  # cosine
                ref = dot / (
                    np.linalg.norm(qn[row]) * np.linalg.norm(dbn[rid])
                )
            assert vals[row, j] == pytest.approx(ref, abs=1e-3)


def test_deleted_rows_never_returned_from_pruned_scan(data):
    db, q = data
    index = Index.build(db, metric="l2", k=K, backend="xla")
    assert index.pack().cluster is not None
    _, before = index.search(q)
    doomed = np.unique(np.asarray(before)[:, 0])  # delete top hits
    index.delete(jnp.asarray(doomed))
    _, after = index.search(q)
    leaked = set(np.asarray(after).ravel().tolist()) & set(doomed.tolist())
    assert not leaked, f"tombstoned rows leaked through the gather: {leaked}"


def test_scanned_fraction_is_actually_small(data):
    db, _ = data
    index = Index.build(db, metric="l2", k=K, backend="xla")
    cp = index.pack().cluster.plan
    assert cp.scanned_fraction < 0.25
    assert cp.scan_rows == cp.probes * cp.rows_per_cluster \
        + cp.spill_capacity


# --- quantized tiers compose -------------------------------------------------


@pytest.mark.parametrize("storage", ["bf16", "int8"])
def test_cluster_composes_with_quantized_storage(data, storage):
    """Pruned quantized scan -> exact f32 rescore: the over-fetches stack
    and the returned values are exact scores (rescore output), not the
    reduced-precision scan scores."""
    db, q = data
    index = Index.build(db, metric="l2", k=K, backend="xla",
                        storage=storage, recall_target=TARGET)
    assert index.pack().cluster is not None
    vals, idxs = index.search(q)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    assert ((idxs >= 0) & (idxs < N)).all()
    _, ei = exact_search(q, db, K, metric="l2")
    assert _recall(idxs, ei) >= TARGET - 0.12
    # rescore exactness: values match f32 recomputation, not int8 scores
    qn, dbn = np.asarray(q, np.float64), np.asarray(db, np.float64)
    for row in range(0, q.shape[0], 11):
        rid = int(idxs[row, 0])
        ref = -(float(qn[row] @ dbn[rid]) - float(dbn[rid] @ dbn[rid]) / 2)
        assert vals[row, 0] == pytest.approx(ref, abs=1e-3)


# --- sharded backend ---------------------------------------------------------


def test_sharded_cluster_search_single_shard(data):
    db, q = data
    mesh = make_mesh((1,), ("model",))
    index = Index.build(db, metric="l2", k=K, backend="xla").shard(
        mesh, db_axis="model"
    )
    pk = index.pack()
    assert pk.cluster is not None  # tables carried through the relayout
    vals, idxs = index.search(q)
    idxs = np.asarray(idxs)
    assert ((idxs >= 0) & (idxs < index.capacity)).all()
    _, ei = exact_search(q, db, K, metric="l2")
    assert _recall(idxs, ei) >= TARGET - 0.12


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_sharded_cluster_quant_and_f32_operand_binding(data, storage):
    """The sharded searcher takes quant and cluster operands in one
    signature; both storage tiers must bind them correctly (a positional
    mix-up would feed centroids where scales belong)."""
    db, q = data
    mesh = make_mesh((1,), ("model",))
    index = Index.build(db, metric="l2", k=K, backend="xla",
                        storage=storage).shard(mesh, db_axis="model")
    _, idxs = index.search(q)
    _, ei = exact_search(q, db, K, metric="l2")
    assert _recall(idxs, ei) >= TARGET - 0.12


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_sharded_cluster_on_the_kernel_layout_runs_the_xla_gather(
        data, storage):
    """Where the shards run the fused kernel (interpret mode here), their
    rows are the per-shard kernel layout, lane-padded; the pruned branch
    still gathers them with XLA, and the dispatch counts that scan."""
    db, q = data
    mesh = make_mesh((1,), ("model",))
    index = Index.build(db, metric="l2", k=K, backend="xla", storage=storage,
                        interpret=True).shard(mesh, db_axis="model")
    pk = index.pack()
    assert pk.cluster is not None and pk.block_n and pk.db.shape[1] == 128
    backends.SHARD_SCAN_COUNTS.clear()
    _, idxs = index.search(q)
    assert dict(backends.SHARD_SCAN_COUNTS) == {"xla": 1}
    _, ei = exact_search(q, db, K, metric="l2")
    assert _recall(idxs, ei) >= TARGET - 0.12


# --- packed add/delete contract ----------------------------------------------


def test_add_assigns_incrementally_without_rebuild():
    db, q = _mixture(2, n=N - 128)
    index = Index.build(db, metric="l2", k=K, backend="xla", capacity=N)
    cs = index.pack().cluster
    assert cs is not None
    total0 = int(cs.counts.sum()) + cs.spill_count
    reset_pack_events()
    new_rows, _ = _mixture(3, n=64)
    index.add(new_rows[:64])
    assert PACK_EVENTS["cluster_assigned"] == 1
    assert PACK_EVENTS["cluster_built"] == 0  # incremental, not a rebuild
    assert PACK_EVENTS["recluster"] == 0
    cs = index.pack().cluster
    assert int(cs.counts.sum()) + cs.spill_count == total0 + 64
    # the appended rows are findable: search for them exactly
    vals, idxs = index.search(new_rows[:8])
    found = set(np.asarray(idxs)[:, 0].tolist())
    appended = set(range(N - 128, N - 128 + 8))
    assert found & appended, "freshly added rows never surfaced"


def test_spill_growth_triggers_lazy_recluster():
    """Spill growth past the planner threshold triggers exactly one
    rebuild at add() time — and the rebuild resets the trigger."""
    db, _ = _mixture(4, n=N - 64)
    index = Index.build(db, metric="l2", k=K, backend="xla", capacity=N)
    cs = index.pack().cluster
    # simulate incremental assignment having grown the spill block past
    # the threshold (deterministic, corpus-independent)
    cs.spill_count = min(
        cs.plan.spill_capacity,
        cs.spill_baseline + cs.plan.spill_capacity,
    )
    grew = cs.spill_count - cs.spill_baseline
    if grew <= cs.plan.spill_capacity * clusterlib._SPILL_REPLAN_FRACTION:
        cs.spill_baseline = 0  # force growth even on a spill-full corpus
    assert cs.needs_recluster
    reset_pack_events()
    index.add(jnp.ones((1, D), jnp.float32))
    assert PACK_EVENTS["recluster"] == 1
    cs = index.pack().cluster
    assert not cs.needs_recluster  # trigger is reset by the rebuild
    reset_pack_events()
    index.add(jnp.ones((1, D), jnp.float32))
    assert PACK_EVENTS["recluster"] == 0  # no thrash


# --- steady-state contracts --------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_zero_retrace_one_dispatch_with_interleaved_updates(data, backend):
    """The clustered path keeps the PR-2 steady-state contract: after the
    warmup compile, interleaved add/delete/search traffic re-traces
    nothing, repacks nothing, and each search is ONE device dispatch."""
    db, q = data
    rng = np.random.default_rng(5)
    index = Index.build(db[: N - 64], metric="l2", k=K, backend=backend,
                        capacity=N)
    assert index.pack().cluster is not None
    index.search(q)  # warmup
    backends.reset_trace_counts()
    backends.reset_dispatch_counts()
    reset_pack_events()
    index._cache.reset_counters()
    for _ in range(3):
        index.add(jnp.asarray(rng.normal(size=(8, D)), jnp.float32))
        index.delete(jnp.asarray(rng.integers(0, N - 64, 4)))
        index.search(q)
    assert not dict(TRACE_COUNTS), "clustered steady state retraced"
    assert DISPATCH_COUNTS[backend] == 3, "more than one dispatch/search"
    assert PACK_EVENTS["packed"] == 0, "a search-time repack happened"
    assert PACK_EVENTS["relayout"] == 0
    assert index.cache_info()["misses"] == 0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_clustered_program_never_pads_database(data, backend):
    """Jaxpr probe: the compiled pruned-scan program pads only query- and
    candidate-sized arrays, never anything database-sized."""
    db, q = data
    index = Index.build(db, metric="l2", k=K, backend=backend)
    pk = index.pack()
    assert pk.cluster is not None
    fn = index._build_block_fn(backend, pk)
    jaxpr = jax.make_jaxpr(fn)(q, *pk.operands()).jaxpr
    pads = _pad_shapes(jaxpr)
    db_elems = pk.db.shape[0] * pk.db.shape[1]
    assert all(int(np.prod(s)) < db_elems for s in pads), (
        f"database-sized pad in the clustered program: {pads}"
    )


def _subjaxprs(p):
    if hasattr(p, "jaxpr"):
        yield p.jaxpr
    elif hasattr(p, "eqns"):
        yield p
    elif isinstance(p, (list, tuple)):
        for x in p:
            yield from _subjaxprs(x)


def _pad_shapes(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad":
            out.append(tuple(eqn.outvars[0].aval.shape))
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                out.extend(_pad_shapes(sub))
    return out


# --- serving -----------------------------------------------------------------


def test_search_server_over_clustered_index(data):
    """SearchServer micro-batching works unchanged over a clustered index
    and returns the same neighbours as a direct search."""
    db, q = data
    index = Index.build(db, metric="l2", k=K, backend="xla")
    assert index.pack().cluster is not None
    server = SearchServer(index, ServeConfig(max_batch=32),
                          clock=VirtualClock())
    tickets = [server.submit(q[i : i + 4]) for i in range(0, 16, 4)]
    server.run_until_idle()
    direct_v, direct_i = index.search(q[:16])
    got_i = np.concatenate([np.asarray(t.result().indices) for t in tickets])
    np.testing.assert_array_equal(got_i, np.asarray(direct_i)[:16])


# --- explain -----------------------------------------------------------------


def test_explain_reports_cluster_decomposition(data):
    db, _ = data
    index = Index.build(db, metric="l2", k=K, backend="xla",
                        recall_target=TARGET)
    report = index.explain()
    cl = report["cluster"]
    assert cl["mode"] == "auto" and cl["enabled"]
    assert 0.0 < cl["scanned_fraction"] < 1.0
    assert cl["expected_recall"] == pytest.approx(
        cl["collision_term"] * cl["miss_term"]
    )
    assert cl["expected_recall"] >= TARGET
    assert report["expected_recall"] == cl["expected_recall"]
    assert index.expected_recall == cl["expected_recall"]


def test_explain_below_crossover_reports_rejection():
    db, _ = _mixture(6, n=SMALL_N)
    index = Index.build(db, metric="l2", k=K, backend="xla")
    cl = index.explain()["cluster"]
    assert cl["mode"] == "auto" and not cl["enabled"]
    assert cl["predicted_speedup"] < 2.0  # why the planner said no


# --- build-time sampled miss check (regime detector) -------------------------


def _gaussian_db(seed, n=N, d=D):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32)


def test_structureless_data_rejected_by_miss_check():
    """i.i.d. Gaussian above the crossover: the planner says "prune" but
    the measured miss rate blows the budget, so the tables are discarded
    and the index is bit-identical to cluster="off" (the quickstart
    regression: recall must not collapse on unclusterable data)."""
    db = _gaussian_db(11)
    q = _gaussian_db(12, n=64)
    auto = Index.build(db, metric="l2", k=K, backend="xla", cluster="auto")
    off = Index.build(db, metric="l2", k=K, backend="xla", cluster="off")
    assert auto.kernel_plan.cluster.enabled      # crossover said yes...
    assert auto.pack().cluster is None           # ...the measurement said no
    assert PACK_EVENTS["cluster_rejected"] >= 1
    va, ia = auto.search(q)
    vo, io = off.search(q)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(io))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vo))
    # the dense fallback keeps the recall guarantee the planner promised
    assert auto.expected_recall == off.expected_recall


def test_rejection_surfaces_in_explain():
    db = _gaussian_db(13)
    index = Index.build(db, metric="l2", k=K, backend="xla")
    cl = index.explain()["cluster"]
    assert cl["mode"] == "auto" and not cl["enabled"]
    assert cl["rejected_by"] == "sampled_miss_check"
    assert cl["sampled_miss"] > cl["miss_budget"]


def test_sampled_miss_rate_separates_regimes():
    """The measurement itself: small on the mixture corpus (within the
    acceptance threshold), large on i.i.d. Gaussian (far past it)."""
    db, _ = _mixture(14)
    mixed = Index.build(db, metric="l2", k=K, backend="xla").pack()
    rate = clusterlib.sampled_miss_rate(
        mixed.cluster, mixed.rows(), mixed.bias_row()[:mixed.n], None, K
    )
    threshold = clusterlib.miss_check_threshold(
        mixed.cluster.plan.miss_budget
    )
    assert rate <= threshold
    gauss = Index.build(_gaussian_db(15), metric="l2", k=K, backend="xla")
    assert gauss.pack().cluster_rejected_miss > 2 * threshold


def test_miss_check_threshold_floor():
    # tight budgets (high targets) keep the absolute floor so sampling
    # noise cannot cause spurious rejections
    assert clusterlib.miss_check_threshold(0.005) == 0.08
    assert clusterlib.miss_check_threshold(0.05) == pytest.approx(0.1)

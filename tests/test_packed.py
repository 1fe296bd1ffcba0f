"""Perf-contract tests for the packed search state + streaming executor.

These encode the performance model (Eq. 10: per-dispatch memory traffic
~ O(min(M, N))) as CI assertions, so a regression that re-introduces
per-search (N, D) padding / metric re-preparation — or per-block Python
dispatch loops — fails the fast tier:

  * steady-state repeat searches: zero packs, zero retraces, cache hits
    only; the compiled pallas program pads nothing database-sized (jaxpr
    inspection),
  * ``add`` metric-prepares only the appended slice; growth relayouts
    without a full pack; ``delete`` patches only the bias row and never
    syncs the host,
  * a multi-block batch is ONE dispatch, and the streaming executor is
    bit-identical to the per-block loop for divisible and ragged M.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.search import Index, SearchSpec, backends
from repro.search.backends import DISPATCH_COUNTS, TRACE_COUNTS
from repro.search.packed import PACK_EVENTS, reset_pack_events

K = 10


@pytest.fixture(scope="module")
def data():
    q = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    db = jax.random.normal(jax.random.PRNGKey(1), (4096, 32))
    return q, db


@pytest.fixture(autouse=True)
def _reset_counters():
    backends.reset_trace_counts()
    backends.reset_dispatch_counts()
    reset_pack_events()
    yield


# --- jaxpr inspection: the compiled program pads only query-sized arrays ----


def _subjaxprs(p):
    if hasattr(p, "jaxpr"):  # ClosedJaxpr
        yield p.jaxpr
    elif hasattr(p, "eqns"):  # raw Jaxpr (e.g. pallas kernel jaxpr)
        yield p
    elif isinstance(p, (list, tuple)):
        for x in p:
            yield from _subjaxprs(x)


def _pad_shapes(jaxpr):
    """Every ``pad`` primitive's output shape, recursing into subjaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad":
            out.append(tuple(eqn.outvars[0].aval.shape))
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                out.extend(_pad_shapes(sub))
    return out


def test_packed_pallas_program_never_pads_database(data):
    q, db = data
    index = Index.build(db, metric="l2", k=K, backend="pallas")
    pk = index.pack()
    fn = index._build_block_fn("pallas", pk)
    pads = _pad_shapes(jax.make_jaxpr(fn)(q, pk.db, pk.bias).jaxpr)
    db_elems = pk.db.shape[0] * pk.db.shape[1]
    assert pads, "query padding should still appear (sanity)"
    assert all(int(np.prod(s)) < db_elems for s in pads), (
        f"database-sized pad re-introduced into the search program: {pads}"
    )


@pytest.mark.parametrize("storage", ["bf16", "int8", "int4"])
def test_quantized_pallas_program_never_pads_database(data, storage):
    """The PR-2 traffic contract extends to quantized tiers: the compiled
    two-pass program pads only query-sized arrays — the quantized scan
    consumes pre-packed operands and the rescore pass is an O(M·K')
    gather, so nothing database-sized is padded (or materialized) per
    dispatch."""
    q, db = data
    index = Index.build(db, metric="l2", k=K, backend="pallas",
                        storage=storage)
    pk = index.pack()
    fn = index._build_block_fn("pallas", pk)
    pads = _pad_shapes(jax.make_jaxpr(fn)(q, *pk.operands()).jaxpr)
    db_elems = pk.db.shape[0] * pk.db.shape[1]
    assert pads, "query padding should still appear (sanity)"
    assert all(int(np.prod(s)) < db_elems for s in pads), (
        f"database-sized pad in the quantized search program: {pads}"
    )


def test_legacy_oneshot_path_does_pad_database(data):
    """Sensitivity check: the same probe flags the pack-inside-jit path,
    so a silent Index regression onto it cannot pass the test above."""
    q, db = data
    pads = _pad_shapes(
        jax.make_jaxpr(
            lambda a, b: backends.pallas_search(
                a, b, None, metric="mips", interpret=True
            )
        )(q, db).jaxpr
    )
    assert any(int(np.prod(s)) >= db.shape[0] * 128 for s in pads)


# --- fused scan→select vs the two-pass parity oracle -------------------------


@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
@pytest.mark.parametrize("storage", ["f32", "bf16", "int8", "int4"])
def test_fused_select_matches_two_pass_oracle(data, metric, storage):
    """The single-pass fused kernel (VMEM winner buffer) must be BIT-identical
    to the two-pass scan→merge_topk composition on every metric × storage
    tier — the acceptance grid of the fused-select tentpole."""
    q, db = data
    fused = Index.build(
        db, metric=metric, k=K, backend="pallas", storage=storage
    ).search(q)
    oracle = Index.build(
        db,
        spec=SearchSpec(metric=metric, k=K, backend="pallas",
                        storage=storage, fused_select=False),
    ).search(q)
    np.testing.assert_array_equal(
        np.asarray(fused.indices), np.asarray(oracle.indices)
    )
    np.testing.assert_array_equal(
        np.asarray(fused.values), np.asarray(oracle.values)
    )


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_fused_dispatches_count_their_select_mode(data, fused, storage):
    """Every dispatch whose program runs the fused kernel counts its select
    mode ("deferred") in ``FUSED_SELECT_COUNTS``, which the registry
    exports and ``SearchServer.stats()`` reports; the two-pass program
    counts nothing."""
    from repro.search import telemetry
    from repro.search.serve import SearchServer, ServeConfig, VirtualClock

    telemetry.reset_all()
    q, db = data
    index = Index.build(
        db,
        spec=SearchSpec(metric="l2", k=K, backend="pallas", storage=storage,
                        fused_select=fused, query_block=32),
    )
    index.search(q[:8])   # one block
    index.search(q)       # two blocks, one streamed dispatch
    server = SearchServer(index, ServeConfig(max_batch=8),
                          clock=VirtualClock())
    server.submit(np.asarray(q[:3]))
    server.run_until_idle()
    want = {"deferred": 3} if fused else {}
    assert sum(DISPATCH_COUNTS.values()) == 3
    assert dict(backends.FUSED_SELECT_COUNTS) == want
    assert server.stats()["fused_select"] == want
    prom = telemetry.export_prometheus()
    assert ('repro_fused_select_dispatches_total{mode="deferred"} 3' in prom
            ) == fused


@pytest.mark.parametrize("backend,storage,want", [
    ("pallas", "f32", {"f32_highest": 3}),
    ("pallas", "bf16", {"bf16": 3}),
    ("pallas", "int8", {"int8_upcast": 3}),
    ("pallas", "int4", {"int4_upcast": 3}),
    ("xla", "bf16", {}),
])
def test_pallas_dispatches_count_their_scan_operands(data, backend, storage,
                                                     want):
    """Every dispatch whose program runs the Pallas scan counts the
    operand form its dot is given in ``SCAN_OPERAND_COUNTS``, which the
    registry exports and ``SearchServer.stats()`` reports; the XLA scan
    counts nothing."""
    from repro.search import telemetry
    from repro.search.serve import SearchServer, ServeConfig, VirtualClock

    telemetry.reset_all()
    q, db = data
    index = Index.build(
        db,
        spec=SearchSpec(metric="l2", k=K, backend=backend, storage=storage,
                        query_block=32),
    )
    index.search(q[:8])   # one block
    index.search(q)       # two blocks, one streamed dispatch
    server = SearchServer(index, ServeConfig(max_batch=8),
                          clock=VirtualClock())
    server.submit(np.asarray(q[:3]))
    server.run_until_idle()
    assert sum(DISPATCH_COUNTS.values()) == 3
    assert dict(backends.SCAN_OPERAND_COUNTS) == want
    assert server.stats()["scan_operands"] == want
    prom = telemetry.export_prometheus()
    for operand, count in want.items():
        assert (f'repro_scan_operand_dispatches_total{{operand="{operand}"}}'
                f" {count}") in prom
    assert ("repro_scan_operand_dispatches_total{" in prom) == bool(want)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("storage", ["f32", "int8", "int4"])
def test_masked_winners_are_sentinels_not_phantom_duplicates(fused, storage):
    """Regression (masked-winner clamp bug): with fewer live rows than k,
    the masked tail of each result row used to be clamped into [0, n) and
    surfaced as duplicate phantom copies of row n-1 after the -inf merge
    tie.  Masked entries must now carry the sentinel index -1, and the
    live prefix must be duplicate-free."""
    from repro.search.backends import MASK_VALUE

    db = jax.random.normal(jax.random.PRNGKey(21), (64, 32))
    index = Index.build(
        db,
        spec=SearchSpec(metric="mips", k=K, backend="pallas",
                        storage=storage, fused_select=fused),
    )
    index.delete(list(range(6, 64)))  # 6 live rows < k=10
    q = jax.random.normal(jax.random.PRNGKey(22), (5, 32))
    vals, idxs = index.search(q)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    for row_v, row_i in zip(vals, idxs):
        live = row_i[row_v > MASK_VALUE * 0.5]
        masked = row_i[row_v <= MASK_VALUE * 0.5]
        assert len(set(live.tolist())) == len(live), (
            f"duplicate live winners: {row_i}"
        )
        assert (live >= 0).all() and (live < 6).all()
        assert masked.size and (masked == -1).all(), (
            f"masked winners must be -1 sentinels, got {masked}"
        )


def test_single_query_clamps_block_m(data):
    """Regression (query-pad bug): an M=1 dispatch used to be padded to a
    full block_m=256 query tile, wasting 256x the MXU work.  The kernel
    now clamps the query tile to the sublane-rounded batch — the compiled
    program pads queries to 8 rows, and the plan prices 8 rows of FLOPs."""
    _, db = data
    index = Index.build(db, metric="mips", k=K, backend="pallas")
    pk = index.pack()
    fn = index._build_block_fn("pallas", pk)
    q1 = jax.random.normal(jax.random.PRNGKey(23), (1, 32))
    pads = _pad_shapes(jax.make_jaxpr(fn)(q1, pk.db, pk.bias).jaxpr)
    assert all(s[0] != 256 for s in pads if len(s) == 2), (
        f"M=1 still padded to a full 256-row query tile: {pads}"
    )
    assert any(s[0] == 8 for s in pads if len(s) == 2), (
        f"expected an 8-row (one sublane tile) query pad, got {pads}"
    )
    # And the planner models the same clamped shape: 8 padded query rows.
    e = index.explain(m=1)
    plan = e["plan"]
    assert e["predicted"]["flops"] == (
        2.0 * 8 * plan["padded_n"] * plan["d_pad"]
    )


def test_scan_k_capped_at_live_count_after_mass_delete(data):
    """Regression (stale over-fetch bug): ``scan_k`` was derived from
    capacity and never revalidated against the live count, so a
    delete-heavy index over-fetched tombstones into the exact rescore
    gather.  The program built after the deletes caps k_scan at the live
    count, and the results match the exact answer over the survivors."""
    from repro.search import exact_search
    from repro.search.packed import scan_k_for

    q, db = data
    spec = SearchSpec(metric="mips", k=K, backend="pallas", storage="int8")
    # unit: the cap binds at program-build time, never below k
    assert scan_k_for(spec, 4096) == 2 * K
    assert scan_k_for(spec, 4096, live=12) == 12
    assert scan_k_for(spec, 4096, live=3) == K
    index = Index.build(db, metric="mips", k=K, backend="pallas",
                        storage="int8")
    survivors = list(range(0, 4096, 341))  # 13 live rows > k
    index.delete([i for i in range(4096) if i not in survivors])
    assert index.size == len(survivors) == 13
    vals, idxs = index.search(q)  # first compile: sees the live count
    _, exact_idx = exact_search(q, db[jnp.asarray(survivors)], K)
    got = np.asarray(idxs)
    want = np.asarray(survivors)[np.asarray(exact_idx)]
    assert (np.sort(got, axis=1) == np.sort(want, axis=1)).all()


# --- steady state: zero packs, zero retraces --------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_steady_state_repeat_search_does_no_database_work(data, backend):
    q, db = data
    index = Index.build(db, metric="cosine", k=K, backend=backend)
    index.search(q)  # warmup: trace + compile once
    backends.reset_trace_counts()
    reset_pack_events()
    index._cache.reset_counters()
    for _ in range(5):
        index.search(q)
    assert not dict(PACK_EVENTS), "repeat search repacked the database"
    assert not dict(TRACE_COUNTS), "repeat search retraced"
    info = index.cache_info()
    assert info["hits"] == 5 and info["misses"] == 0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("storage", ["bf16", "int8"])
def test_quantized_steady_state_keeps_traffic_contract(data, backend, storage):
    """Zero repacks, zero retraces, cache hits only on quantized tiers —
    scale/rescore operands are passed per dispatch, never re-derived."""
    q, db = data
    index = Index.build(db, metric="l2", k=K, backend=backend,
                        storage=storage)
    index.search(q)  # warmup: trace + compile once
    backends.reset_trace_counts()
    reset_pack_events()
    index._cache.reset_counters()
    for _ in range(5):
        index.search(q)
    assert not dict(PACK_EVENTS), "quantized repeat search repacked"
    assert not dict(TRACE_COUNTS), "quantized repeat search retraced"
    info = index.cache_info()
    assert info["hits"] == 5 and info["misses"] == 0


def test_quantized_multi_block_batch_is_one_dispatch(data):
    _, db = data
    qb = 16
    index = Index.build(db, k=K, backend="xla", storage="int8",
                        query_block=qb)
    big = jax.random.normal(jax.random.PRNGKey(3), (8 * qb, 32))
    index.search(big)  # warmup
    backends.reset_dispatch_counts()
    index._cache.reset_counters()
    index.search(big)
    assert DISPATCH_COUNTS["xla"] == 1, "quantized 8-block batch >1 dispatch"
    assert index.cache_info()["hits"] == 1


def test_quantized_mutations_stay_incremental(data):
    """add/delete on a quantized tier patch the packed state in place —
    same PACK_EVENTS taxonomy as f32, no hidden full packs."""
    _, db = data
    index = Index.build(db[:2048], metric="l2", k=K, backend="xla",
                        storage="int8", capacity=4096)
    reset_pack_events()
    index.add(db[2048:])
    assert dict(PACK_EVENTS) == {"rows_updated": 1}
    reset_pack_events()
    index.delete([1, 2, 3])
    assert dict(PACK_EVENTS) == {"bias_patched": 1}
    # live count stays a lazy device scalar (no host sync on delete)
    assert not isinstance(index._num_live, int)


def test_multi_block_batch_is_one_dispatch(data):
    _, db = data
    qb = 16
    index = Index.build(db, k=K, backend="xla", query_block=qb)
    big = jax.random.normal(jax.random.PRNGKey(3), (8 * qb, 32))
    index.search(big)  # warmup
    backends.reset_trace_counts()
    backends.reset_dispatch_counts()
    index._cache.reset_counters()
    index.search(big)
    assert DISPATCH_COUNTS["xla"] == 1, "8-block batch took >1 dispatch"
    assert index.cache_info()["hits"] == 1
    assert not dict(TRACE_COUNTS)


# --- incremental mutations ---------------------------------------------------


@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
def test_add_prepares_only_the_appended_slice(data, metric):
    _, db = data
    index = Index.build(
        db[:2048], metric=metric, k=K, backend="xla", capacity=4096
    )
    reset_pack_events()
    index.add(db[2048:])
    assert dict(PACK_EVENTS) == {"rows_updated": 1}
    # Numerics: the incrementally packed state equals a from-scratch pack
    # of the full database at the same capacity.
    full = Index.build(db, metric=metric, k=K, backend="xla", capacity=4096)
    np.testing.assert_allclose(
        np.asarray(index.pack().db), np.asarray(full.pack().db), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(index.pack().bias), np.asarray(full.pack().bias)
    )


def test_add_with_growth_relayouts_without_full_pack(data):
    _, db = data
    index = Index.build(db[:1024], metric="l2", k=K, backend="pallas")
    reset_pack_events()
    index.add(db[1024:1100])
    ev = dict(PACK_EVENTS)
    assert ev == {"relayout": 1, "rows_updated": 1}, ev
    # grown region stays dead until written: nothing above the high-water
    # mark is ever returned
    q = jax.random.normal(jax.random.PRNGKey(7), (8, 32))
    _, idxs = index.search(q)
    assert int(np.asarray(idxs).max()) < 1100


def test_non_rowwise_metric_forces_full_repack_at_add_time(data):
    from repro.search import Metric, exact_mips, register_metric
    from repro.search.metrics import _REGISTRY

    register_metric(
        Metric(
            name="coupled-mips",
            negate_output=False,
            prepare_database=lambda db: (db, None),
            prepare_queries=lambda q: q,
            exact=exact_mips,
            rowwise=False,
        ),
        overwrite=True,
    )
    try:
        _, db = data
        index = Index.build(
            db[:2048], metric="coupled-mips", k=K, backend="xla",
            capacity=4096,
        )
        reset_pack_events()
        index.add(db[2048:])
        ev = dict(PACK_EVENTS)
        # repack happens (at add() time), never an undefined slice update
        assert ev.get("full_pack") == 1 and "rows_updated" not in ev, ev
        with pytest.raises(ValueError, match="row-wise"):
            index.metric.prepare_update(db[:4])
    finally:
        _REGISTRY.pop("coupled-mips", None)


def test_delete_patches_bias_only_and_never_syncs(data):
    q, db = data
    index = Index.build(db, metric="mips", k=K, backend="xla")
    index.search(q)
    reset_pack_events()
    index.delete([1, 2, 3])
    assert dict(PACK_EVENTS) == {"bias_patched": 1}
    # live count stays a lazy device scalar until read
    assert not isinstance(index._num_live, int)
    assert index.size == 4093
    assert isinstance(index._num_live, int)
    # deleted ids are really gone from results
    _, idxs = index.search(q)
    assert not {1, 2, 3} & set(np.asarray(idxs).ravel().tolist())


def test_shard_reuses_packed_layout(data):
    q, db = data
    mesh = make_mesh((1,), ("model",))
    index = Index.build(db, metric="cosine", k=K)
    reset_pack_events()
    sharded = index.shard(mesh, db_axis="model")
    ev = dict(PACK_EVENTS)
    assert "full_pack" not in ev and ev.get("relayout") == 1, ev
    vals, idxs = sharded.search(q)
    assert vals.shape == (64, K)


# --- streaming executor parity ----------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("m", [128, 100])  # divisible / ragged by query_block
def test_stream_matches_per_block_loop(data, backend, m):
    _, db = data
    queries = jax.random.normal(jax.random.PRNGKey(5), (m, 32))
    stream = Index.build(
        db[:1024], k=K, backend=backend, query_block=16
    ).search(queries)
    loop = Index.build(
        db[:1024],
        spec=SearchSpec(k=K, backend=backend, query_block=16, stream=False),
    ).search(queries)
    np.testing.assert_array_equal(
        np.asarray(stream.indices), np.asarray(loop.indices)
    )
    np.testing.assert_array_equal(
        np.asarray(stream.values), np.asarray(loop.values)
    )


def test_stream_matches_loop_sharded(data):
    _, db = data
    mesh = make_mesh((1,), ("model",))
    queries = jax.random.normal(jax.random.PRNGKey(5), (100, 32))
    stream = (
        Index.build(db[:1024], k=K, query_block=16)
        .shard(mesh, db_axis="model")
        .search(queries)
    )
    loop = (
        Index.build(
            db[:1024], spec=SearchSpec(k=K, query_block=16, stream=False)
        )
        .shard(mesh, db_axis="model")
        .search(queries)
    )
    np.testing.assert_array_equal(
        np.asarray(stream.indices), np.asarray(loop.indices)
    )
    np.testing.assert_array_equal(
        np.asarray(stream.values), np.asarray(loop.values)
    )


_MULTIDEVICE_SCRIPT = r"""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.search import Index, SearchSpec, exact_search

mesh = make_mesh((2, 4), ("data", "model"))
db = jax.random.normal(jax.random.PRNGKey(1), (4096, 64))
q = jax.random.normal(jax.random.PRNGKey(0), (128, 64))

stream = Index.build(db, k=10, query_block=32).shard(
    mesh, db_axis="model", batch_axis="data")
loop = Index.build(db, spec=SearchSpec(k=10, query_block=32, stream=False)
    ).shard(mesh, db_axis="model", batch_axis="data")
s, l = stream.search(q), loop.search(q)
assert np.array_equal(np.asarray(s.indices), np.asarray(l.indices))
assert np.array_equal(np.asarray(s.values), np.asarray(l.values))

# both must actually be CORRECT, not merely equal: the old concatenate-based
# loop silently psummed shard_map outputs (x n_shards) on >1 db shards.
_, e = exact_search(q, db, 10)
rec = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
               for a, b in zip(np.asarray(s.indices), np.asarray(e))])
assert rec >= stream.expected_recall - 0.07, rec
assert int(np.asarray(s.indices).max()) < 4096
publish({"recall": float(rec), "expected": float(stream.expected_recall)})
"""


def test_stream_matches_loop_multidevice(fake_devices):
    """8 fake devices in a subprocess (the main process stays 1-device):
    multi-block sharded search is bit-identical stream vs loop AND correct
    against the exact baseline."""
    res = fake_devices(_MULTIDEVICE_SCRIPT, n=8)
    assert res["recall"] >= res["expected"] - 0.07

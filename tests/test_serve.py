"""Deterministic concurrency tests for the micro-batching SearchServer.

Everything here runs under the virtual clock (no threads, no sleeps, no
timing assumptions) except one wall-clock end-to-end smoke — so the
serving contracts are CI-stable:

  * coalescing produces exactly ONE device dispatch per micro-batch
    (asserted against ``backends.DISPATCH_COUNTS``), and scattered
    per-request results are bit-identical to a direct ``Index.search`` of
    the same rows,
  * per-request k budgets are slices of the shared dispatch,
  * admission control bounds the queue depth (``QueueFull`` beyond it),
  * bucket shapes never retrace once precompiled (``TRACE_COUNTS`` and the
    index compile-cache counters stay clean under mixed request sizes),
  * oversize requests ride the streaming executor — still one dispatch,
  * the engine/datastore integrations route lookups through the server
    without changing results.
"""
import threading

import jax
import numpy as np
import pytest

from repro.search import (
    Index,
    QueueFull,
    SearchServer,
    SearchSpec,
    ServeConfig,
    VirtualClock,
    backends,
)
from repro.search.backends import DISPATCH_COUNTS, TRACE_COUNTS
from repro.search.plan import plan_buckets
from repro.search.serve import SERVE_EVENTS, reset_serve_events

K = 10
D = 16


@pytest.fixture(scope="module")
def index():
    db = jax.random.normal(jax.random.PRNGKey(1), (2048, D))
    return Index.build(db, metric="mips", k=K, backend="xla")


@pytest.fixture(autouse=True)
def _reset_counters():
    backends.reset_trace_counts()
    backends.reset_dispatch_counts()
    reset_serve_events()
    yield


def _vserver(index, **cfg):
    cfg.setdefault("max_batch", 32)
    return SearchServer(index, ServeConfig(**cfg), clock=VirtualClock())


def _queries(seed, m):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (m, D)))


# --- coalescing: one dispatch, bit-identical scatter -------------------------


def test_coalesced_micro_batch_is_one_dispatch(index):
    server = _vserver(index)
    server.precompile()
    backends.reset_dispatch_counts()
    reset_serve_events()
    sizes = [3, 5, 8, 4]  # 20 rows -> one bucket-32 micro-batch
    qs = [_queries(10 + i, m) for i, m in enumerate(sizes)]
    tickets = [server.submit(q) for q in qs]
    server.run_until_idle()
    assert DISPATCH_COUNTS["xla"] == 1, dict(DISPATCH_COUNTS)
    assert SERVE_EVENTS["batches"] == 1
    assert SERVE_EVENTS["coalesced_requests"] == len(sizes)
    for q, t in zip(qs, tickets):
        direct = index.search(q)
        vals, idxs = t.result()
        np.testing.assert_array_equal(np.asarray(idxs), np.asarray(direct.indices))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(direct.values))


def test_dispatch_count_equals_micro_batch_count(index):
    """A stream of requests larger than one batch: dispatches == batches,
    never one per request."""
    server = _vserver(index)
    server.precompile()
    backends.reset_dispatch_counts()
    tickets = [server.submit(_queries(50 + i, 8)) for i in range(12)]  # 96 rows
    server.run_until_idle()
    batches = server.stats()["batches"]
    assert batches == 3  # 96 rows / 32-row micro-batches, whole requests
    assert DISPATCH_COUNTS["xla"] == batches
    assert all(t.done for t in tickets)


def test_fifo_whole_request_coalescing(index):
    """Requests are never split: a request that does not fit the open
    micro-batch starts the next one, FIFO order preserved."""
    server = _vserver(index, max_batch=24)
    for m in (10, 10, 10):
        server.submit(_queries(3, m))
    assert server.step()      # batch 1: 10 + 10 = 20 <= 24; 30 would not fit
    assert server.step()      # batch 2: the remaining 10
    assert not server.step()  # queue empty
    s = server.stats()
    assert s["batches"] == 2
    assert s["coalesced_requests"] == 3
    assert s["dispatched_rows"] == 30


def test_per_request_k_budgets_share_one_dispatch(index):
    server = _vserver(index)
    server.precompile()
    backends.reset_dispatch_counts()
    q = _queries(21, 6)
    t1 = server.submit(q, k=1)
    t3 = server.submit(q, k=3)
    tk = server.submit(q)  # full spec.k
    server.run_until_idle()
    assert DISPATCH_COUNTS["xla"] == 1
    direct = index.search(q)
    for t, kk in ((t1, 1), (t3, 3), (tk, K)):
        vals, idxs = t.result()
        assert vals.shape == idxs.shape == (6, kk)
        np.testing.assert_array_equal(
            np.asarray(idxs), np.asarray(direct.indices)[:, :kk]
        )
        np.testing.assert_array_equal(
            np.asarray(vals), np.asarray(direct.values)[:, :kk]
        )
    with pytest.raises(ValueError, match="per-request k"):
        server.submit(q, k=K + 1)


def test_single_row_request_and_shapes(index):
    server = _vserver(index)
    t = server.submit(_queries(33, 1)[0])  # a bare (D,) row is promoted
    server.run_until_idle()
    vals, idxs = t.result()
    assert vals.shape == idxs.shape == (1, K)
    with pytest.raises(ValueError, match="dim"):
        server.submit(np.zeros((2, D + 1), np.float32))


def test_server_rejects_unsorted_candidate_specs():
    """aggregate_to_topk=False returns raw unsorted bin winners — slicing
    the first k columns of those would be silently wrong, so the server
    must refuse the spec up front."""
    db = jax.random.normal(jax.random.PRNGKey(4), (256, D))
    raw = Index.build(
        db, spec=SearchSpec(k=4, backend="xla", aggregate_to_topk=False)
    )
    with pytest.raises(ValueError, match="aggregate_to_topk"):
        SearchServer(raw, clock=VirtualClock())


# --- admission / backpressure ------------------------------------------------


def test_backpressure_bounds_queue_depth(index):
    server = _vserver(index, max_pending_rows=16)
    for _ in range(4):
        server.submit(_queries(7, 4))
    assert server.pending_rows == 16
    with pytest.raises(QueueFull):
        server.submit(_queries(7, 1))
    assert server.stats()["peak_pending_rows"] <= 16
    server.step()  # drains up to max_batch rows -> space frees
    server.submit(_queries(8, 4))  # admitted again
    server.run_until_idle()
    assert server.pending_rows == 0
    # a request that could never be admitted fails loudly up front
    with pytest.raises(QueueFull, match="admission capacity"):
        server.submit(_queries(9, 17))


def test_virtual_clock_latency_accounting(index):
    clock = VirtualClock()
    server = SearchServer(index, ServeConfig(max_batch=32), clock=clock)
    t0 = server.submit(_queries(40, 4))
    clock.advance(0.5)
    t1 = server.submit(_queries(41, 4))
    clock.advance(0.25)
    server.run_until_idle()
    # completion happens at the same (virtual) instant for a shared batch:
    # latency = completion - submit on the virtual clock, deterministic.
    assert t0.latency_s == pytest.approx(0.75)
    assert t1.latency_s == pytest.approx(0.25)


# --- compile behavior: bucket shapes never retrace ---------------------------


def test_bucket_shapes_never_retrace(index):
    server = _vserver(index, buckets=(8, 16, 32))
    server.precompile()
    backends.reset_trace_counts()
    index._cache.reset_counters()
    rng = np.random.default_rng(0)
    for wave in range(6):
        for _ in range(int(rng.integers(1, 5))):
            server.submit(_queries(int(rng.integers(0, 1000)),
                                   int(rng.integers(1, 9))))
        server.run_until_idle()
    assert not dict(TRACE_COUNTS), "serving traffic retraced a search"
    info = index.cache_info()
    assert info["misses"] == 0, (
        f"bucket dispatch missed the compile cache: {info}"
    )
    assert info["hits"] == server.stats()["batches"]


def test_planner_derives_bucket_ladder(index):
    # spec hook: Index.build resolves serve_buckets from the planner
    assert index.spec.serve_buckets == plan_buckets(index.spec.query_block)
    # server defaults honour the spec ladder, clipped to max_batch
    server = _vserver(index, max_batch=64)
    assert server.buckets == (8, 16, 32, 64)
    # explicit config wins, and the max_batch rung is always present
    server = _vserver(index, max_batch=64, buckets=(4, 12))
    assert server.buckets == (4, 12, 64)


def test_oversize_request_rides_streaming_executor():
    db = jax.random.normal(jax.random.PRNGKey(2), (1024, D))
    oversize = Index.build(db, metric="l2", k=K, backend="xla", query_block=16)
    server = SearchServer(
        oversize, ServeConfig(max_batch=16, max_pending_rows=512),
        clock=VirtualClock(),
    )
    q = _queries(60, 40)         # 40 rows > max_batch -> solo batch,
    t = server.submit(q)         # padded to 64 = 16 * 2**2
    server.submit(_queries(61, 4))  # next batch, proves no starvation
    server.run_until_idle()      # warmup compile of both shapes
    backends.reset_dispatch_counts()
    t = server.submit(q)
    server.run_until_idle()
    assert DISPATCH_COUNTS["xla"] == 1, (
        "oversize request should be ONE streamed dispatch, got "
        f"{dict(DISPATCH_COUNTS)}"
    )
    assert server.stats()["oversize_batches"] >= 1
    # oversize shapes ship solo — their staging buffers are transient,
    # never pinned in the double-buffer cache
    assert all(b <= server.max_batch for b in server._staging)
    direct = oversize.search(q)
    vals, idxs = t.result()
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(direct.indices))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(direct.values))


def test_double_buffered_staging_reuses_two_buffers(index):
    server = _vserver(index)
    for i in range(4):
        server.submit(_queries(70 + i, 20))  # one 32-bucket batch each
    server.run_until_idle()
    s = server.stats()
    assert s["staging_swaps"] == 4
    assert len(server._staging) == 1          # one bucket in play...
    assert len(server._staging[32][:2]) == 2  # ...double-buffered
    # results stay correct across buffer reuse (no aliasing): re-check one
    q = _queries(71, 20)
    t = server.submit(q)
    server.run_until_idle()
    np.testing.assert_array_equal(
        np.asarray(t.result().indices), np.asarray(index.search(q).indices)
    )


# --- wall-clock mode ---------------------------------------------------------


def test_wall_clock_mode_end_to_end(index):
    """Real worker thread, concurrent submitters — correctness only (no
    timing assertions): every request completes and matches direct search."""
    server = SearchServer(index, ServeConfig(max_batch=32, max_delay_s=0.001))
    results = {}
    errors = []

    def client(cid):
        try:
            q = _queries(100 + cid, 1 + cid % 5)
            results[cid] = (q, server.submit(q).result(timeout=60))
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    server.close()
    assert not errors
    assert len(results) == 16
    for cid, (q, res) in results.items():
        direct = index.search(q)
        np.testing.assert_array_equal(
            np.asarray(res.indices), np.asarray(direct.indices)
        )
    assert server.stats()["batches"] <= 16  # some coalescing may occur
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(_queries(0, 1))


def test_wall_clock_concurrent_extend_takes_mutation_gate():
    """Index growth from the caller thread while the worker serves lookups:
    the datastore's mutation gate keeps them serialized — every lookup
    completes, nothing crashes, and post-extend rows are searchable."""
    from repro.retrieval.datastore import KNNDatastore

    keys = jax.random.normal(jax.random.PRNGKey(8), (512, D))
    toks = jax.random.randint(jax.random.PRNGKey(9), (512,), 0, 100)
    ds = KNNDatastore(keys, toks, k=4, capacity=2048)
    ds.attach_server(config=ServeConfig(max_batch=32, max_delay_s=0.0))
    stop = threading.Event()
    errors, served = [], []

    def client(cid):
        try:
            while not stop.is_set():
                served.append(ds.lookup(_queries(cid, 4)))
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(3)]
    for th in threads:
        th.start()
    try:
        for i in range(8):
            ds.extend(_queries(200 + i, 16), np.full((16,), i))
    finally:
        stop.set()
        for th in threads:
            th.join(60)
        ds.server.close()
    assert not errors
    assert len(ds) == 512 + 8 * 16
    assert served  # the worker really ran concurrently with the extends
    probe = _queries(207, 16)  # == last extend's keys
    _, idxs = ds.index.search(probe)
    # new rows are live and searchable (MIPS self-match can legitimately
    # lose to a longer old vector occasionally, so assert the bulk)
    assert (np.asarray(idxs)[:, 0] >= 512).mean() >= 0.75


# --- integration: engine + datastore route through the server ----------------


def test_datastore_lookup_via_server_matches_direct():
    from repro.retrieval.datastore import KNNDatastore

    keys = jax.random.normal(jax.random.PRNGKey(5), (512, D))
    toks = jax.random.randint(jax.random.PRNGKey(6), (512,), 0, 100)
    ds = KNNDatastore(keys, toks, k=4, capacity=1024)
    q = _queries(80, 6)
    direct = ds.lookup(q)
    ds.attach_server(clock=VirtualClock(), config=ServeConfig(max_batch=32))
    served = ds.lookup(q)
    np.testing.assert_array_equal(np.asarray(direct[0]), np.asarray(served[0]))
    np.testing.assert_array_equal(np.asarray(direct[1]), np.asarray(served[1]))
    assert ds.stats()["server"]["batches"] == 1
    # frequent updates keep working with a server attached (extend/forget
    # take the server's mutation gate) and are immediately visible
    # long keys, so under MIPS each new key is its own exact top-1
    new_keys = 4 * _queries(81, 4)
    ds.extend(new_keys, np.arange(4))
    _, served_toks = ds.lookup(new_keys)
    assert (np.asarray(served_toks)[:, 0] == np.arange(4)).all()
    ds.forget([0, 1])
    _, idxs = ds.index.search(q)
    assert not {0, 1} & set(np.asarray(idxs).ravel().tolist())
    with pytest.raises(ValueError, match="different Index"):
        other = Index.build(keys, metric="mips", k=4)
        ds.attach_server(SearchServer(other, clock=VirtualClock()))


def test_engine_retrieval_coalesces_through_shared_server():
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.serving.engine import ServingEngine

    cfg = get_config("internlm2-1.8b-smoke")
    params = tfm.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, batch=2, max_seq=64)

    keys = jax.random.normal(jax.random.PRNGKey(1), (1024, 32))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1024,), 0, 100)
    idx = Index.build(keys, metric="mips", k=4)
    server = SearchServer(idx, ServeConfig(max_batch=32), clock=VirtualClock())
    server.precompile()
    eng.attach_retrieval(idx, tokens, server=server)

    q = keys[:3] + 0.01
    backends.reset_dispatch_counts()
    # another client's queued request shares the engine lookup's dispatch
    other = server.submit(np.asarray(keys[10:14]))
    scores, toks = eng.retrieve(q)
    assert DISPATCH_COUNTS["xla"] == 1  # engine slots + other: ONE dispatch
    assert other.done
    assert scores.shape == toks.shape == (3, 4)
    direct_scores, direct_idxs = idx.search(q)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(direct_scores))
    _, direct_toks = np.asarray(direct_scores), np.take(
        np.asarray(tokens), np.asarray(direct_idxs), axis=0
    )
    np.testing.assert_array_equal(np.asarray(toks), direct_toks)

    # a server over a different index is rejected up front
    with pytest.raises(ValueError, match="different Index"):
        other = Index.build(keys, metric="mips", k=4)
        eng.attach_retrieval(
            idx, tokens, server=SearchServer(other, clock=VirtualClock())
        )


# --- fault-tolerance surface (PR 7; depth lives in tests/test_faults.py) -----


def test_deadline_request_completes_within_budget(index):
    """The happy path: a deadline that never expires changes nothing —
    same coalescing, same results."""
    clock = VirtualClock()
    server = SearchServer(index, ServeConfig(max_batch=32), clock=clock)
    q = _queries(90, 4)
    t = server.submit(q, deadline_s=10.0)
    server.run_until_idle()
    np.testing.assert_array_equal(
        np.asarray(t.result().indices), np.asarray(index.search(q).indices)
    )
    assert server.stats()["deadline_expired"] == 0
    server.close()


def test_health_on_a_clean_server(index):
    server = _vserver(index)
    h = server.health()
    assert h["status"] == "ok"
    assert h["worker_alive"] and not h["closed"]
    assert h["pending_rows"] == 0
    assert "cluster_miss" not in h  # unclustered index: no miss monitor
    server.submit(_queries(91, 4)).result()
    assert server.health()["failed_batches"] == 0
    server.close()


def test_stats_include_failure_taxonomy_counters(index):
    server = _vserver(index)
    s = server.stats()
    for key in ("deadline_expired", "transient_faults", "dispatch_retries",
                "worker_deaths", "worker_restarts", "requeued_tickets",
                "load_shed", "miss_sampled_rows"):
        assert s[key] == 0, key
    server.close()

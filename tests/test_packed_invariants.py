"""Property tests for the packed-state invariants (satellite of PR 4).

Three families of invariants the packed search state
(``repro.search.packed``) relies on but only spot-checked until now:

  * **Metric consistency** — for every ``rowwise`` metric,
    ``prepare_database`` restricted to a slice equals ``prepare_update``
    of that slice (db rows AND bias), for arbitrary slices; this is the
    exact property ``Index.add`` exploits to prepare only appended rows.
  * **Fused bias-row correctness** — after an *arbitrary interleaving* of
    ``add`` / ``delete`` (with duplicate ids, growth events, deletes of
    not-yet-compacted rows), the packed bias row and db rows are equal to
    a reference rebuilt from scratch with ``fuse_bias`` over the raw
    database and live mask.
  * **Tail-mask containment** — the pallas layout pads N up to the tile
    contract; padded (and tombstoned) rows must never surface in top-k,
    even when k presses against the live row count.

Runs under Hypothesis when it is installed (the repo's property-test
convention, cf. ``tests/test_binning.py``); in environments without it the
suite falls back to a fixed, deterministically-sampled example grid over
the same strategies, so these invariants keep CI coverage instead of
skipping (the container image has no ``hypothesis``).
"""
import itertools
import random

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.search import Index, SearchSpec, fuse_bias, get_metric
from repro.search.packed import pack_state

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # deterministic fallback, see module docstring
    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, values):
            self.values = list(values)

    class st:  # noqa: N801 — mirrors the hypothesis namespace
        @staticmethod
        def integers(min_value, max_value):
            span = max_value - min_value
            picks = sorted({
                min_value,
                max_value,
                min_value + span // 2,
                min_value + span // 3,
                min_value + (2 * span) // 3,
            })
            return _Strategy(picks)

        @staticmethod
        def sampled_from(seq):
            return _Strategy(seq)

    def settings(**_kwargs):
        def deco(fn):
            return fn

        return deco

    def given(**strategies):
        """Run the property over a fixed sample of the strategy product.

        A deterministic ``random.Random`` picks (at most) 16 combinations,
        always including the all-minimum and all-maximum corners.
        """

        def deco(fn):
            names = list(strategies)
            pools = [strategies[n].values for n in names]

            # NOT functools.wraps: pytest must see a zero-argument
            # signature, or it mistakes the strategy params for fixtures.
            def wrapper():
                combos = list(itertools.product(*pools))
                corners = [combos[0], combos[-1]]
                rnd = random.Random(0xC0FFEE)
                body = (
                    rnd.sample(combos, 8) if len(combos) > 8 else combos
                )
                seen = set()
                for combo in corners + body:
                    if combo in seen:
                        continue
                    seen.add(combo)
                    fn(**dict(zip(names, combo)))

            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper

        return deco


METRICS = ("mips", "l2", "cosine")
D = 16


def _db(seed: int, n: int, d: int = D) -> jnp.ndarray:
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d))


# --- Metric.prepare / prepare_update / rowwise consistency -------------------


@settings(max_examples=25, deadline=None)
@given(
    metric=st.sampled_from(METRICS),
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.integers(min_value=2, max_value=96),
    cut_num=st.integers(min_value=0, max_value=7),
)
def test_prepare_update_matches_full_prepare_on_any_slice(
    metric, seed, n, cut_num
):
    """rowwise contract: prepare_database(db)[i:j] == prepare_update(db[i:j])
    for db rows and bias alike — the property Index.add builds on."""
    m = get_metric(metric)
    assert m.rowwise
    db = _db(seed, n)
    cut = (cut_num * n) // 8  # slice start anywhere in [0, n)
    full_rows, full_bias = m.prepare_database(db)
    part_rows, part_bias = m.prepare_update(db[cut:])
    np.testing.assert_allclose(
        np.asarray(full_rows[cut:]), np.asarray(part_rows), rtol=1e-6
    )
    if full_bias is None:
        assert part_bias is None
    else:
        np.testing.assert_allclose(
            np.asarray(full_bias[cut:]), np.asarray(part_bias), rtol=1e-6
        )


# --- fused bias row under arbitrary add/delete interleavings -----------------


def _apply_random_ops(index, pool, rng, n_ops):
    """Drive ``index`` with a random interleaving of add/delete; mirror the
    same ops on a host-side reference (db rows + live mask)."""
    ref_db = [np.asarray(r) for r in np.asarray(index._db[: index._size])]
    ref_live = [True] * index._size
    cursor = index._size
    for _ in range(n_ops):
        if rng.random() < 0.5 and cursor < pool.shape[0]:
            r = int(rng.integers(1, 5))
            rows = pool[cursor : cursor + r]
            if rows.shape[0] == 0:
                continue
            index.add(rows)
            ref_db.extend(np.asarray(rows))
            ref_live.extend([True] * rows.shape[0])
            cursor += rows.shape[0]
        else:
            # duplicate ids within a call and re-deletes across calls are
            # both legal; ids may also hit rows added moments ago
            ids = rng.integers(0, len(ref_db), size=int(rng.integers(1, 4)))
            index.delete(ids.tolist())
            for i in ids:
                ref_live[int(i)] = False
    return np.stack(ref_db), np.asarray(ref_live)


@settings(max_examples=15, deadline=None)
@given(
    metric=st.sampled_from(METRICS),
    backend=st.sampled_from(("xla", "pallas")),
    seed=st.integers(min_value=0, max_value=2**16),
    n_ops=st.integers(min_value=1, max_value=12),
)
def test_bias_row_matches_reference_under_interleaving(
    metric, backend, seed, n_ops
):
    """After ANY interleaving of add/delete (growth included), the packed
    state equals a from-scratch reference pack of the same rows + live
    mask: incremental patches never drift."""
    rng = np.random.default_rng(seed)
    pool = _db(seed, 160)
    n0 = int(rng.integers(8, 48))
    index = Index.build(
        pool[:n0], metric=metric, k=4, backend=backend, capacity_block=32
    )
    ref_rows, ref_live = _apply_random_ops(index, pool, rng, n_ops)

    pk = index.pack()
    m = get_metric(metric)
    prepped, metric_bias = m.prepare_database(jnp.asarray(ref_rows))
    want_bias = np.asarray(
        fuse_bias(
            metric_bias,
            jnp.asarray(ref_live),
            num_rows=ref_rows.shape[0],
        )
    )
    n_written = ref_rows.shape[0]
    np.testing.assert_allclose(
        np.asarray(pk.rows()[:n_written]), np.asarray(prepped), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(pk.bias_row()[:n_written]), want_bias
    )
    # everything past the append high-water mark is dead capacity
    from repro.search.backends import MASK_VALUE

    tail = np.asarray(pk.bias_row()[n_written:])
    assert (tail == MASK_VALUE).all()
    # and the index agrees with the reference live count
    assert index.size == int(ref_live.sum())


# --- tail mask never leaks padded rows into top-k ----------------------------


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.integers(min_value=33, max_value=203),
    k=st.integers(min_value=1, max_value=16),
    n_delete=st.integers(min_value=0, max_value=24),
)
def test_tail_mask_never_leaks_padded_rows(seed, n, k, n_delete):
    """Pallas layout: N is padded up to the kernel tile contract and rows
    may be tombstoned — no padded or deleted row index may ever appear in
    top-k, even with k pressing against the live count."""
    k = min(k, max(1, n - n_delete - 1))
    db = _db(seed, n)
    index = Index.build(db, metric="mips", k=k, backend="pallas")
    rng = np.random.default_rng(seed)
    dead = (
        np.unique(rng.integers(0, n, size=n_delete)) if n_delete else
        np.asarray([], np.int64)
    )
    if dead.size:
        index.delete(dead.tolist())
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, D))
    _, idxs = index.search(q)
    got = np.asarray(idxs)
    assert got.min() >= 0
    assert got.max() < n, (
        f"padded row index {got.max()} >= n={n} leaked into top-k"
    )
    assert not (set(got.ravel().tolist()) & set(dead.tolist())), (
        "tombstoned row leaked into top-k"
    )


# --- quantized tiers: incremental patches == from-scratch pack ---------------


@settings(max_examples=15, deadline=None)
@given(
    metric=st.sampled_from(METRICS),
    storage=st.sampled_from(("bf16", "int8")),
    seed=st.integers(min_value=0, max_value=2**16),
    n_ops=st.integers(min_value=1, max_value=10),
)
def test_quantized_state_matches_reference_under_interleaving(
    metric, storage, seed, n_ops
):
    """quantize -> prepare_update consistency: after ANY add/delete
    interleaving (growth included) the quantized rows, int8 scales, scan
    bias (with its stored-value bias correction) and f32 rescore tail all
    equal a from-scratch ``pack_state`` of the same rows + live mask."""
    from repro.search.packed import pack_state
    from repro.search.spec import SearchSpec

    rng = np.random.default_rng(seed)
    pool = _db(seed, 160)
    n0 = int(rng.integers(8, 48))
    index = Index.build(
        pool[:n0], metric=metric, k=4, backend="xla", storage=storage,
        capacity_block=32,
    )
    ref_rows, ref_live = _apply_random_ops(index, pool, rng, n_ops)

    pk = index.pack()
    n_written = ref_rows.shape[0]
    cap = index.capacity
    ref_padded = jnp.zeros((cap, D)).at[:n_written].set(ref_rows)
    ref_live_padded = (
        jnp.zeros((cap,), bool).at[:n_written].set(jnp.asarray(ref_live))
    )
    want = pack_state(
        ref_padded, ref_live_padded, get_metric(metric), index.spec, "xla"
    )
    np.testing.assert_array_equal(np.asarray(pk.db), np.asarray(want.db))
    np.testing.assert_array_equal(
        np.asarray(pk.bias), np.asarray(want.bias)
    )
    if storage == "int8":
        # dead capacity past the high-water mark is bias-masked, so its
        # scale is arbitrary (growth pads 0, a fresh pack floors it) —
        # the written region must agree exactly.
        np.testing.assert_array_equal(
            np.asarray(pk.scale_row()[:n_written]),
            np.asarray(want.scale_row()[:n_written]),
        )
    np.testing.assert_allclose(
        np.asarray(pk.rescore_db), np.asarray(want.rescore_db), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(pk.rescore_bias), np.asarray(want.rescore_bias)
    )
    assert index.size == int(ref_live.sum())


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.integers(min_value=33, max_value=203),
    k=st.integers(min_value=1, max_value=16),
    n_delete=st.integers(min_value=0, max_value=24),
)
def test_rescore_tail_never_leaks_tombstoned_rows(seed, n, k, n_delete):
    """The exact rescore pass recomputes true scores from the f32 tail —
    without its own tombstone mask it would resurrect deleted rows with
    *winning* scores.  Same adversarial grid as the f32 tail-mask test,
    on the quantized pallas layout."""
    k = min(k, max(1, n - n_delete - 1))
    db = _db(seed, n)
    index = Index.build(db, metric="mips", k=k, backend="pallas",
                        storage="int8")
    rng = np.random.default_rng(seed)
    dead = (
        np.unique(rng.integers(0, n, size=n_delete)) if n_delete else
        np.asarray([], np.int64)
    )
    if dead.size:
        index.delete(dead.tolist())
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, D))
    _, idxs = index.search(q)
    got = np.asarray(idxs)
    assert got.min() >= 0
    assert got.max() < n, (
        f"padded row index {got.max()} >= n={n} leaked into quantized top-k"
    )
    assert not (set(got.ravel().tolist()) & set(dead.tolist())), (
        "tombstoned row resurrected by the rescore tail"
    )


# --- result indices: unique, live, sentinel-masked (PR 9) --------------------


@settings(max_examples=15, deadline=None)
@given(
    backend=st.sampled_from(("xla", "pallas")),
    storage=st.sampled_from(("f32", "bf16", "int8", "int4")),
    seed=st.integers(min_value=0, max_value=2**16),
    density_pct=st.integers(min_value=0, max_value=90),
)
def test_result_indices_unique_and_live(backend, storage, seed, density_pct):
    """Across backend × storage (int4 included) × add/delete interleavings
    × tombstone densities up to 90 %: every returned index with a real
    (non-masked) score is unique within its row, in range, and live; on
    the pallas path a masked entry carries the sentinel index -1 (never a
    phantom alias of a real row — the masked-winner clamp bug)."""
    from repro.search.backends import MASK_VALUE

    rng = np.random.default_rng(seed)
    pool = _db(seed, 160)
    n0 = int(rng.integers(40, 96))
    index = Index.build(
        pool[:n0], metric="mips", k=8, backend=backend, storage=storage,
        capacity_block=32,
    )
    _, ref_live = _apply_random_ops(index, pool, rng, int(rng.integers(1, 6)))
    n_written = ref_live.shape[0]
    target_dead = (n_written * density_pct) // 100
    extra = [i for i in range(n_written) if ref_live[i]][: target_dead]
    if extra:
        index.delete(extra)
        ref_live[np.asarray(extra)] = False
    live_ids = set(np.flatnonzero(ref_live).tolist())
    q = jax.random.normal(jax.random.PRNGKey(seed + 3), (6, D))
    vals, idxs = index.search(q)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    for row_v, row_i in zip(vals, idxs):
        real = row_i[np.abs(row_v) < -MASK_VALUE * 0.5]
        assert len(set(real.tolist())) == len(real), f"duplicates: {row_i}"
        assert all(int(i) in live_ids for i in real), (
            f"dead/padded row surfaced: {row_i}"
        )
        masked = row_i[np.abs(row_v) >= -MASK_VALUE * 0.5]
        if backend == "pallas":
            assert (masked == -1).all(), (
                f"pallas masked winners must be -1, got {masked}"
            )


def test_quantized_mass_delete_returns_only_sentinels():
    db = _db(11, 40)
    index = Index.build(db, metric="l2", k=4, backend="xla", storage="int8")
    index.delete(list(range(40)))
    assert index.size == 0
    vals, idxs = index.search(
        jax.random.normal(jax.random.PRNGKey(9), (4, D))
    )
    from repro.search.backends import MASK_VALUE

    # L2 negates at the boundary: masked scores surface as -MASK_VALUE
    assert (np.asarray(vals) >= -MASK_VALUE).all()
    assert int(np.asarray(idxs).max()) < 40


def test_fallback_grid_is_active_without_hypothesis():
    """Make the fallback visible in test output: exactly one of the two
    modes is in effect, and the strategies sample real values either way."""
    s = st.integers(min_value=0, max_value=10)
    if HAVE_HYPOTHESIS:
        # a real hypothesis strategy, not our shim
        assert type(s).__module__.startswith("hypothesis")
        assert not hasattr(s, "values")
    else:
        assert s.values[0] == 0 and s.values[-1] == 10


# direct (non-property) regression pins for corners the sampling above
# might visit rarely: growth exactly at the capacity boundary, and a
# delete-everything index.


def test_growth_boundary_keeps_bias_reference():
    pool = _db(3, 80)
    index = Index.build(pool[:32], metric="l2", k=4, backend="xla",
                        capacity_block=32)
    index.add(pool[32:64])   # fills capacity exactly
    index.add(pool[64:65])   # forces growth by one block
    pk = index.pack()
    m = get_metric("l2")
    prepped, bias = m.prepare_database(pool[:65])
    np.testing.assert_allclose(
        np.asarray(pk.rows()[:65]), np.asarray(prepped), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(pk.bias_row()[:65]),
        np.asarray(fuse_bias(bias, jnp.ones((65,), bool))),
    )


def test_all_rows_deleted_returns_only_sentinels():
    db = _db(5, 40)
    index = Index.build(db, metric="mips", k=4, backend="pallas")
    index.delete(list(range(40)))
    assert index.size == 0
    vals, idxs = index.search(jax.random.normal(jax.random.PRNGKey(9), (4, D)))
    from repro.search.backends import MASK_VALUE

    assert (np.asarray(vals) <= MASK_VALUE).all()
    assert int(np.asarray(idxs).max()) < 40


# --- snapshot restore-then-serve bit-parity (PR 7) ---------------------------
#
# ``Index.save`` / ``Index.restore`` must reproduce the packed state well
# enough that a restored replica returns BIT-identical results — across
# every backend x storage-tier x cluster combination — without re-running
# build / k-means / quantization (asserted via ``PACK_EVENTS``).

import os  # noqa: E402  (section-local import, mirrors the PR-7 tests)

import pytest  # noqa: E402

from repro.search.packed import PACK_EVENTS, reset_pack_events  # noqa: E402


def _restore_parity(index, queries, tmp_path, *, mesh_axis=None):
    direct = index.search(queries)
    path = os.path.join(tmp_path, "snap")
    index.save(path)
    reset_pack_events()
    restored = Index.restore(path)
    if mesh_axis is not None:  # snapshots land unmeshed; re-shard explicitly
        restored = restored.shard(make_mesh((1,), (mesh_axis,)),
                                  db_axis=mesh_axis)
    got = restored.search(queries)
    np.testing.assert_array_equal(
        np.asarray(got.indices), np.asarray(direct.indices)
    )
    np.testing.assert_array_equal(
        np.asarray(got.values), np.asarray(direct.values)
    )
    assert PACK_EVENTS["restore"] == 1
    assert PACK_EVENTS["full_pack"] == 0, (
        f"restore re-ran a packing pass: {dict(PACK_EVENTS)}"
    )
    return restored


@pytest.mark.parametrize("storage", ["f32", "bf16", "int8", "int4"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_restore_parity_backend_x_storage(backend, storage, tmp_path):
    db = _db(11, 512)
    index = Index.build(db, metric="l2", k=8, backend=backend,
                        storage=storage)
    q = jax.random.normal(jax.random.PRNGKey(12), (16, D))
    _restore_parity(index, q, tmp_path)


@pytest.mark.parametrize("storage", ["f32", "bf16", "int8"])
def test_restore_parity_clustered(storage, tmp_path):
    # the mixture corpus is the regime where cluster="auto" actually
    # enables pruning (cf. tests/test_cluster.py); restore must bring the
    # k-means tables back verbatim, never re-cluster
    rng = np.random.default_rng(13)
    centers = rng.normal(size=(64, D)) * 2.5
    db = jnp.asarray(
        centers[rng.integers(0, 64, 8192)] + rng.normal(size=(8192, D)),
        jnp.float32,
    )
    q = jnp.asarray(
        centers[rng.integers(0, 64, 16)] + rng.normal(size=(16, D)),
        jnp.float32,
    )
    index = Index.build(db, metric="l2", k=10, backend="xla",
                        storage=storage)
    assert index.explain()["cluster"]["enabled"]
    restored = _restore_parity(index, q, tmp_path)
    rep = restored.explain()["cluster"]
    assert rep["enabled"]  # the pruned path, not a silent dense fallback
    assert PACK_EVENTS["cluster_built"] == 0, dict(PACK_EVENTS)


def test_restore_parity_sharded_single_device(tmp_path):
    mesh = make_mesh((1,), ("model",))
    db = _db(14, 512)
    index = Index.build(db, metric="mips", k=8).shard(mesh, db_axis="model")
    q = jax.random.normal(jax.random.PRNGKey(15), (8, D))
    _restore_parity(index, q, tmp_path, mesh_axis="model")


def test_restore_parity_sharded_2d(tmp_path):
    """A 2-D (query x database) sharded index snapshots its full logical
    arrays; restore lands unmeshed and re-sharding onto a 2-D mesh brings
    back bit-identical results with no re-pack."""
    mesh = make_mesh((1, 1), ("data", "model"))
    db = _db(16, 512)
    index = Index.build(db, metric="l2", k=8).shard(
        mesh, db_axis=("data", "model")
    )
    q = jax.random.normal(jax.random.PRNGKey(17), (8, D))
    direct = index.search(q)
    path = os.path.join(tmp_path, "snap2d")
    index.save(path)
    reset_pack_events()
    restored = Index.restore(path).shard(
        make_mesh((1, 1), ("data", "model")),
        db_axis=("data", "model"), batch_axis=None,
    )
    got = restored.search(q)
    np.testing.assert_array_equal(
        np.asarray(got.indices), np.asarray(direct.indices)
    )
    np.testing.assert_array_equal(
        np.asarray(got.values), np.asarray(direct.values)
    )
    assert PACK_EVENTS["restore"] == 1
    assert PACK_EVENTS["full_pack"] == 0, dict(PACK_EVENTS)


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_restore_parity_host_tier(storage, tmp_path):
    """A host-resident index restores bit-identically — residency and the
    planned segment schedule ride in the snapshot spec, so the restored
    replica streams the same waves without re-packing."""
    db = _db(18, 2048)
    index = Index.build(db, metric="l2", k=8, storage=storage,
                        residency="host", segment_rows=1024)
    q = jax.random.normal(jax.random.PRNGKey(19), (8, D))
    restored = _restore_parity(index, q, tmp_path)
    assert restored.spec.residency == "host"
    assert restored.spec.segment_rows == 1024
    assert restored.explain()["residency"]["num_segments"] == 2


# --- stage composition == the compiled search (PR 8) -------------------------
#
# The backends are assemblies of ``repro.search.stages`` primitives; the
# property below re-assembles the dense pipeline *eagerly* (no jit) from
# the live packed operands and demands bit-parity with ``Index.search``
# after arbitrary add/delete interleavings — i.e. stage composition
# commutes with the incremental-update machinery.


@settings(max_examples=10, deadline=None)
@given(
    metric=st.sampled_from(METRICS),
    storage=st.sampled_from(("f32", "int8")),
    seed=st.integers(min_value=0, max_value=2**16),
    n_ops=st.integers(min_value=1, max_value=10),
)
def test_stage_composition_matches_search_under_interleaving(
    metric, storage, seed, n_ops
):
    from repro.search import stages
    from repro.search.packed import scan_k_for

    rng = np.random.default_rng(seed)
    pool = _db(seed, 160)
    n0 = int(rng.integers(8, 48))
    index = Index.build(
        pool[:n0], metric=metric, k=4, backend="xla", storage=storage,
        capacity_block=32, cluster="off",
    )
    _apply_random_ops(index, pool, rng, n_ops)
    q = jax.random.normal(jax.random.PRNGKey(seed + 2), (6, D))
    want = index.search(q)

    pk = index.pack()
    spec = index.spec
    m = get_metric(metric)
    qp = m.prepare_queries(q)
    scores = stages.score_rows(qp, pk.db, pk.bias, pk.scale)
    if pk.rescore_db is not None:
        k_scan = scan_k_for(spec, pk.n)
        vals, idxs = stages.scan_candidates(
            scores, k_scan, recall_target=spec.recall_target,
            reduction_input_size_override=spec.reduction_input_size_override,
            aggregate_to_topk=False,
        )
        vals, idxs = stages.rescore_candidates(
            qp, vals, idxs, pk.rescore_db, pk.rescore_bias, spec.k, k_scan,
            spec.use_bitonic,
        )
    else:
        vals, idxs = stages.scan_candidates(
            scores, spec.k, recall_target=spec.recall_target,
            reduction_input_size_override=spec.reduction_input_size_override,
            aggregate_to_topk=True, use_bitonic=spec.use_bitonic,
        )
    vals = stages.finalize_values(vals, m.negate_output)
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want.values))

"""Quantized storage tiers (``repro.search.quant``): correctness contracts.

What this suite pins down:

  * ``storage="f32"`` is bit-identical to the pre-quantization path on
    every backend x metric (the acceptance criterion: the new subsystem
    must be invisible until opted into).
  * bf16/int8 two-pass search returns *exact* values for the indices it
    returns (the rescore pass recomputes true scores), meets a recall
    floor on every backend, and never resurrects tombstoned rows.
  * Quantization primitives: per-row int8 error bound, bf16 round-trip,
    scan_k over-fetch math, the metric-bias correction (scan bias is
    computed from the *stored* values).
  * Incremental ``add`` equals a from-scratch pack on quantized tiers
    (rows, scale, bias, rescore tail), and ``explain()`` reports traffic
    from the stored dtype.
  * Unsupported metric x storage combos fail at build/spec time with an
    actionable error, not a kernel-level failure.

Statistical recall validation lives in ``tests/test_recall_guarantee.py``
(storage axis); traffic-contract (jaxpr/counter) checks in
``tests/test_packed.py``; add/delete interleaving invariants in
``tests/test_packed_invariants.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.search import (
    Index,
    SearchSpec,
    exact_search,
    get_metric,
)
from repro.search import quant
from repro.search.metrics import _REGISTRY, Metric, exact_mips, register_metric

N, D, K = 2048, 24, 8


@pytest.fixture(scope="module")
def data():
    db = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    q = jax.random.normal(jax.random.PRNGKey(0), (64, D))
    return q, db


def _recall(idxs, exact_idxs, k):
    a, b = np.asarray(idxs), np.asarray(exact_idxs)
    return np.mean(
        [len(set(r.tolist()) & set(e.tolist())) / k for r, e in zip(a, b)]
    )


# --- quantization primitives -------------------------------------------------


def test_int8_per_row_error_bound():
    rows = jax.random.normal(jax.random.PRNGKey(3), (32, 64)) * jnp.arange(
        1, 33
    )[:, None]  # wildly different row norms — per-row scales must adapt
    stored, scale = quant.quantize_rows(rows, "int8")
    assert stored.dtype == jnp.int8 and scale.shape == (32,)
    err = np.abs(np.asarray(quant.dequantize_rows(stored, scale) - rows))
    # symmetric rounding: per-entry error <= scale/2 (+ float slack)
    bound = np.asarray(scale)[:, None] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_bf16_roundtrip_and_zero_rows():
    rows = jnp.zeros((4, 8)).at[0, 0].set(1.0)
    stored, scale = quant.quantize_rows(rows, "bf16")
    assert stored.dtype == jnp.bfloat16 and scale is None
    np.testing.assert_allclose(
        np.asarray(quant.dequantize_rows(stored, None)),
        np.asarray(rows), rtol=1e-2,
    )
    # all-zero rows must not divide by zero in the int8 path
    z, zs = quant.quantize_rows(jnp.zeros((3, 8)), "int8")
    assert (np.asarray(z) == 0).all() and np.isfinite(np.asarray(zs)).all()


def test_scan_k_overfetch():
    assert quant.scan_k("f32", 10) == 10
    assert quant.scan_k("bf16", 10) == 15
    assert quant.scan_k("int8", 10) == 20
    assert quant.scan_k("int4", 10) == 30  # T(int4) = 2K extra candidates
    assert quant.scan_k("int8", 10, n=12) == 12  # clamped to the database
    with pytest.raises(ValueError, match="storage tier"):
        quant.scan_k("fp4", 10)


def test_bf16_scan_promotes_fewer_rivals_than_its_budget():
    """The bf16 scan rounds the queries as well as the rows (one bf16 x
    bf16 pass).  The over-fetch budget T(bf16) = ceil(K/2) counts the
    truly-worse rows whose scan score may pass a true top-K entry's; with
    both roundings that count stays far under it (``quant``'s docstring)."""
    from repro.search.stages import score_rows

    n, d, m, k = 16384, 128, 32, 10
    rows = jax.random.normal(jax.random.PRNGKey(40), (n, d))
    q = jax.random.normal(jax.random.PRNGKey(41), (m, d))
    stored, _ = quant.quantize_rows(rows, "bf16")
    held = np.asarray(stored, np.float64)
    scan = np.asarray(score_rows(q, stored, jnp.asarray(
        -0.5 * (held * held).sum(1), jnp.float32)), np.float64)
    x, qe = np.asarray(rows, np.float64), np.asarray(q, np.float64)
    exact = qe @ x.T - 0.5 * (x * x).sum(1)
    rivals = []
    for i in range(m):
        top = np.argsort(-exact[i])[:k]
        worse = np.ones(n, bool)
        worse[top] = False
        rivals += [int(np.sum(worse & (scan[i] > scan[i, e]))) for e in top]
    budget = quant.scan_k("bf16", k) - k
    assert max(rivals) <= budget
    assert np.mean(rivals) <= 0.1 * budget


def test_int4_per_row_error_bound():
    rows = jax.random.normal(jax.random.PRNGKey(5), (32, 64)) * jnp.arange(
        1, 33
    )[:, None]
    stored, scale = quant.quantize_rows(rows, "int4")
    codes = np.asarray(stored)
    # canonical form: int8 container, one code per element, codes in [-7, 7]
    assert stored.dtype == jnp.int8 and codes.shape == rows.shape
    assert codes.min() >= -7 and codes.max() <= 7
    np.testing.assert_allclose(
        np.asarray(scale), np.abs(np.asarray(rows)).max(axis=-1) / 7.0,
        rtol=1e-6,
    )
    err = np.abs(np.asarray(quant.dequantize_rows(stored, scale) - rows))
    assert (err <= np.asarray(scale)[:, None] * 0.5 + 1e-6).all()


@pytest.mark.parametrize("d", [8, 64, 7])  # odd d exercises the zero-pad
def test_int4_pack_roundtrip(d):
    rows = jax.random.normal(jax.random.PRNGKey(7), (16, d)) * 3.0
    codes, _ = quant.quantize_rows(rows, "int4")
    packed = quant.pack_int4_rows(codes)
    assert packed.dtype == jnp.int8 and packed.shape == (16, (d + 1) // 2)
    unpacked = np.asarray(quant.unpack_int4_rows(packed))[:, :d]
    np.testing.assert_array_equal(unpacked, np.asarray(codes))


def test_storage_bias_is_computed_from_stored_values(data):
    """The L2 scan bias must be -||x_hat||^2/2 of the *dequantized stored*
    rows, not of the f32 originals — otherwise quantized scan scores are
    internally inconsistent."""
    _, db = data
    m = get_metric("l2")
    qr = m.prepare_storage(db, "int8")
    want = -0.5 * np.sum(
        np.asarray(quant.dequantize_rows(qr.rows, qr.scale)) ** 2, axis=-1
    )
    np.testing.assert_allclose(np.asarray(qr.bias), want, rtol=1e-5)
    # and the rescore tail keeps the exact f32 bias
    np.testing.assert_allclose(
        np.asarray(qr.exact_bias),
        -0.5 * np.sum(np.asarray(db) ** 2, axis=-1),
        rtol=1e-6,
    )


# --- f32 bit-identity (the "invisible until opted into" criterion) -----------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
def test_f32_storage_is_bit_identical(data, backend, metric):
    q, db = data
    plain = Index.build(db, metric=metric, k=K, backend=backend).search(q)
    tiered = Index.build(
        db, metric=metric, k=K, backend=backend, storage="f32"
    ).search(q)
    np.testing.assert_array_equal(
        np.asarray(plain.values), np.asarray(tiered.values)
    )
    np.testing.assert_array_equal(
        np.asarray(plain.indices), np.asarray(tiered.indices)
    )


def test_f32_storage_is_bit_identical_sharded(data):
    q, db = data
    mesh = make_mesh((1,), ("model",))
    plain = Index.build(db, k=K).shard(mesh, db_axis="model").search(q)
    tiered = (
        Index.build(db, k=K, storage="f32")
        .shard(mesh, db_axis="model")
        .search(q)
    )
    np.testing.assert_array_equal(
        np.asarray(plain.values), np.asarray(tiered.values)
    )
    np.testing.assert_array_equal(
        np.asarray(plain.indices), np.asarray(tiered.indices)
    )


# --- two-pass search: recall + exact values ----------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("storage", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
def test_quantized_search_recall_floor(data, backend, storage, metric):
    q, db = data
    index = Index.build(
        db, metric=metric, k=K, backend=backend, storage=storage,
        recall_target=0.95,
    )
    assert index.expected_recall >= 0.95  # over-fetched Eq. 13 bound
    _, idxs = index.search(q)
    _, exact = exact_search(q, db, K, metric=metric)
    assert _recall(idxs, exact, K) >= 0.9


@pytest.mark.parametrize("storage", ["bf16", "int8"])
def test_quantized_search_sharded(data, storage):
    q, db = data
    mesh = make_mesh((1,), ("model",))
    index = Index.build(db, metric="l2", k=K, storage=storage).shard(
        mesh, db_axis="model"
    )
    _, idxs = index.search(q)
    _, exact = exact_search(q, db, K, metric="l2")
    assert _recall(idxs, exact, K) >= 0.9


@pytest.mark.parametrize("metric", ["mips", "l2"])
def test_rescored_values_are_exact(data, metric):
    """The values returned for quantized tiers come from the f32 rescore
    pass — they must equal the exact metric scores of the returned
    indices, not the quantized scan's approximations."""
    q, db = data
    index = Index.build(db, metric=metric, k=K, backend="xla",
                        storage="int8")
    vals, idxs = index.search(q)
    ev, ei = exact_search(q, db, N, metric=metric)  # full ranking
    lookup = {}
    for row, (rv, ri) in enumerate(zip(np.asarray(ev), np.asarray(ei))):
        for v, i in zip(rv, ri):
            lookup[(row, int(i))] = v
    got = np.asarray(vals)
    for row in range(got.shape[0]):
        for col, i in enumerate(np.asarray(idxs)[row]):
            np.testing.assert_allclose(
                got[row, col], lookup[(row, int(i))], rtol=1e-5, atol=1e-5,
                err_msg=f"row {row} idx {i}: returned value is not the "
                "exact score (rescore pass skipped or biased?)",
            )


def test_rescore_off_returns_approximate_values(data):
    """rescore=False (footprint mode): still searches, values carry
    quantization error, no rescore tail is materialized."""
    q, db = data
    index = Index.build(db, metric="mips", k=K, backend="xla",
                        storage="int8", rescore=False)
    pk = index.pack()
    assert pk.rescore_db is None and pk.rescore_bias is None
    _, idxs = index.search(q)
    _, exact = exact_search(q, db, K, metric="mips")
    assert _recall(idxs, exact, K) >= 0.8  # no over-fetch, looser floor


def test_quantized_tombstones_never_return(data):
    q, db = data
    for backend in ("xla", "pallas"):
        index = Index.build(db, metric="mips", k=K, backend=backend,
                            storage="int8")
        # delete the entire exact top-1 column so the scan's favourites die
        _, exact = exact_search(q, db, K, metric="mips")
        dead = sorted(set(np.asarray(exact)[:, 0].tolist()))
        index.delete(dead)
        _, idxs = index.search(q)
        assert not (set(np.asarray(idxs).ravel().tolist()) & set(dead)), (
            f"{backend}: tombstoned rows resurfaced via the rescore tail"
        )


# --- incremental mutations match a from-scratch pack -------------------------


@pytest.mark.parametrize("storage", ["bf16", "int8", "int4"])
def test_incremental_add_matches_full_pack_quantized(data, storage):
    _, db = data
    inc = Index.build(db[:1024], metric="l2", k=K, backend="xla",
                      storage=storage, capacity=N)
    inc.add(db[1024:])
    full = Index.build(db, metric="l2", k=K, backend="xla",
                       storage=storage, capacity=N)
    a, b = inc.pack(), full.pack()
    np.testing.assert_array_equal(np.asarray(a.db), np.asarray(b.db))
    np.testing.assert_array_equal(np.asarray(a.bias), np.asarray(b.bias))
    if storage in ("int8", "int4"):
        np.testing.assert_array_equal(
            np.asarray(a.scale), np.asarray(b.scale)
        )
    np.testing.assert_allclose(
        np.asarray(a.rescore_db), np.asarray(b.rescore_db), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(a.rescore_bias), np.asarray(b.rescore_bias)
    )


def test_incremental_add_matches_full_pack_bf16_compute_dtype(data):
    """dtype="bfloat16" + storage="int8": the incremental path must repeat
    the full pack's cast-to-compute-dtype-then-quantize order exactly."""
    _, db = data
    kw = dict(metric="l2", k=K, backend="xla", storage="int8",
              dtype="bfloat16", capacity=N)
    inc = Index.build(db[:1024], **kw)
    inc.add(db[1024:])
    full = Index.build(db, **kw)
    np.testing.assert_array_equal(
        np.asarray(inc.pack().db), np.asarray(full.pack().db)
    )
    np.testing.assert_array_equal(
        np.asarray(inc.pack().scale), np.asarray(full.pack().scale)
    )


# --- planner / explain report the stored dtype -------------------------------


def test_explain_reports_storage_traffic(data):
    _, db = data
    f32 = Index.build(db, k=K, backend="xla").explain()
    i8 = Index.build(db, k=K, backend="xla", storage="int8").explain()
    assert f32["storage"]["tier"] == "f32"
    assert f32["storage"]["db_bytes_per_element"] == 4
    assert i8["storage"]["tier"] == "int8"
    assert i8["storage"]["db_bytes_per_element"] == 1
    assert i8["storage"]["rescore"] and i8["storage"]["k_scan"] == 2 * K
    assert (
        i8["storage"]["db_resident_bytes"]
        == f32["storage"]["db_resident_bytes"] / 4
    )
    assert i8["plan"]["storage"] == "int8"


def test_explain_reports_int4_storage_traffic(data):
    """int4 is priced at two codes per byte on the Pallas path (the only
    backend that streams the packed nibbles; dense backends keep the
    canonical 1-byte codes and the planner floors them at int8 cost)."""
    _, db = data
    f32 = Index.build(db, k=K, backend="pallas").explain()
    i4 = Index.build(db, k=K, backend="pallas", storage="int4").explain()
    assert i4["storage"]["tier"] == "int4"
    assert i4["storage"]["db_bytes_per_element"] == 0.5
    assert (
        i4["storage"]["db_resident_bytes"]
        == f32["storage"]["db_resident_bytes"] / 8
    )
    assert i4["storage"]["rescore"] and i4["storage"]["k_scan"] == 3 * K
    assert i4["plan"]["storage"] == "int4"
    # the fused-select scan is the default, and its predicted traffic is
    # what the bench smoke compares against measured db bytes
    assert i4["storage"]["fused_select"]
    assert i4["storage"]["predicted_hbm_bytes"] == i4["plan"]["hbm_bytes"]


def test_planner_traffic_drops_on_fused_kernel():
    """Eq. 10/20 with 1- and 2-byte rows: at a memory-bound shape the
    fused-kernel model must predict >=2x (int8) less HBM traffic — the
    roofline shift the storage tier exists for.  (The dense XLA model is
    dominated by its f32 score matrix, so the drop shows on pallas.)"""
    from repro.search.plan import plan_search

    kw = dict(n=1 << 20, d=128, k=10, m=256, backend="pallas",
              device="tpu_v4")
    f32 = plan_search(**kw)
    bf16 = plan_search(storage="bf16", **kw)
    i8 = plan_search(storage="int8", **kw)
    assert f32.hbm_bytes / i8.hbm_bytes >= 2.0
    assert f32.hbm_bytes / bf16.hbm_bytes >= 1.5
    # reduced traffic moves the knee: attainable FLOP/s never decreases
    assert i8.attainable_flops >= f32.attainable_flops
    assert bf16.attainable_flops >= f32.attainable_flops


def test_quantized_hlo_check_runs(data):
    _, db = data
    report = Index.build(db, k=K, backend="xla", storage="int8").explain(
        validate_hlo=True
    )
    assert "hlo" in report and "skipped" not in report["hlo"]
    assert report["hlo"]["hlo_dot_flops"] > 0


# --- validation: actionable errors, not kernel failures ----------------------


def test_unknown_storage_tier_rejected():
    with pytest.raises(ValueError, match="storage tier"):
        SearchSpec(storage="fp4")


def test_rescore_requires_quantized_tier():
    with pytest.raises(ValueError, match="quantized storage tier"):
        SearchSpec(storage="f32", rescore=True)


def test_rescore_needs_aggregate_to_topk():
    with pytest.raises(ValueError, match="aggregate_to_topk"):
        SearchSpec(storage="int8", rescore=True, aggregate_to_topk=False)
    # auto-resolution: raw-winners mode silently disables the second pass
    assert not SearchSpec(
        storage="int8", aggregate_to_topk=False
    ).rescore_enabled


def test_metric_storage_combo_rejected_actionably(data):
    """A metric whose prepare does not normalize (the ISSUE's 'int8 cosine
    without normalized prepare') must be rejected at spec/build time."""
    register_metric(
        Metric(
            name="raw-cosine",
            negate_output=False,
            prepare_database=lambda db: (db, None),  # NOT normalized
            prepare_queries=lambda q: q,
            exact=exact_mips,
            storage_tiers=("f32", "bf16"),
        ),
        overwrite=True,
    )
    try:
        _, db = data
        with pytest.raises(ValueError, match="storage='int8'"):
            SearchSpec(metric="raw-cosine", storage="int8")
        # the declared tiers still work
        Index.build(db, metric="raw-cosine", k=K, backend="xla",
                    storage="bf16").search(jnp.asarray(data[0]))
    finally:
        _REGISTRY.pop("raw-cosine", None)


def test_late_registered_metric_storage_combo_caught_at_build(data):
    """SearchSpec validates lazily (the metric may not be registered yet);
    Index.build must still catch the bad combo eagerly."""
    spec = SearchSpec(metric="late-raw-cosine", k=K, storage="int8")  # ok
    register_metric(
        Metric(
            name="late-raw-cosine",
            negate_output=False,
            prepare_database=lambda db: (db, None),
            prepare_queries=lambda q: q,
            exact=exact_mips,
            storage_tiers=("f32",),
        ),
        overwrite=True,
    )
    try:
        _, db = data
        with pytest.raises(ValueError, match="storage='int8'"):
            Index.build(db, spec=spec)
    finally:
        _REGISTRY.pop("late-raw-cosine", None)

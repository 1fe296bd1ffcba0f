"""Kernel planner tests: Eq. 4-10/13-14 as configuration, edge cases, parity.

The hard acceptance criteria of the planner PR:
  * ``Index.build(plan="model")`` (the default) is bit-identical to the old
    hard-coded tiles,
  * the planner never emits an invalid layout on degenerate workloads,
  * ``Index.explain()`` reports the plan with predicted roofline numbers,
  * ``plan="measure"`` refines via sweep and persists in the plan cache.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core.binning import round_up
from repro.core.roofline import HARDWARE
from repro.search import Index, SearchSpec, plan_search, tune_plan
from repro.search.plan import (
    Plan,
    PlanCache,
    _vmem_budget,
    _vmem_need,
    detect_device,
)

LEGACY = dict(block_m=256, max_block_n=1024, query_block=4096)


def _data(n, d, m=64, seed=0):
    kq, kd = jax.random.split(jax.random.PRNGKey(seed))
    return (
        jax.random.normal(kd, (n, d)),
        jax.random.normal(kq, (m, d)),
    )


# --- plan validity ----------------------------------------------------------


def _assert_valid(p: Plan):
    """A plan must always describe a realizable layout."""
    assert p.num_bins >= 1
    assert p.padded_n >= p.n
    assert p.num_bins * p.bin_size == p.padded_n
    assert p.block_n % p.bin_size == 0
    assert p.block_n >= p.bin_size
    # tiles never balloon past the data (up to bin/sublane alignment;
    # 32 is the largest sublane count across dtypes)
    assert p.block_n <= round_up(p.n, max(p.bin_size, 32))
    assert p.block_m >= 8 and p.block_m % 8 == 0
    assert p.query_block >= 8
    assert p.d_pad % 128 == 0 and p.d_pad >= p.d
    assert 0.0 < p.expected_recall <= 1.0
    assert p.bottleneck in ("compute", "memory", "instruction")
    assert p.flops > 0 and p.attainable_flops > 0 and p.predicted_s > 0


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(n=4096, d=64, k=10),                      # vanilla
        dict(n=100, d=8, k=1),                          # k=1: bins degenerate
        dict(n=40, d=16, k=4),                          # N < any default tile
        dict(n=1024, d=100, k=10),                      # D not a x128 multiple
        dict(n=1024, d=130, k=10),                      # D just past a lane
        dict(n=128, d=32, k=64, recall_target=0.999),   # recall at the ceiling
        dict(n=256, d=32, k=256),                       # k == n
        dict(n=1_000_000, d=128, k=10, m=10_000),       # paper scale
        dict(n=4096, d=64, k=10, dtype="bfloat16"),     # dtype-aware tiling
    ],
)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_planner_edge_cases_emit_valid_layouts(kwargs, backend):
    p = plan_search(backend=backend, device="tpu_v4", **kwargs)
    _assert_valid(p)
    assert p.source == "model"


def test_recall_ceiling_falls_back_to_exact_layout():
    """A recall target above what L < N bins can give => bin size 1."""
    p = plan_search(n=128, d=32, k=64, recall_target=0.999, device="tpu_v4")
    assert p.log2_bin_size == 0
    assert p.num_bins == p.n


def test_k1_needs_one_bin():
    p = plan_search(n=100, d=8, k=1, device="tpu_v4")
    assert p.expected_recall == 1.0  # the best entry always wins its bin


def test_invalid_requests_raise():
    with pytest.raises(ValueError):
        plan_search(n=10, d=4, k=11, device="tpu_v4")  # k > n
    with pytest.raises(ValueError):
        plan_search(n=0, d=4, k=1, device="tpu_v4")
    with pytest.raises(ValueError):
        plan_search(n=10, d=4, k=2, device="not_a_device")


def test_overrides_pin_choices():
    p = plan_search(
        n=4096, d=64, k=10, device="tpu_v4",
        block_m=64, max_block_n=512, query_block=128,
    )
    assert (p.block_m, p.block_n, p.query_block)[0] == 64
    assert p.block_n <= 512
    assert p.query_block == 128
    assert p.source == "user"


def test_block_m_escalates_off_the_memory_wall():
    """Paper-scale L2 on TPU v4: the planner must not leave the kernel
    memory-bound when a larger query tile fixes it (Fig. 2 as a decision).

    At recall 0.99 the bins (and so block_n) are 1024 wide, and a 512-row
    query tile fits VMEM; at 0.95 a bin fills a 4096-row block_n and the
    full-precision f32 tile at 512 rows would not (``_vmem_need``)."""
    p = plan_search(
        n=1_000_000, d=128, k=10, m=10_000, metric="l2", device="tpu_v4",
        backend="pallas", recall_target=0.99,
    )
    assert p.bottleneck != "memory"
    assert p.block_m > 256  # escalated beyond the legacy anchor
    # Sift/L2 on v4 hits the COP wall (the paper's headline regression)
    assert p.bottleneck == "instruction"
    assert p.attainable_flops < 0.9 * HARDWARE["tpu_v4"].peak_flops


@pytest.mark.parametrize("n,d,metric,storage,tiles", [
    # (block_m, block_n, k_scan, query_block) of the benchmark's
    # sift1m-f32 and glove1.2m-int8 configurations
    (1_000_000, 128, "l2", "f32", (256, 4096, 10, 4096)),
    (1_183_514, 100, "cosine", "int8", (256, 2048, 20, 4096)),
])
def test_v5e_plans_charge_the_fused_winner_buffer(n, d, metric, storage,
                                                  tiles):
    """The fused kernel keeps every bin winner of a query block in VMEM;
    the planner charges that buffer and the benchmark plans keep their
    tiles."""
    from repro.kernels.partial_reduce import winner_buffer_bytes
    from repro.search.plan import _vmem_need

    p = plan_search(n=n, d=d, k=10, metric=metric, recall_target=0.95,
                    backend="pallas", device="tpu_v5e", storage=storage)
    assert (p.block_m, p.block_n, p.k_scan, p.query_block) == tiles
    db_bytes = 4 if storage == "f32" else 1
    two_pass = _vmem_need(p.block_m, p.block_n, p.d_pad, 4, p.bin_size,
                          db_bytes)
    fused = _vmem_need(p.block_m, p.block_n, p.d_pad, 4, p.bin_size,
                       db_bytes, fused_n=n)
    lanes = round_up(round_up(n, p.block_n) // p.bin_size, 128)
    assert fused - two_pass == winner_buffer_bytes(
        p.block_m, round_up(n, p.block_n), p.bin_size
    ) == 2 * 4 * p.block_m * lanes


def test_block_m_shrinks_to_fit_a_large_winner_buffer():
    """Small bins over a large database make the fused winner buffer the
    largest thing in VMEM; the planner halves block_m until it fits."""
    from repro.search.plan import _vmem_budget, _vmem_need

    p = plan_search(n=1_280_000, d=128, k=500, metric="l2",
                    recall_target=0.95, backend="pallas", device="tpu_v5e")
    assert p.block_m < 256
    assert _vmem_need(p.block_m, p.block_n, p.d_pad, 4, p.bin_size, 4,
                      fused_n=p.n) <= _vmem_budget(HARDWARE["tpu_v5e"])


def test_device_detection_resolves():
    assert detect_device() in HARDWARE  # live backend, whatever it is
    assert detect_device("cpu") == "cpu"


@pytest.mark.parametrize("kind,profile", [
    ("TPU v5 lite", "tpu_v5e"),
    ("TPU v4", "tpu_v4"),
    ("TPU v7x", None),
])
def test_device_detection_by_tpu_kind(monkeypatch, kind, profile):
    """A TPU maps onto its profile by ``device_kind``; a kind with no
    profile raises (naming it) instead of planning against another chip."""
    class StubDevice:
        device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [StubDevice()])
    if profile is not None:
        assert detect_device() == profile
    else:
        with pytest.raises(ValueError, match="TPU v7x"):
            detect_device()


# --- Index integration ------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
def test_model_plan_bit_parity_with_legacy_tiles(backend, metric):
    """plan="model" (default) must produce identical results to the old
    hard-coded (256, 1024, 4096) configuration."""
    db, q = _data(1000, 60, m=100)
    new = Index.build(db, spec=SearchSpec(metric=metric, k=7, backend=backend))
    old = Index.build(
        db, spec=SearchSpec(metric=metric, k=7, backend=backend, **LEGACY)
    )
    v1, i1 = new.search(q)
    v2, i2 = old.search(q)
    assert (i1 == i2).all()
    assert (v1 == v2).all()


def test_built_spec_is_resolved_and_plan_exposed():
    db, _ = _data(512, 32)
    index = Index.build(db, k=5)
    assert index.spec.resolved
    p = index.kernel_plan
    _assert_valid(p)
    assert p.source == "model"
    assert index.spec.block_m == p.block_m
    assert index.spec.max_block_n == p.block_n
    assert index.spec.query_block == p.query_block


def test_pallas_tiles_respect_sublane_alignment():
    """block_n must satisfy the TPU tiling contract for the compute dtype
    (sublane-multiple rows), not just the bin-size multiple — interpret
    mode would not catch a Mosaic mistiling on real hardware."""
    p = plan_search(n=1000, d=60, k=7, backend="pallas",
                    dtype="bfloat16", device="tpu_v4")
    assert p.block_n % 16 == 0 and p.block_m % 16 == 0
    p2 = plan_search(n=100, d=16, k=5, backend="pallas", device="tpu_v4")
    assert p2.block_n % 8 == 0  # f32 sublane, even with bin_size 1


def test_pinned_max_block_n_matches_packed_layout():
    """A pin larger than the data is honoured exactly the way the packed
    layout honours it — kernel_plan must describe the executed tile."""
    db, _ = _data(100, 16)
    index = Index.build(db, k=3, backend="pallas", block_m=256,
                        max_block_n=1024, query_block=4096)
    assert index.kernel_plan.block_n == index.pack().block_n


def test_legacy_shim_attribute_access_still_works():
    """`import repro.core; repro.core.knn.mips` worked pre-planner (eager
    shim imports) and must keep working through the lazy re-exports."""
    import importlib
    import warnings

    import repro.core
    import repro.kernels

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert callable(repro.core.knn.mips)
        assert callable(repro.core.mips)
        assert callable(repro.kernels.ops.mips_topk)
        assert callable(repro.kernels.mips_topk)
    assert importlib.import_module("repro.core.knn") is repro.core.knn


def test_small_database_is_not_padded_to_default_tile():
    """N < legacy tile: the planner stops block_n at the data (the packed
    pallas layout then carries no multi-x padding)."""
    db, q = _data(40, 16)
    index = Index.build(db, k=4, backend="pallas")
    p = index.kernel_plan
    assert p.block_n <= round_up(40, max(p.bin_size, 8))
    pk = index.pack()
    assert pk.db.shape[0] <= round_up(40, p.block_n)
    v, i = index.search(q)
    ev, ei = index.metric.exact(q, db, 4)
    assert (i == ei).all()  # tiny N: approx == exact


def test_explicit_plan_object_accepted():
    db, q = _data(256, 32)
    p = plan_search(n=256, d=32, k=3, device="cpu", backend="xla")
    index = Index.build(db, k=3, backend="xla", plan=p)
    assert index.kernel_plan is p
    index.search(q)  # runs


def test_bad_plan_mode_raises():
    db, _ = _data(64, 8)
    with pytest.raises(ValueError):
        Index.build(db, k=2, plan="hillclimb")


def test_plan_survives_growth_and_shard_consistently():
    db, q = _data(500, 24)
    index = Index.build(db, k=5, backend="xla", capacity_block=256)
    index.add(jax.random.normal(jax.random.PRNGKey(9), (600, 24)))
    p = index.kernel_plan
    assert p.n == index.capacity  # re-planned over the grown row space
    _assert_valid(p)
    v, i = index.search(q)
    assert v.shape == (64, 5)


# --- explain ----------------------------------------------------------------


def test_explain_reports_plan_and_predictions():
    db, _ = _data(1024, 48)
    index = Index.build(db, metric="l2", k=10)
    report = index.explain()
    assert report["plan"]["source"] == "model"
    assert report["plan"]["num_bins"] >= 10
    pred = report["predicted"]
    assert pred["bottleneck"] in ("compute", "memory", "instruction")
    assert pred["attainable_flops"] > 0
    assert pred["wall_s"] > 0 and pred["qps"] > 0
    assert 0 < report["expected_recall"] <= 1
    assert report["packed"]["bin_size"] == report["plan"]["bin_size"]


def test_explain_measure_and_hlo_crosscheck():
    db, _ = _data(512, 40)
    index = Index.build(db, k=5, backend="xla")
    report = index.explain(m=64, measure=True, validate_hlo=True)
    meas = report["measured"]
    assert meas["wall_s"] > 0 and meas["qps"] > 0
    assert meas["achieved_flops"] > 0
    # HLO self-audit: the dense xla path runs the unpadded (64, 40) x
    # (512, 40) einsum and the model costs exactly that program, so the
    # compiled dot FLOPs must agree with the model's.
    hlo = report["hlo"]
    assert hlo["hlo_dot_flops"] == 2 * 64 * 512 * 40
    assert hlo["flops_ratio"] == pytest.approx(1.0)


def test_plan_inherits_database_dtype():
    """spec.dtype=None means "inherit the input dtype" — the planner must
    size tiles (and report) for the dtype that actually runs."""
    db = jnp.ones((256, 32), jnp.bfloat16)
    index = Index.build(db, k=3)
    assert index.kernel_plan.dtype == "bfloat16"
    # bf16 sublane floor is 16, so a planner-chosen block_m respects it
    assert index.kernel_plan.block_m % 8 == 0


@pytest.mark.parametrize("m", [1, 24])
def test_bf16_tier_plans_bf16_query_tiles(m):
    """The bf16 tier's kernel takes its queries rounded to bf16, so even
    with f32 queries its query tile keeps the bf16 sublane floor of 16
    rows, and no tile is charged a VMEM cast (the same tile as an index
    whose compute dtype is bf16)."""
    kw = dict(n=1 << 16, d=128, k=10, m=m, backend="pallas",
              device="tpu_v5e", storage="bf16")
    p = plan_search(**kw)
    assert p.block_m % 16 == 0
    both = plan_search(dtype="bfloat16", **kw)
    assert (p.block_m, p.block_n) == (both.block_m, both.block_n)


def test_replans_preserve_recall_accounting_override():
    """Growth re-plans must keep reduction_input_size_override, matching
    the packed relayout's bin math (paper §7 accounting)."""
    db, _ = _data(512, 16)
    index = Index.build(
        db, k=5, backend="xla", capacity_block=256,
        reduction_input_size_override=4096,
    )
    assert index.kernel_plan.reduction_input_size_override == 4096
    before = index.kernel_plan.expected_recall
    index.add(jax.random.normal(jax.random.PRNGKey(3), (600, 16)))
    p = index.kernel_plan
    assert p.reduction_input_size_override == 4096
    # accounting still against the global-N override, and the plan's bin
    # layout equals what the packed state actually laid out
    assert p.num_bins == index.pack().plan.num_bins
    assert p.expected_recall == index.pack().plan.expected_recall
    assert before > 0


def test_xla_cost_models_unpadded_program():
    """The xla plan costs the raw (n, d) einsum, not the pallas padding."""
    p = plan_search(n=500, d=64, k=5, m=64, backend="xla", device="cpu")
    assert p.flops == 2 * 64 * 500 * 64
    pp = plan_search(n=500, d=64, k=5, m=64, backend="pallas", device="cpu")
    assert pp.flops == 2 * 64 * pp.padded_n * 128


def test_explain_rescales_prediction_with_m():
    db, _ = _data(512, 32)
    index = Index.build(db, k=5)
    small = index.explain(m=8)["predicted"]["flops"]
    large = index.explain(m=800)["predicted"]["flops"]
    assert large == pytest.approx(100 * small)


# --- measured refinement + cache -------------------------------------------


def test_tune_plan_persists_and_hits_cache(tmp_path):
    db, _ = _data(256, 16)
    model = plan_search(n=256, d=16, k=3, m=32, backend="xla", device="cpu")
    cache = PlanCache(str(tmp_path / "plans.json"))
    tuned = tune_plan(db, model, cache=cache, repeats=1)
    assert tuned.source == "measure"
    _assert_valid(tuned)
    assert len(cache) == 1
    entry = cache.get(model)
    assert entry["block_m"] == tuned.block_m
    assert entry["wall_s"] > 0
    # a fresh cache object re-reads the file; the sweep must not rerun
    # (we verify via the identical tile triple coming straight from disk)
    reloaded = PlanCache(str(tmp_path / "plans.json"))
    tuned2 = tune_plan(db, model, cache=reloaded, repeats=1)
    assert (tuned2.block_m, tuned2.block_n, tuned2.query_block) == (
        tuned.block_m, tuned.block_n, tuned.query_block
    )


def test_build_with_measure_mode(tmp_path):
    db, q = _data(256, 16, m=16)
    cache = PlanCache(str(tmp_path / "plans.json"))
    index = Index.build(db, k=3, backend="xla", plan="measure",
                        plan_cache=cache)
    assert index.kernel_plan.source == "measure"
    assert len(cache) == 1
    v, i = index.search(q)
    # measured tiles may differ from the model's, results may not
    ref = Index.build(db, k=3, backend="xla")
    rv, ri = ref.search(q)
    assert (i == ri).all() and (v == rv).all()


def test_measure_respects_pins_and_keys_cache_separately(tmp_path):
    """A pinned spec field is never varied by the sweep, the reported plan
    matches the executed spec, and pinned results get their own cache key."""
    db, _ = _data(256, 16)
    cache = PlanCache(str(tmp_path / "plans.json"))
    index = Index.build(db, k=3, backend="xla", plan="measure",
                        plan_cache=cache, query_block=64)
    assert index.spec.query_block == 64
    assert index.kernel_plan.query_block == 64  # report == execution
    assert len(cache) == 1
    # the pinned entry must not be served to an unpinned lookup
    assert cache.get(index.kernel_plan) is None


def test_plan_to_spec_round_trip():
    p = plan_search(n=2048, d=64, k=10, device="cpu", backend="xla")
    spec = p.to_spec(SearchSpec(metric="l2", k=10, query_block=64))
    assert spec.query_block == 64       # explicit override wins
    assert spec.block_m == p.block_m    # planner fills the rest
    assert spec.max_block_n == p.block_n
    assert spec.resolved


def test_measured_plan_prediction_matches_its_tiles(tmp_path):
    """tune_plan must re-derive the roofline prediction for the winning
    tiles — not report the model tiles' numbers under measured tiles."""
    model = plan_search(n=1024, d=32, k=5, m=256, backend="pallas",
                        device="tpu_v4")
    cache = PlanCache(str(tmp_path / "p.json"))
    cache.put(model, {
        "block_m": model.block_m * 2, "block_n": model.block_n,
        "query_block": model.query_block, "wall_s": 1.0,
    })
    tuned = tune_plan(None, model, cache=cache)  # cache hit: db unused
    assert tuned.source == "measure"
    assert tuned.block_m == model.block_m * 2
    ref = plan_search(
        n=1024, d=32, k=5, m=256, backend="pallas", device="tpu_v4",
        block_m=model.block_m * 2, max_block_n=model.block_n,
        query_block=model.query_block,
    )
    assert tuned.hbm_bytes == ref.hbm_bytes
    assert tuned.bottleneck == ref.bottleneck
    assert tuned.predicted_s == ref.predicted_s


def test_sharded_query_block_not_shrunk_by_global_n():
    """The sharded score tile is (qb, n_local) per shard; the planner must
    not shrink qb against the *global* N it cannot apportion."""
    p = plan_search(n=1 << 22, d=64, k=10, backend="sharded",
                    device="tpu_v4")
    assert p.query_block == 4096


def test_sharded_plan_runs_the_fused_kernel_in_each_shard():
    """deep50m-f32 on a four-chip v5e: each shard's 12.5M rows get the
    one-chip fused-kernel tiles, with the global recall plan's 2^18-row
    bins capped at the tile and the winner buffer charged to VMEM."""
    p = plan_search(n=50_000_000, d=96, k=10, m=10_000, metric="l2",
                    backend="sharded", db_shards=4, device="tpu_v5e")
    assert p.bin_size <= p.block_n and p.block_n % p.bin_size == 0
    n_local = 50_000_000 // 4
    need = _vmem_need(p.block_m, p.block_n, p.d_pad, 4, p.bin_size,
                      fused_n=n_local)
    assert need <= _vmem_budget(HARDWARE["tpu_v5e"])
    assert p.expected_recall >= 0.95
    assert (p.block_m, p.block_n, p.bin_size) == (128, 4096, 4096)
    assert p.num_bins == 4 * 3052 and p.query_block == 4096
    # Priced as the fused kernel over one shard's padded block (2MND at
    # the padded query rows and lanes), not the dense scan over the
    # global N; the all-gather carries k winners a shard.
    rows = round_up(n_local, p.block_n)
    assert p.flops == 2.0 * round_up(10_000, 128) * rows * 128
    assert p.ici_bytes == 8.0 * 10_000 * 10 * 4


@pytest.mark.parametrize("pins", [{}, {"block_m": 8}, {"max_block_n": 256}])
def test_shard_keeps_the_pinned_tiles_and_replans_the_rest(pins):
    """A plan records the tiles its caller pinned (``pinned_tiles``);
    ``Index.shard`` keeps those and re-plans the others for one shard's
    rows, as a sharded build plans them (at a 256-row pinned tile the
    capped bins move block_m 256 -> 512)."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))
    db = jax.random.normal(jax.random.PRNGKey(0), (1 << 16, 96))
    kw = dict(metric="l2", k=10, recall_target=0.5, cluster="off",
              device="tpu_v5e", **pins)
    one = Index.build(db, backend="pallas", **kw)
    moved = one.shard(mesh, db_axis="model")
    built = Index.build(db, backend="sharded", **kw).shard(
        mesh, db_axis="model")
    want = tuple(tile for tile, field in (("block_m", "block_m"),
                                          ("block_n", "max_block_n"))
                 if field in pins)
    assert one.kernel_plan.pinned_tiles == want
    assert moved.kernel_plan.pinned_tiles == want
    tiles = (moved.spec.block_m, moved.spec.max_block_n)
    assert tiles == (built.spec.block_m, built.spec.max_block_n)
    for field, value in pins.items():
        assert getattr(moved.spec, field) == value


@pytest.mark.parametrize("n,d,metric,storage,want", [
    (1_000_000, 128, "l2", "f32", (256, 4096, 4096, 10, 4096)),
    (1_183_514, 100, "cosine", "int8", (256, 2048, 2048, 20, 4096)),
    (1_000_000, 128, "l2", "bf16", (256, 2048, 2048, 15, 4096)),
])
def test_one_chip_plans_of_the_benchmark_cells_hold(n, d, metric, storage,
                                                     want):
    """sift1m-f32, glove1.2m-int8 and sift1m-bf16 on one v5e: block_m,
    block_n, bin_size, k_scan and query_block as their cells run them."""
    p = plan_search(n=n, d=d, k=10, m=10_000, metric=metric,
                    storage=storage, backend="pallas", device="tpu_v5e")
    assert (p.block_m, p.block_n, p.bin_size, p.k_scan,
            p.query_block) == want


def test_plan_cache_corrupt_file_is_empty(tmp_path):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    cache = PlanCache(str(path))
    assert len(cache) == 0


def test_summary_is_json_friendly():
    import json

    p = plan_search(n=512, d=32, k=5, device="tpu_v5e")
    s = p.summary()
    json.dumps(s)  # no numpy scalars / dataclass leftovers
    assert s["bin_size"] == 1 << s["log2_bin_size"]

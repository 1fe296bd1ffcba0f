"""Statistical validation of the paper's recall-in-expectation guarantee.

The headline analytical claim (Eq. 13–14, §5.1) is that the bin layout the
planner derives — L bins, top-1 kept per bin — achieves
``E[recall] = ((L-1)/L)^(K-1) >= recall_target`` *in expectation* over the
random placement of the true top-K entries.  Until now that equation only
*configured* the kernels; nothing checked that searches actually deliver
it.  This suite closes the loop empirically:

  * many independent (database, queries) draws per configuration, fixed
    seeds — the run is bit-reproducible;
  * empirical mean recall against the exact baseline is compared with the
    target minus a concentration margin: per-query recall lies in [0, 1],
    so by Hoeffding the probability that the empirical mean of n samples
    falls ``eps = sqrt(ln(1/delta) / 2n)`` below its expectation is at
    most ``delta`` (we use delta = 1e-6; samples within one trial share a
    database, but for i.i.d. Gaussian data that coupling is negligible —
    and with fixed seeds the test is deterministic anyway: the margin
    calibrates "fail only on a real regression", it is not re-rolled luck);
  * the sweep covers metric x backend x (k, recall_target) corners, all
    under the planner's default ``plan="model"`` configuration — the same
    path production ``Index.build`` takes.

A failure therefore means one of: the bin layout no longer matches Eq. 14,
PartialReduce drops more than the model allows (e.g. a masking bug), or
rescoring corrupts the candidate set — all real regressions, not noise.
"""
import math

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.search import Index, exact_search

N = 2048
D = 24
DELTA = 1e-6  # false-failure probability budget for the Hoeffding margin


def _hoeffding_eps(n_samples: int, delta: float = DELTA) -> float:
    """One-sided deviation eps with P(mean < E[mean] - eps) <= delta for n
    independent samples bounded in [0, 1] (binomial/Hoeffding bound)."""
    return math.sqrt(math.log(1.0 / delta) / (2.0 * n_samples))


def _gaussian_draw(kd, kq, n, m, d):
    """The default i.i.d. Gaussian (db, queries) draw."""
    return jax.random.normal(kd, (n, d)), jax.random.normal(kq, (m, d))


def _mixture_draw(kd, kq, n, m, d, components=64, sep=2.5):
    """Mixture-of-Gaussians (db, queries) draw — queries from the SAME
    component centers as the database.

    This is the regime the cluster-pruned front-end's miss bound models
    (``repro.search.cluster``): neighbour mass concentrated in a few
    clusters, so pruned probing finds it.  On i.i.d. Gaussian data every
    point is nearly equidistant and NO coarse quantizer can prune without
    large misses — that is a property of the data, not a code bug, which
    is why the cluster corners below use this draw instead of reusing
    ``_gaussian_draw``.
    """
    kc, ka, kn = jax.random.split(kd, 3)
    centers = jax.random.normal(kc, (components, d)) * sep
    assign = jax.random.randint(ka, (n,), 0, components)
    db = centers[assign] + jax.random.normal(kn, (n, d))
    kqa, kqn = jax.random.split(kq)
    qassign = jax.random.randint(kqa, (m,), 0, components)
    q = centers[qassign] + jax.random.normal(kqn, (m, d))
    return db, q


def _recall_samples(metric, backend, k, recall_target, *, trials, m, seed=0,
                    storage="f32", cluster="auto", n=N, d=D,
                    draw=_gaussian_draw):
    """Per-query recall samples over ``trials`` fresh (db, queries) draws.

    Returns (samples, expected_recall) where ``expected_recall`` is the
    planner's analytic Eq. 13 value for the layout it chose (for quantized
    ``storage`` tiers: the over-fetched ``((L-1)/L)^(K'-1)`` bound the
    two-pass guarantee rests on; for a cluster-pruned index: the product
    P(no bin collision) x P(no cluster miss)).
    """
    samples = []
    expected = None
    root = jax.random.PRNGKey(seed)
    for t in range(trials):
        kd, kq = jax.random.split(jax.random.fold_in(root, t))
        db, q = draw(kd, kq, n, m, d)
        index = Index.build(
            db, metric=metric, k=k, recall_target=recall_target,
            backend=backend, storage=storage, cluster=cluster,
        )
        assert index.kernel_plan.source == "model"  # the default config
        # Eq. 14: the planner's layout must meet the target analytically.
        assert index.expected_recall >= recall_target
        expected = index.expected_recall
        _, idxs = index.search(q)
        _, exact = exact_search(q, db, k, metric=metric)
        approx = np.asarray(idxs)
        truth = np.asarray(exact)
        for row in range(m):
            hits = len(set(approx[row].tolist()) & set(truth[row].tolist()))
            samples.append(hits / k)
    return np.asarray(samples), expected


# (metric, backend, k, recall_target) corners: every metric, both
# single-device backends, k from "a few" to "many", targets from loose to
# near the guarantee's ceiling.  The pallas entries run the fused kernel in
# interpret mode on CPU, so they use a smaller sample budget.
FAST_CORNERS = [
    ("mips", "xla", 10, 0.95, 6, 256),
    ("l2", "xla", 32, 0.90, 6, 256),
    ("cosine", "xla", 4, 0.99, 6, 256),
    ("mips", "pallas", 8, 0.90, 3, 128),
    ("l2", "pallas", 16, 0.95, 3, 128),
]


@pytest.mark.parametrize(
    "metric,backend,k,recall_target,trials,m", FAST_CORNERS
)
def test_recall_meets_target_in_expectation(
    metric, backend, k, recall_target, trials, m
):
    samples, expected = _recall_samples(
        metric, backend, k, recall_target, trials=trials, m=m
    )
    eps = _hoeffding_eps(len(samples))
    mean = float(samples.mean())
    # The paper's guarantee: E[recall] >= recall_target (Eq. 14) ...
    assert mean >= recall_target - eps, (
        f"{metric}/{backend} k={k}: empirical recall {mean:.4f} is below "
        f"target {recall_target} by more than the {eps:.4f} confidence "
        f"margin over {len(samples)} samples — a real regression"
    )
    # ... and the planner's own Eq. 13 expectation for the layout it chose
    # (a tighter bound, since the discrete bin count rounds recall up).
    assert mean >= expected - eps, (
        f"{metric}/{backend} k={k}: empirical recall {mean:.4f} vs "
        f"analytic E[recall] {expected:.4f} (margin {eps:.4f})"
    )


# Quantized storage tiers (repro.search.quant): the scan ranks by reduced-
# precision scores, the bins are over-fetched (quant.scan_k) and an exact
# second pass rescores — the SAME Eq. 13–14 guarantee must hold at the
# user's k within the same Hoeffding margin.  Corners span tier x metric x
# backend; pallas again with a smaller budget (interpret mode).
QUANT_CORNERS = [
    ("mips", "xla", "bf16", 10, 0.95, 4, 256),
    ("l2", "xla", "int8", 10, 0.95, 4, 256),
    ("cosine", "xla", "int8", 4, 0.99, 4, 256),
    ("l2", "pallas", "bf16", 16, 0.90, 2, 128),
    # sift1m-bf16's k and target: queries and rows both rounded to bf16
    # (one bf16 x bf16 pass) under T(bf16) = ceil(K/2).
    ("l2", "xla", "bf16", 10, 0.95, 4, 256),
    ("l2", "pallas", "bf16", 10, 0.95, 2, 128),
    ("mips", "pallas", "int8", 8, 0.90, 2, 128),
    # int4: half-byte rows, T(int4)=2K over-fetch (quant.scan_k) — the
    # widest-error tier the two-pass guarantee must still absorb.
    ("l2", "xla", "int4", 10, 0.90, 4, 256),
    ("mips", "pallas", "int4", 8, 0.90, 2, 128),
]


@pytest.mark.parametrize(
    "metric,backend,storage,k,recall_target,trials,m", QUANT_CORNERS
)
def test_recall_meets_target_quantized(
    metric, backend, storage, k, recall_target, trials, m
):
    samples, expected = _recall_samples(
        metric, backend, k, recall_target, trials=trials, m=m, seed=3,
        storage=storage,
    )
    eps = _hoeffding_eps(len(samples))
    mean = float(samples.mean())
    assert mean >= recall_target - eps, (
        f"{metric}/{backend}/{storage} k={k}: quantized recall {mean:.4f} "
        f"below target {recall_target} beyond the {eps:.4f} margin over "
        f"{len(samples)} samples — the over-fetch/rescore guarantee broke"
    )
    # the over-fetched layout's own (conservative) Eq. 13 expectation
    assert expected >= recall_target
    assert mean >= expected - eps, (
        f"{metric}/{backend}/{storage} k={k}: {mean:.4f} vs over-fetched "
        f"E[recall] {expected:.4f} (margin {eps:.4f})"
    )


# Cluster-pruned front-end (repro.search.cluster): above the planner's
# crossover the scan covers only the top-rho clusters plus the spill
# block, and the guarantee becomes P(no bin collision) x P(no cluster
# miss) >= recall_target — still with ZERO user tuning parameters (the
# spec only says cluster="auto").  N is above the crossover so the
# planner actually enables pruning; the corpus is the mixture draw the
# miss bound models (see _mixture_draw).  One corner stacks cluster
# pruning over the int8 tier, so the quantized over-fetch and the pruned
# gather compose in a single search.
CLUSTER_N = 8192
CLUSTER_CORNERS = [
    ("mips", "xla", "f32", 10, 0.95, 2, 256),
    ("l2", "xla", "f32", 32, 0.90, 2, 256),
    ("cosine", "xla", "f32", 4, 0.95, 2, 256),
    ("l2", "xla", "int8", 10, 0.95, 2, 256),
    ("l2", "pallas", "f32", 16, 0.90, 1, 128),
]


@pytest.mark.parametrize(
    "metric,backend,storage,k,recall_target,trials,m", CLUSTER_CORNERS
)
def test_recall_meets_target_cluster_pruned(
    metric, backend, storage, k, recall_target, trials, m
):
    samples, expected = _recall_samples(
        metric, backend, k, recall_target, trials=trials, m=m, seed=17,
        storage=storage, cluster="auto", n=CLUSTER_N, draw=_mixture_draw,
    )
    # The planner must have actually enabled pruning at this N — otherwise
    # this test silently degenerates to the dense path.
    probe = Index.build(
        jax.random.normal(jax.random.PRNGKey(0), (CLUSTER_N, D)),
        metric=metric, k=k, recall_target=recall_target, backend=backend,
        storage=storage,
    )
    assert probe.kernel_plan.cluster is not None
    assert probe.kernel_plan.cluster.enabled
    eps = _hoeffding_eps(len(samples))
    mean = float(samples.mean())
    assert mean >= recall_target - eps, (
        f"{metric}/{backend}/{storage} k={k}: cluster-pruned recall "
        f"{mean:.4f} below target {recall_target} beyond the {eps:.4f} "
        f"margin over {len(samples)} samples — the collision x miss "
        f"guarantee broke"
    )
    # the planner's own product bound must itself certify the target
    assert expected >= recall_target


def test_recall_is_approximate_not_exact():
    """Sanity for the whole suite: the approximate path must actually lose
    some neighbours (empirical recall < 1), otherwise every guarantee test
    above is vacuous (e.g. a silent fallback to exact top-k)."""
    samples, expected = _recall_samples(
        "mips", "xla", 32, 0.90, trials=4, m=256
    )
    assert expected < 1.0
    assert samples.mean() < 1.0 - 1e-4, (
        "approximate search returned exact results across 1024 queries — "
        "the recall-guarantee suite is no longer testing the approximate "
        "path"
    )


def test_recall_guarantee_sharded_global_accounting():
    """Paper §7: on the sharded backend the bin budget is split across
    shards but recall is accounted against the *global* N — the guarantee
    must survive that redistribution."""
    mesh = make_mesh((1,), ("model",))
    samples = []
    expected = None
    root = jax.random.PRNGKey(7)
    for t in range(3):
        kd, kq = jax.random.split(jax.random.fold_in(root, t))
        db = jax.random.normal(kd, (N, D))
        q = jax.random.normal(kq, (128, D))
        index = Index.build(db, metric="mips", k=10, recall_target=0.9).shard(
            mesh, db_axis="model"
        )
        assert index.expected_recall >= 0.9
        expected = index.expected_recall
        _, idxs = index.search(q)
        _, exact = exact_search(q, db, 10, metric="mips")
        approx, truth = np.asarray(idxs), np.asarray(exact)
        samples.extend(
            len(set(a.tolist()) & set(b.tolist())) / 10
            for a, b in zip(approx, truth)
        )
    samples = np.asarray(samples)
    eps = _hoeffding_eps(len(samples))
    assert samples.mean() >= 0.9 - eps
    assert samples.mean() >= expected - eps


def test_recall_guarantee_sharded_2d_global_accounting():
    """Eq. 13–14 under 2-D (query x database) sharding: per-shard bins are
    laid out against the GLOBAL N (`reduction_input_size_override`), so
    the ((L-1)/L)^(K-1) bound composes across the db axes exactly as in
    the 1-D §7 argument — the measured recall must clear both the target
    and the planner's analytic expectation, and the plan must price the
    per-shard scan (not the global one) plus the ICI gather."""
    mesh = make_mesh((1, 1), ("data", "model"))
    samples = []
    expected = None
    root = jax.random.PRNGKey(21)
    for t in range(3):
        kd, kq = jax.random.split(jax.random.fold_in(root, t))
        db = jax.random.normal(kd, (N, D))
        q = jax.random.normal(kq, (128, D))
        index = Index.build(db, metric="mips", k=10, recall_target=0.9).shard(
            mesh, db_axis=("data", "model"), batch_axis=None
        )
        assert index.expected_recall >= 0.9
        expected = index.expected_recall
        report = index.explain()
        assert report["sharding"]["db_axes"] == ["data", "model"]
        assert report["sharding"]["per_shard_n"] * \
            report["sharding"]["db_shards"] >= N
        # one shard on the (1,1) test mesh => nothing crosses the ICI;
        # the planner prices the O(k) gather once shards exist
        assert report["sharding"]["ici_gather_bytes"] == 0.0
        from repro.search import plan as planlib

        pod = planlib.plan_search(n=N, d=D, k=10, metric="mips",
                                  recall_target=0.9, backend="sharded",
                                  db_shards=8)
        assert pod.db_shards == 8 and pod.ici_bytes > 0 and pod.ici_s > 0
        _, idxs = index.search(q)
        _, exact = exact_search(q, db, 10, metric="mips")
        approx, truth = np.asarray(idxs), np.asarray(exact)
        samples.extend(
            len(set(a.tolist()) & set(b.tolist())) / 10
            for a, b in zip(approx, truth)
        )
    samples = np.asarray(samples)
    eps = _hoeffding_eps(len(samples))
    assert samples.mean() >= 0.9 - eps
    assert samples.mean() >= expected - eps


@pytest.mark.slow
@pytest.mark.parametrize("metric", ["mips", "l2", "cosine"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("k,recall_target", [(4, 0.99), (10, 0.95), (32, 0.90)])
def test_recall_guarantee_full_sweep(metric, backend, k, recall_target):
    """The exhaustive metric x backend x (k, target) grid (slow tier)."""
    trials, m = (6, 256) if backend == "xla" else (3, 128)
    samples, expected = _recall_samples(
        metric, backend, k, recall_target, trials=trials, m=m, seed=11
    )
    eps = _hoeffding_eps(len(samples))
    assert samples.mean() >= recall_target - eps
    assert samples.mean() >= expected - eps

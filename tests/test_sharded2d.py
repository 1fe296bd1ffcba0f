"""Layout-parity grid: every distribution layout is bit-identical to the
replicated single-device oracle.

The load-bearing claim of the §7 distributed design is that sharding is
*invisible* in the results: per-row scores are computed by the same stage
primitives (``repro.search.stages``) in every layout, shard/segment bin
boundaries align with the oracle's, and only (value, global id) winners
cross the ICI — so in the high-recall regime the (values, indices) pairs
match the replicated oracle bit for bit.  This grid enforces exactly that
over layout x metric x storage, including tombstoned rows and the padded
tails sharding adds, on 8 (fast) / 16 / 48 (``@slow``) fake devices.

Clustered pruning is approximate per construction (bin collisions inside
the pruned candidate list depend on the ownership partition), so its grid
asserts the honest invariants instead: equal-shard-count layouts are
mutually bit-identical, and every layout meets the planner's analytic
recall floor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.search import backends, hosttier
from repro.search.stages import MASK_VALUE

# (A, B) mesh factorization per grid size: A shards the query batch
# ("data"), B — or the (A, B) tuple — shards the database ("model").
_MESHES = {8: (2, 4), 16: (2, 8), 48: (6, 8)}

_GRID_CHILD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.search import Index, backends

A, B = @A@, @B@
NDEV = A * B
N, D, M, K = 4999, 32, 24, 7
RT = 0.999  # high-recall regime: bin layouts align -> exact parity

rng = np.random.default_rng(7)
db = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
q = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
mesh1 = make_mesh((NDEV,), ("model",))
mesh2 = make_mesh((A, B), ("data", "model"))

CONFIGS = [("mips", "f32"), ("l2", "f32"), ("cosine", "f32"),
           ("l2", "bf16"), ("mips", "int8"), ("cosine", "int8")]
report = {}
for metric, storage in CONFIGS:
    oracle = Index.build(db, metric=metric, k=K, backend="xla",
                         recall_target=RT, storage=storage, cluster="off")
    _, oi0 = oracle.search(q)
    dead = np.unique(np.asarray(oi0)[:, 0])
    oracle.delete(dead)  # tombstones: each query loses its best row
    ov, oi = oracle.search(q)
    ov, oi = np.asarray(ov), np.asarray(oi)
    assert oi.max() < N, "oracle leaked a padded/tombstoned id"
    assert not set(oi.ravel().tolist()) & set(dead.tolist())

    layouts = {
        "sharded-1d": oracle.shard(mesh1, db_axis="model"),
        "sharded-2d": oracle.shard(mesh2, db_axis="model",
                                   batch_axis="data"),
        "sharded-2d-tuple": oracle.shard(mesh2, db_axis=("data", "model")),
    }
    # Host cold tier: built (not sharded) from the same rows, same
    # deletes; 2**18-byte budget forces the minimum 1024-row segment,
    # so N=4999 streams as 5 waves.
    host = Index.build(db, metric=metric, k=K, recall_target=RT,
                       storage=storage, cluster="off", residency="host",
                       hbm_budget_bytes=2 ** 18)
    host.delete(dead)
    waves = host.explain()["residency"]["num_segments"]
    assert waves >= 4, waves
    layouts["host"] = host

    for name, idx in layouts.items():
        before_sh = backends.DISPATCH_COUNTS["sharded"]
        before_host = backends.DISPATCH_COUNTS["host"]
        traces0 = backends.TRACE_COUNTS["host"]
        sv, si = idx.search(q)
        sv, si = np.asarray(sv), np.asarray(si)
        assert np.array_equal(ov, sv), (metric, storage, name, "values")
        assert np.array_equal(oi, si), (metric, storage, name, "indices")
        assert si.max() < N, (name, "padded-tail id leaked")
        if name == "host":
            assert backends.DISPATCH_COUNTS["host"] - before_host == waves
            # steady state: re-search retraces nothing
            traces1 = backends.TRACE_COUNTS["host"]
            idx.search(q)
            assert backends.TRACE_COUNTS["host"] == traces1, "host retrace"
        else:
            # one device dispatch per query batch, whatever the layout
            assert backends.DISPATCH_COUNTS["sharded"] - before_sh == 1
        report[(metric, storage, name)] = True
publish({"cases": report, "ndev": NDEV, "host_waves": waves})
"""

_CLUSTER_CHILD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.search import Index, exact_search

A, B = @A@, @B@
NDEV = A * B
N, D, M, K = 8199, 32, 24, 7

rng = np.random.default_rng(0)
centers = rng.normal(size=(64, D)) * 2.5
db = jnp.asarray(centers[rng.integers(0, 64, N)]
                 + rng.normal(size=(N, D)), jnp.float32)
q = jnp.asarray(centers[rng.integers(0, 64, M)]
                + rng.normal(size=(M, D)), jnp.float32)
mesh1 = make_mesh((NDEV,), ("model",))
mesh2 = make_mesh((A, B), ("data", "model"))

oracle = Index.build(db, metric="l2", k=K, backend="xla",
                     recall_target=0.95, cluster="auto")
assert oracle._cluster_plan_in_effect() is not None, "crossover not hit"
results = {
    "sharded-1d": oracle.shard(mesh1, db_axis="model").search(q),
    "sharded-2d": oracle.shard(mesh2, db_axis="model",
                               batch_axis="data").search(q),
    "sharded-2d-tuple":
        oracle.shard(mesh2, db_axis=("data", "model")).search(q),
}
# Equal shard counts => identical ownership partition => bit-identical.
a, b = results["sharded-1d"], results["sharded-2d-tuple"]
assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
# Every layout meets the analytic recall floor against the exact scan.
_, exact = exact_search(q, db, K, metric="l2")
floors = {}
for name, res in results.items():
    rec = float(np.mean(
        [len(set(r.tolist()) & set(t.tolist())) / K
         for r, t in zip(np.asarray(res.indices), np.asarray(exact))]
    ))
    assert rec >= oracle.expected_recall - 0.07, (name, rec)
    floors[name] = rec
publish({"recalls": floors, "expected": oracle.expected_recall})
"""


def _fill(template: str, n: int) -> str:
    a, b = _MESHES[n]
    return template.replace("@A@", str(a)).replace("@B@", str(b))


def test_layout_parity_grid(fake_devices, device_grid):
    """1-D, 2-D, 2-D-tuple and host-tiered searches return bit-identical
    (values, indices) — global user-space ids — to the replicated oracle,
    across metric x storage, with tombstoned and padded-tail rows."""
    res = fake_devices(_fill(_GRID_CHILD, device_grid), n=device_grid)
    assert res["ndev"] == device_grid
    assert res["host_waves"] >= 4
    assert len(res["cases"]) == 6 * 4 and all(res["cases"].values())


def test_clustered_layout_invariants(fake_devices, device_grid):
    """Cluster-pruned sharded layouts: equal shard counts bit-match each
    other; all meet the planner's recall floor (pruning is approximate,
    so cross-shard-count bit-parity is not a claim the design makes)."""
    res = fake_devices(_fill(_CLUSTER_CHILD, device_grid), n=device_grid)
    assert set(res["recalls"]) == {
        "sharded-1d", "sharded-2d", "sharded-2d-tuple"
    }


def test_wave_program_jaxpr_single_scan():
    """The host-tier wave program lowers to exactly one (M, seg) scan
    matmul per wave — the jaxpr half of the one-dispatch/zero-retrace
    steady-state contract (the counter half lives in the parity grid)."""
    m, seg, d, k = 8, 1024, 32, 5
    jaxpr = jax.make_jaxpr(
        lambda q, db, b, off, cv, ci: hosttier.wave_program(
            q, db, b, None, None, None, off, cv, ci,
            metric="l2", k=k, k_scan=k, recall_target=0.999,
            global_n=4 * seg, rescore=False, is_last=False,
            use_bitonic=False,
        )
    )(
        jnp.zeros((m, d)), jnp.zeros((seg, d)), jnp.zeros((seg,)),
        jnp.int32(0), jnp.full((m, k), MASK_VALUE), jnp.zeros((m, k),
                                                              jnp.int32),
    )
    def count_dots(jx):
        n = sum(e.primitive.name == "dot_general" for e in jx.eqns)
        for e in jx.eqns:
            for p in e.params.values():
                if hasattr(p, "jaxpr"):  # nested (pjit/closed-call) jaxprs
                    n += count_dots(p.jaxpr)
        return n

    dots = count_dots(jaxpr.jaxpr)
    assert dots == 1, f"expected 1 scan matmul, got {dots}"


def test_host_tier_occupancy_reports_live_fraction():
    """Segment-wave occupancy (benchmark observability): tombstoning a
    whole segment's rows drops that wave's live fraction to zero while
    the schedule shape — and thus the compiled program — is unchanged."""
    from repro.search import Index

    rng = np.random.default_rng(3)
    db = jnp.asarray(rng.normal(size=(2048, 16)), jnp.float32)
    idx = Index.build(db, metric="mips", k=3, residency="host",
                      segment_rows=1024)
    searcher = idx._build_host_searcher()
    occ = searcher.occupancy(idx.pack())
    assert occ == [1.0, 1.0]
    idx.delete(np.arange(1024))
    occ = searcher.occupancy(idx.pack())
    assert occ[0] == 0.0 and occ[1] == 1.0

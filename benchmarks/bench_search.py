"""Steady-state search benchmark: QPS + dispatch overhead, stream vs loop.

Measures what the packed-state PR changed — per-search host/HBM overhead —
across a backend x metric x (M, N, D) grid:

  * steady-state QPS of ``Index.search`` over pre-packed operands,
  * dispatches per search (``backends.DISPATCH_COUNTS``): the streaming
    executor issues ONE for a multi-block batch, the per-block Python loop
    (``SearchSpec(stream=False)``) issues M / query_block,
  * the stream-over-loop wall-clock speedup ("before/after" of the packed
    state PR),
  * model-planned vs legacy hard-coded tile configs (``plan_results``):
    the kernel planner (``repro.search.plan``) must match or beat the old
    (256, 1024, 4096) defaults at bit-identical results,
  * sharded scaling + the host cold tier (``shard_results``): QPS and the
    one-dispatch contract vs fake device count (one subprocess per count),
    and the host tier's segment-wave schedule with per-wave occupancy.

Writes ``BENCH_search.json`` (one run per invocation; history lives in git —
commit full-grid runs, CI smoke runs only touch the working tree).

  python benchmarks/bench_search.py                  # full grid
  python benchmarks/bench_search.py --smoke          # CI: one tiny config,
                                                     # asserts the dispatch
                                                     # contract + no big
                                                     # stream regression

CPU wall-clocks are machine-relative; the dispatch counts are exact
everywhere.  On CPU the dispatch overhead is a large fraction of a small
search, which is exactly why the smoke config can see the streaming win.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.search import Index, SearchSpec, backends, exact_search
from repro.search import telemetry
from repro.search import plan as planlib
from repro.search.packed import PACK_EVENTS

# The pre-planner hard-coded tile configuration (PR-2 and earlier): the
# baseline the model-planned path must match or beat.
LEGACY_BLOCKS = dict(block_m=256, max_block_n=1024, query_block=4096)

# (M, N, D) grid: M spans single-block through 16-block batches at the
# query_block below; N/D stay CPU-tractable while keeping the matmul real.
# The (4096, 2048, 32) entry is the dispatch-bound corner (16 small blocks)
# where the streaming executor's win is largest.
FULL_GRID = [
    (256, 4096, 64),
    (1024, 4096, 64),
    (2048, 16384, 64),
    (2048, 4096, 128),
    (4096, 2048, 32),
]
FULL_BACKENDS = ("xla", "pallas")
FULL_METRICS = ("mips", "l2", "cosine")
QUERY_BLOCK = 256

SMOKE_GRID = [(512, 2048, 32)]
SMOKE_QUERY_BLOCK = 32  # 512 queries = 16 blocks (criterion: M >= 4*qb)


def _time_search(index, queries, repeats, passes=3):
    """Best-of-``passes`` mean wall per search (min filters scheduler noise)."""
    index.search(queries).values.block_until_ready()  # warmup/compile
    best = float("inf")
    for _ in range(passes):
        backends.reset_dispatch_counts()
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = index.search(queries)
        out.values.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / repeats)
        dispatches = sum(backends.DISPATCH_COUNTS.values())
    return best, dispatches / repeats


def bench_config(backend, metric, m, n, d, query_block, repeats, emit):
    key = jax.random.PRNGKey(0)
    kq, kd = jax.random.split(key)
    db = jax.random.normal(kd, (n, d))
    queries = jax.random.normal(kq, (m, d))
    row = {
        "backend": backend, "metric": metric,
        "m": m, "n": n, "d": d, "query_block": query_block,
    }
    for mode, stream in (("stream", True), ("loop", False)):
        index = Index.build(
            db,
            spec=SearchSpec(
                metric=metric, k=10, backend=backend,
                query_block=query_block, stream=stream,
            ),
        )
        wall, dispatches = _time_search(index, queries, repeats)
        row[mode] = {
            "wall_s_per_search": wall,
            "qps": m / wall,
            "dispatches_per_search": dispatches,
        }
    row["stream_speedup"] = (
        row["loop"]["wall_s_per_search"] / row["stream"]["wall_s_per_search"]
    )
    emit(
        f"{backend},{metric},M={m},N={n},D={d}: "
        f"stream {row['stream']['qps']:.0f} qps "
        f"({row['stream']['dispatches_per_search']:.0f} dispatch) vs "
        f"loop {row['loop']['qps']:.0f} qps "
        f"({row['loop']['dispatches_per_search']:.0f} dispatches) "
        f"-> {row['stream_speedup']:.2f}x"
    )
    return row


def bench_plan(backend, metric, m, n, d, repeats, emit):
    """Model-planned tiles vs the pre-planner hard-coded defaults.

    Also asserts bit-parity: the planner may only change layout/padding,
    never results.
    """
    key = jax.random.PRNGKey(0)
    kq, kd = jax.random.split(key)
    db = jax.random.normal(kd, (n, d))
    queries = jax.random.normal(kq, (m, d))
    model = Index.build(db, spec=SearchSpec(metric=metric, k=10, backend=backend))
    legacy = Index.build(
        db, spec=SearchSpec(metric=metric, k=10, backend=backend, **LEGACY_BLOCKS)
    )
    vm, im = model.search(queries)
    vl, il = legacy.search(queries)
    assert (vm == vl).all() and (im == il).all(), (
        f"planner changed results for {backend}/{metric} M={m} N={n} D={d}"
    )
    wall_model, _ = _time_search(model, queries, repeats)
    wall_legacy, _ = _time_search(legacy, queries, repeats)
    plan = model.kernel_plan
    row = {
        "backend": backend, "metric": metric, "m": m, "n": n, "d": d,
        "planned": {
            "block_m": plan.block_m, "block_n": plan.block_n,
            "query_block": plan.query_block, "num_bins": plan.num_bins,
            "bin_size": plan.bin_size, "bottleneck": plan.bottleneck,
            "source": plan.source,
        },
        "model_qps": m / wall_model,
        "legacy_qps": m / wall_legacy,
        "model_over_legacy": wall_legacy / wall_model,
    }
    emit(
        f"plan,{backend},{metric},M={m},N={n},D={d}: "
        f"model {row['model_qps']:.0f} qps "
        f"(bm={plan.block_m},bn={plan.block_n},qb={plan.query_block}) vs "
        f"legacy {row['legacy_qps']:.0f} qps -> "
        f"{row['model_over_legacy']:.2f}x"
    )
    return row


def bench_quant(backend, metric, m, n, d, query_block, repeats, emit):
    """Quantized storage tiers vs f32 (repro.search.quant).

    Reports steady-state QPS per tier, empirical recall vs the f32 tier's
    results, the planner's predicted database HBM-traffic ratio, and the
    one-dispatch/zero-retrace/zero-repack contract counters on the
    quantized path.
    """
    key = jax.random.PRNGKey(0)
    kq, kd = jax.random.split(key)
    db = jax.random.normal(kd, (n, d))
    queries = jax.random.normal(kq, (m, d))
    base = Index.build(
        db,
        spec=SearchSpec(metric=metric, k=10, backend=backend,
                        query_block=query_block),
    )
    _, base_idx = base.search(queries)
    base_sets = [set(r.tolist()) for r in jax.device_get(base_idx)]
    row = {
        "backend": backend, "metric": metric,
        "m": m, "n": n, "d": d, "query_block": query_block, "tiers": {},
    }
    for storage in ("f32", "bf16", "int8", "int4"):
        index = Index.build(
            db,
            spec=SearchSpec(metric=metric, k=10, backend=backend,
                            query_block=query_block, storage=storage),
        )
        index.search(queries)  # warmup: trace + compile + pack
        telemetry.reset_all()  # one reset for every counter surface
        wall, dispatches = _time_search(index, queries, repeats)
        retraces = sum(backends.TRACE_COUNTS.values())
        packs = sum(PACK_EVENTS.values())
        _, idxs = index.search(queries)
        rec = sum(
            len(set(r.tolist()) & s) / 10
            for r, s in zip(jax.device_get(idxs), base_sets)
        ) / m
        # The planner's fused-kernel traffic model: what the tier buys on
        # the memory wall (Eq. 10/20) — pure math, device-independent.
        plan = planlib.plan_search(
            n=n, d=d, k=10, m=query_block, metric=metric,
            backend="pallas", device="tpu_v4", storage=storage,
        )
        row["tiers"][storage] = {
            "wall_s_per_search": wall,
            "qps": m / wall,
            "dispatches_per_search": dispatches,
            "steady_retraces": retraces,
            "steady_pack_events": packs,
            "recall_vs_f32": rec,
            "predicted_hbm_bytes": plan.hbm_bytes,
            "k_scan": plan.k_scan,
        }
        emit(
            f"quant,{backend},{metric},M={m},N={n},D={d},{storage}: "
            f"{m / wall:.0f} qps ({dispatches:.0f} dispatch, "
            f"{retraces} retrace, {packs} packs) recall@f32 {rec:.3f} "
            f"pred-HBM {plan.hbm_bytes / 1e6:.2f}MB"
        )
    f32_bytes = row["tiers"]["f32"]["predicted_hbm_bytes"]
    for storage in ("bf16", "int8", "int4"):
        row["tiers"][storage]["hbm_drop_vs_f32"] = (
            f32_bytes / row["tiers"][storage]["predicted_hbm_bytes"]
        )
    return row


def bench_fused(metric, m, n, d, query_block, repeats, emit):
    """Single-pass fused scan→select vs the two-pass oracle (pallas).

    The fused kernel's win is an HBM-traffic property (Eq. 20: the
    database streamed once plus O(M·k_scan) winners, no score-tile round
    trip), so the hard contracts live on the deterministic cost model at
    the TPU roofline.  Measured wall-clock on this host runs the kernel in
    interpret mode — where the in-kernel merge is Python-priced and the
    sign of the win is not meaningful — so it is reported, and only a
    gross regression fails.  Bit-parity fused vs two-pass is asserted
    unconditionally: the fusion may change traffic, never results.
    """
    from repro.core import roofline

    key = jax.random.PRNGKey(0)
    kq, kd = jax.random.split(key)
    db = jax.random.normal(kd, (n, d))
    queries = jax.random.normal(kq, (m, d))
    row = {"metric": metric, "m": m, "n": n, "d": d,
           "query_block": query_block, "storage": "int4", "modes": {}}
    outs = {}
    for mode, fused in (("fused", True), ("two_pass", False)):
        index = Index.build(
            db,
            spec=SearchSpec(metric=metric, k=10, backend="pallas",
                            query_block=query_block, storage="int4",
                            fused_select=fused),
        )
        outs[mode] = index.search(queries)  # warmup + parity sample
        telemetry.reset_all()  # one reset for every counter surface
        wall, dispatches = _time_search(index, queries, repeats)
        row["modes"][mode] = {
            "wall_s_per_search": wall,
            "qps": m / wall,
            "dispatches_per_search": dispatches,
            "steady_retraces": sum(backends.TRACE_COUNTS.values()),
            "steady_pack_events": sum(PACK_EVENTS.values()),
        }
    assert (outs["fused"].values == outs["two_pass"].values).all() and (
        outs["fused"].indices == outs["two_pass"].indices
    ).all(), f"fused/two-pass divergence on {metric} M={m} N={n} D={d}"

    # Eq. 20 traffic contract, priced at one query block (a one-pass
    # shape: query_block <= block_m, sublane-aligned).  f32 with no
    # rescore is EXACT: queries + db stream + 8-byte winners.  int4 adds
    # the exact-rescore tail, which must stay O(M·k_scan·D) — bounded
    # without any N term (the score-tile round trip the fusion deletes).
    pf = planlib.plan_search(n=n, d=d, k=10, m=query_block, metric=metric,
                             backend="pallas", device="tpu_v4")
    pi = planlib.plan_search(n=n, d=d, k=10, m=query_block, metric=metric,
                             backend="pallas", device="tpu_v4",
                             storage="int4")
    qb = query_block
    row["f32_predicted_hbm_bytes"] = pf.hbm_bytes
    row["f32_expected_hbm_bytes"] = (
        4 * qb * pf.d_pad + 4.0 * pf.padded_n * pf.d_pad + 8 * qb * pf.k_scan
    )
    scan4 = (
        4 * qb * pi.d_pad + 0.5 * pi.padded_n * pi.d_pad + 8 * qb * pi.k_scan
    )
    row["int4_predicted_hbm_bytes"] = pi.hbm_bytes
    row["int4_scan_hbm_bytes"] = scan4
    row["int4_rescore_tail_bound"] = 4.0 * qb * pi.k_scan * pi.d_pad
    # Model-level "fused >= two-pass QPS": same FLOPs, strictly less HBM
    # than the two-pass kernel (Eq. 10 re-reads its winner tiles), so the
    # attainable-FLOP/s knee can only move up.
    hw = roofline.HARDWARE["tpu_v4"]
    two = roofline.partial_reduce_cost(
        qb, pi.padded_n, pi.d_pad, pi.num_bins,
        block_rows=pi.block_m, db_bytes=0.5,
    )
    row["two_pass_model_hbm_bytes"] = two.hbm_bytes
    row["two_pass_model_attainable_flops"] = roofline.attainable_flops(
        two, hw
    )
    row["fused_model_attainable_flops"] = pi.attainable_flops
    emit(
        f"fused,{metric},M={m},N={n},D={d},int4: "
        f"fused {row['modes']['fused']['qps']:.0f} qps vs two-pass "
        f"{row['modes']['two_pass']['qps']:.0f} qps (interpret mode); "
        f"model HBM fused {pi.hbm_bytes / 1e3:.0f}KB vs two-pass "
        f"{two.hbm_bytes / 1e3:.0f}KB"
    )
    return row


# Cluster-pruned front-end config: N must sit well above the planner's
# crossover, and the corpus must be CLUSTERABLE (mixture of Gaussians,
# queries from the same component centers) — on i.i.d. Gaussian data no
# coarse quantizer can prune without large misses, so benchmarking the
# pruned path there would measure the wrong regime.  recall is measured
# against the exact baseline, not the dense approximate path.
CLUSTER_M, CLUSTER_N, CLUSTER_D = 256, 32768, 32
CLUSTER_TARGET = 0.90
CLUSTER_COMPONENTS = 64


def _mixture_corpus(m, n, d, seed=7):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(CLUSTER_COMPONENTS, d)) * 3.0
    db = centers[rng.integers(0, CLUSTER_COMPONENTS, n)] \
        + rng.normal(size=(n, d))
    q = centers[rng.integers(0, CLUSTER_COMPONENTS, m)] \
        + rng.normal(size=(m, d))
    return jnp.asarray(db, jnp.float32), jnp.asarray(q, jnp.float32)


def bench_cluster(backend, metric, m, n, d, query_block, repeats, emit):
    """Cluster-pruned scan vs the dense scan at large N.

    Reports steady-state QPS for ``cluster="auto"`` (planner-enabled
    pruning) vs ``cluster="off"``, measured recall of BOTH against the
    exact baseline, the scanned-row fraction, and the one-dispatch /
    zero-retrace contract counters on the clustered path.
    """
    db, queries = _mixture_corpus(m, n, d)
    _, exact_idx = exact_search(queries, db, 10, metric=metric)
    exact_sets = [set(r.tolist()) for r in jax.device_get(exact_idx)]
    row = {
        "backend": backend, "metric": metric,
        "m": m, "n": n, "d": d, "query_block": query_block,
        "recall_target": CLUSTER_TARGET, "modes": {},
    }
    for mode in ("auto", "off"):
        index = Index.build(
            db,
            spec=SearchSpec(metric=metric, k=10, backend=backend,
                            recall_target=CLUSTER_TARGET,
                            query_block=query_block, cluster=mode),
        )
        _, idxs = index.search(queries)  # warmup + recall sample
        rec = sum(
            len(set(r.tolist()) & s) / 10
            for r, s in zip(jax.device_get(idxs), exact_sets)
        ) / m
        telemetry.reset_all()  # one reset for every counter surface
        wall, dispatches = _time_search(index, queries, repeats)
        cplan = index.pack().cluster.plan if mode == "auto" \
            and index.pack().cluster is not None else None
        row["modes"][mode] = {
            "wall_s_per_search": wall,
            "qps": m / wall,
            "dispatches_per_search": dispatches,
            "steady_retraces": sum(backends.TRACE_COUNTS.values()),
            "steady_pack_events": sum(PACK_EVENTS.values()),
            "recall_vs_exact": rec,
            "cluster_enabled": cplan is not None,
            "scanned_fraction": cplan.scanned_fraction if cplan else 1.0,
        }
        emit(
            f"cluster,{backend},{metric},M={m},N={n},D={d},{mode}: "
            f"{m / wall:.0f} qps ({dispatches:.0f} dispatch) "
            f"recall {rec:.3f} scanned "
            f"{row['modes'][mode]['scanned_fraction']:.3f}"
        )
    row["cluster_speedup"] = (
        row["modes"]["off"]["wall_s_per_search"]
        / row["modes"]["auto"]["wall_s_per_search"]
    )
    emit(f"cluster,{backend},{metric}: pruned scan "
         f"{row['cluster_speedup']:.2f}x the dense scan")
    return row


# Child script for the device-count scaling sweep.  Fake devices only exist
# per-process (XLA_FLAGS is read at jax import), so each device count is one
# subprocess; the result rides back on a marked JSON stdout line.  @NAME@
# placeholders avoid brace-escaping an f-string template.
_SHARD_CHILD = """\
import json, time
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.search import Index, SearchSpec, backends

NDEV, M, N, D, REPEATS = @NDEV@, @M@, @N@, @D@, @REPEATS@
rng = np.random.default_rng(0)
db = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
q = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
index = Index.build(db, metric="mips", k=10, backend="xla",
                    recall_target=0.95)
if NDEV > 1:
    mesh = make_mesh((NDEV,), ("model",))
    index = index.shard(mesh, db_axis="model")
index.search(q).values.block_until_ready()  # warmup/compile
backends.reset_dispatch_counts()
t0 = time.perf_counter()
for _ in range(REPEATS):
    out = index.search(q)
out.values.block_until_ready()
wall = (time.perf_counter() - t0) / REPEATS
print("@@SHARD@@" + json.dumps({
    "devices": NDEV,
    "platform": jax.devices()[0].platform,
    "backend": "sharded" if NDEV > 1 else "xla",
    "qps": M / wall,
    "wall_s_per_search": wall,
    "dispatches_per_search": sum(backends.DISPATCH_COUNTS.values()) / REPEATS,
    "dispatch_counts": dict(backends.DISPATCH_COUNTS),
}))
"""


def bench_shard(m, n, d, device_counts, repeats, emit):
    """Device-count scaling of the sharded backend + host-tier waves.

    QPS and the one-dispatch-per-batch contract vs fake device count
    (each count is a CPU subprocess — XLA fixes the device count at
    import; its rows carry ``platform: "cpu"``),
    plus the host-RAM cold tier's segment-wave schedule and per-wave
    live-row occupancy on the default single device.
    """
    src_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "src")
    rows = []
    for ndev in device_counts:
        child = _SHARD_CHILD
        for name, val in (("NDEV", ndev), ("M", m), ("N", n), ("D", d),
                          ("REPEATS", repeats)):
            child = child.replace(f"@{name}@", str(val))
        env = dict(os.environ)
        # The children simulate devices on the host CPU, never the
        # accelerator: the parent already holds it.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev}"
        )
        env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", child], env=env,
                              capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"shard bench child (devices={ndev}) failed:\n"
                f"{proc.stdout}\n{proc.stderr}"
            )
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("@@SHARD@@"))
        row = json.loads(line[len("@@SHARD@@"):])
        rows.append(row)
        emit(f"shard,M={m},N={n},D={d},{row['platform']} devices={ndev}: "
             f"{row['qps']:.0f} qps "
             f"({row['dispatches_per_search']:.0f} dispatch)")

    # Host-RAM cold tier: budget sized for 1024-row segments so the build
    # streams N/1024 waves; occupancy is the per-wave live-row fraction.
    rng = np.random.default_rng(0)
    hn = max(4096, n)
    db = jnp.asarray(rng.normal(size=(hn, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    index = Index.build(db, metric="mips", k=10, residency="host",
                        hbm_budget_bytes=2 * 1024 * d * 4)
    index.search(q).values.block_until_ready()  # warmup/compile
    backends.reset_dispatch_counts()
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = index.search(q)
    out.values.block_until_ready()
    wall = (time.perf_counter() - t0) / repeats
    searcher = index._build_host_searcher()
    occupancy = searcher.occupancy(index.pack())
    host = {
        "n": hn, "d": d, "m": m,
        "segment_rows": searcher.segment_rows,
        "num_segments": len(occupancy),
        "occupancy": occupancy,
        "qps": m / wall,
        "wall_s_per_search": wall,
        "dispatches_per_search":
            backends.DISPATCH_COUNTS["host"] / repeats,
    }
    emit(f"shard,host-tier,N={hn},D={d}: {host['qps']:.0f} qps over "
         f"{host['num_segments']} waves of {host['segment_rows']} rows")
    return {"m": m, "n": n, "d": d, "devices": rows, "host_tier": host}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--out", default="BENCH_search.json")
    ap.add_argument("--repeats", type=int, default=0, help="0 = auto")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        grid, bks, mets, qb = SMOKE_GRID, ("xla",), ("mips",), SMOKE_QUERY_BLOCK
        repeats = args.repeats or 20
    else:
        grid, bks, mets, qb = FULL_GRID, FULL_BACKENDS, FULL_METRICS, QUERY_BLOCK
        repeats = args.repeats or 10

    results = []
    for backend in bks:
        for metric in mets:
            for m, n, d in grid:
                results.append(
                    bench_config(backend, metric, m, n, d, qb, repeats, print)
                )

    plan_results = []
    for backend in bks:
        for metric in mets:
            for m, n, d in grid:
                plan_results.append(
                    bench_plan(backend, metric, m, n, d, repeats, print)
                )

    quant_results = []
    # One shape per backend — the tiers are the axis, not the sizes.  Use
    # the most database-traffic-heavy grid entry: the storage tiers exist
    # for the Eq. 10 regime where streaming (N, D) dominates; at tiny N·D
    # the over-fetched winner/rescore terms (both O(M)) mask the win.
    qm, qn, qd = max(grid, key=lambda s: s[1] * s[2])
    for backend in bks:
        quant_results.append(
            bench_quant(backend, mets[0], qm, qn, qd, qb, repeats, print)
        )

    # Fused-vs-two-pass section: pallas-only by construction (the fusion
    # is a Pallas kernel property), one shape — interpret mode on CPU
    # makes the measured side expensive, and the hard contracts are on
    # the cost model anyway.
    fm, fn, fd = grid[0]
    fused_results = [
        bench_fused(mets[0], min(fm, 512), fn, fd, qb, min(repeats, 5),
                    print)
    ]

    cluster_results = []
    # One clustered config per backend: the cluster N is its own (large)
    # size — pruning only exists above the planner crossover, which every
    # grid entry above sits below or near.
    for backend in bks:
        cluster_results.append(
            bench_cluster(backend, "l2", CLUSTER_M, CLUSTER_N, CLUSTER_D,
                          qb if qb >= 256 else 256, repeats, print)
        )

    # Device-count scaling (subprocess per count — fake devices are fixed
    # at jax import) + the host cold tier.  Smoke keeps to [1, 2] so the
    # fast tier pays for two interpreter startups, not four.
    shard_devices = (1, 2) if args.smoke else (1, 2, 4, 8)
    sm, sn, sd = grid[0]
    shard_results = bench_shard(sm, sn, sd, shard_devices, repeats, print)

    report = {
        "meta": {
            "jax": jax.__version__,
            "device": jax.default_backend(),
            "platform": platform.platform(),
            "repeats": repeats,
            "smoke": args.smoke,
        },
        "telemetry": telemetry.export_json(),
        "results": results,
        "plan_results": plan_results,
        "quant_results": quant_results,
        "fused_results": fused_results,
        "cluster_results": cluster_results,
        "shard_results": shard_results,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out} ({len(results)} configs)")

    if args.smoke:
        # The hard perf contract (deterministic): one dispatch per streamed
        # batch, M/qb for the loop.  Wall-clock is noisy in CI, so only a
        # gross streaming regression fails.
        r = results[0]
        assert r["stream"]["dispatches_per_search"] == 1, r["stream"]
        assert r["loop"]["dispatches_per_search"] == r["m"] / r["query_block"]
        # Wall-clock gets slack for noisy CI machines (the config above
        # measures ~1.7x locally); only a gross regression fails.
        assert r["stream_speedup"] > 0.8, (
            f"streaming executor only {r['stream_speedup']:.2f}x the "
            "per-block loop — dispatch overhead regression"
        )
        # Planner contract: model-planned tiles match or beat the old
        # hard-coded defaults on every smoke config (bit-parity is asserted
        # inside bench_plan).  Wall-clock slack for CI noise only.
        for p in plan_results:
            assert p["model_over_legacy"] > 0.8, (
                f"model-planned config {p['planned']} is "
                f"{p['model_over_legacy']:.2f}x the legacy default "
                f"on {p['backend']}/{p['metric']} — planner regression"
            )
        # Quantized-tier contracts (deterministic): the planner's predicted
        # database HBM traffic must drop >=2x on the fused-kernel model,
        # and the quantized steady state must keep the one-dispatch /
        # zero-retrace / zero-repack contract of the f32 path.
        for qrow in quant_results:
            tiers = qrow["tiers"]
            assert tiers["int8"]["hbm_drop_vs_f32"] >= 2.0, (
                f"int8 predicted HBM bytes only "
                f"{tiers['int8']['hbm_drop_vs_f32']:.2f}x below f32"
            )
            assert tiers["bf16"]["hbm_drop_vs_f32"] >= 1.5, tiers["bf16"]
            assert tiers["int4"]["hbm_drop_vs_f32"] >= 3.0, (
                f"int4 predicted HBM bytes only "
                f"{tiers['int4']['hbm_drop_vs_f32']:.2f}x below f32"
            )
            for storage in ("bf16", "int8", "int4"):
                t = tiers[storage]
                assert t["dispatches_per_search"] == 1, (storage, t)
                assert t["steady_retraces"] == 0, (storage, t)
                assert t["steady_pack_events"] == 0, (storage, t)
                # int4's wider codes get a laxer floor (T(int4)=2K
                # over-fetch + exact rescore still lands ~0.98 here).
                floor = 0.85 if storage == "int4" else 0.9
                assert t["recall_vs_f32"] >= floor, (storage, t)
        # Fused-kernel contracts (deterministic).  Bit-parity fused vs
        # two-pass was asserted inside bench_fused; here: the Eq. 20
        # traffic model is EXACTLY db-bytes + queries + O(M·k) winners
        # (f32), the quantized tiers add only an O(M·k_scan·D) rescore
        # tail (no N term), the TPU-roofline model puts fused at or above
        # two-pass QPS, and the fused int4 steady state keeps the
        # one-dispatch / zero-retrace / zero-repack contract.
        for frow in fused_results:
            assert (
                frow["f32_predicted_hbm_bytes"]
                == frow["f32_expected_hbm_bytes"]
            ), frow
            tail = (frow["int4_predicted_hbm_bytes"]
                    - frow["int4_scan_hbm_bytes"])
            assert 0 < tail <= frow["int4_rescore_tail_bound"], frow
            assert (frow["int4_predicted_hbm_bytes"]
                    < frow["two_pass_model_hbm_bytes"]), frow
            assert (frow["fused_model_attainable_flops"]
                    >= frow["two_pass_model_attainable_flops"]), frow
            for mode in ("fused", "two_pass"):
                fmode = frow["modes"][mode]
                assert fmode["dispatches_per_search"] == 1, (mode, fmode)
                assert fmode["steady_retraces"] == 0, (mode, fmode)
                assert fmode["steady_pack_events"] == 0, (mode, fmode)
            # interpret mode inverts the perf sign (the merge runs as
            # Python per grid step) — only a gross regression fails.
            assert (frow["modes"]["fused"]["qps"]
                    > 0.2 * frow["modes"]["two_pass"]["qps"]), frow
        # Cluster-pruned front-end contracts: at the large-N config the
        # pruned scan must be a real speedup (>=1.5x, with headroom: the
        # config above measures >=2x locally) while HOLDING the recall
        # target against the exact baseline, scanning a small fraction of
        # the rows, and keeping the one-dispatch / zero-retrace /
        # zero-repack steady-state contract.
        for crow in cluster_results:
            auto, off = crow["modes"]["auto"], crow["modes"]["off"]
            assert auto["cluster_enabled"], crow
            assert not off["cluster_enabled"], crow
            assert crow["cluster_speedup"] >= 1.5, (
                f"pruned scan only {crow['cluster_speedup']:.2f}x the "
                f"dense scan at N={crow['n']} — cluster perf regression"
            )
            assert auto["recall_vs_exact"] >= crow["recall_target"], (
                f"pruned recall {auto['recall_vs_exact']:.3f} below the "
                f"{crow['recall_target']} target — miss/collision "
                "guarantee regression"
            )
            assert auto["scanned_fraction"] < 0.25, auto
            assert auto["dispatches_per_search"] == 1, auto
            assert auto["steady_retraces"] == 0, auto
            assert auto["steady_pack_events"] == 0, auto
        # Sharded + host-tier contracts (deterministic): every device count
        # keeps the one-dispatch-per-batch contract (the top-k merge is part
        # of the same compiled program, not extra dispatches), and the host
        # tier dispatches exactly one wave per segment with fully-live
        # occupancy on a fresh build.
        for srow in shard_results["devices"]:
            assert srow["dispatches_per_search"] == 1, srow
        host = shard_results["host_tier"]
        assert host["num_segments"] >= 2, host
        assert host["dispatches_per_search"] == host["num_segments"], host
        assert all(o == 1.0 for o in host["occupancy"]), host
        print("smoke contract OK")


if __name__ == "__main__":
    main()

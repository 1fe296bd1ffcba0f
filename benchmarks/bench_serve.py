"""Concurrent serving benchmark: QPS + latency percentiles under load.

Measures what the SearchServer PR changed — many small concurrent requests
served at coalesced-batch efficiency instead of one dispatch each:

  * **closed-loop**: C concurrent clients, each submitting an m-row
    request and waiting for its result before the next (the classic
    latency-vs-concurrency curve) — wall-clock QPS, p50/p99 latency,
    batching occupancy, dispatches per request;
  * **poisson**: open-loop arrivals at a target rate (independent of
    completions, so queueing shows up honestly) — same metrics plus the
    achieved rate;
  * **coalesce-vs-direct**: R requests totalling B rows pushed through the
    server (virtual clock, zero sleeps) against one pre-formed (B, D)
    ``Index.search`` — the serving overhead everything above pays.
  * **fault-rate axis**: the closed-loop load repeated at 0 / 1% / 5%
    injected transient dispatch faults (seeded ``FaultInjector``) —
    goodput (successfully served rows/s), p50/p99 of *successful*
    requests, retry/failure counters: what the retry-with-backoff layer
    costs and saves under an unreliable dispatch path;
  * **snapshot**: ``Index.save`` / ``Index.restore`` wall time for the
    benchmark index (``time_to_restore_s`` is the cold-replica recovery
    story), with bit-parity asserted against the live index.
  * **telemetry**: a C=8 closed loop against a fresh metrics registry —
    Prometheus series count, trace-span coverage of measured latency,
    exported-histogram vs bench-measured p50/p99 agreement, roofline
    drift at fault rate 0, and the tracing-on vs tracing-off overhead
    (interleaved min-wall, same idiom as coalesce-vs-direct).

Writes ``BENCH_serve.json`` (commit full runs; CI smoke runs write to an
untracked path, exactly like ``bench_search.py``).

  python benchmarks/bench_serve.py                    # full load grid
  python benchmarks/bench_serve.py --smoke            # CI: asserts ONE
                                                      # dispatch per micro-
                                                      # batch, bit-identical
                                                      # scatter, and no gross
                                                      # coalescing overhead

Wall-clock numbers are machine-relative; the dispatch/batch counts and the
parity checks are exact everywhere.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import shutil
import tempfile
import threading
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.search import Index, SearchSpec, SearchServer, ServeConfig, backends
from repro.search import telemetry
from repro.search.faults import FaultInjector, InjectedFault
from repro.search.serve import VirtualClock

N, D, K = 4096, 64, 10
MAX_BATCH = 64

CLOSED_LOOP_CLIENTS = (1, 4, 16)
POISSON_RATES = (200.0, 1000.0)
REQUEST_ROWS = 4


def _build_index(backend="xla", metric="mips"):
    db = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    return Index.build(db, metric=metric, k=K, backend=backend)


def _percentiles(latencies):
    lat = np.asarray(sorted(latencies))
    return {
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p90_ms": float(np.percentile(lat, 90) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "mean_ms": float(lat.mean() * 1e3),
    }


def _batch_stats(server, requests):
    s = server.stats()
    return {
        "batches": s["batches"],
        "dispatches_per_request": s["batches"] / max(1, requests),
        "occupancy": round(s["occupancy"], 4),
        "oversize_batches": s["oversize_batches"],
        "peak_pending_rows": s["peak_pending_rows"],
    }


def bench_closed_loop(index, clients, requests_per_client, emit):
    """C clients, each: submit -> wait -> repeat.  Wall clock, real worker."""
    server = SearchServer(
        index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.001),
        warmup=True,
    )
    queries = [
        np.asarray(jax.random.normal(jax.random.PRNGKey(100 + c),
                                     (REQUEST_ROWS, D)))
        for c in range(clients)
    ]
    latencies, errors = [], []

    def client(cid):
        try:
            mine = []
            for _ in range(requests_per_client):
                t = server.submit(queries[cid])
                t.result(timeout=120)
                mine.append(t.latency_s)
            latencies.extend(mine)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,)) for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    total = clients * requests_per_client
    row = {
        "mode": "closed_loop",
        "clients": clients,
        "requests": total,
        "request_rows": REQUEST_ROWS,
        "wall_s": wall,
        "qps": total * REQUEST_ROWS / wall,
        "rps": total / wall,
        **_percentiles(latencies),
        **_batch_stats(server, total),
    }
    server.close()
    emit(
        f"closed-loop C={clients}: {row['rps']:.0f} req/s "
        f"({row['qps']:.0f} qps), p50 {row['p50_ms']:.2f}ms "
        f"p99 {row['p99_ms']:.2f}ms, "
        f"{row['dispatches_per_request']:.2f} dispatches/req, "
        f"occupancy {row['occupancy']:.2f}"
    )
    return row


def bench_poisson(index, rate_rps, duration_s, emit, seed=0):
    """Open-loop Poisson arrivals at ``rate_rps`` requests/second."""
    server = SearchServer(
        index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.001,
                           max_pending_rows=65536),
        warmup=True,
    )
    rng = np.random.default_rng(seed)
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (REQUEST_ROWS, D)))
    tickets = []
    t0 = time.perf_counter()
    next_at = t0
    while True:
        now = time.perf_counter()
        if now - t0 >= duration_s:
            break
        if now < next_at:
            time.sleep(next_at - now)
        tickets.append(server.submit(q))
        next_at += float(rng.exponential(1.0 / rate_rps))
    results = [t.result(timeout=120) for t in tickets]
    wall = time.perf_counter() - t0
    assert len(results) == len(tickets)
    row = {
        "mode": "poisson",
        "offered_rps": rate_rps,
        "achieved_rps": len(tickets) / wall,
        "requests": len(tickets),
        "request_rows": REQUEST_ROWS,
        "wall_s": wall,
        "qps": len(tickets) * REQUEST_ROWS / wall,
        **_percentiles([t.latency_s for t in tickets]),
        **_batch_stats(server, len(tickets)),
    }
    server.close()
    emit(
        f"poisson {rate_rps:.0f} req/s offered: {row['achieved_rps']:.0f} "
        f"achieved, p50 {row['p50_ms']:.2f}ms p99 {row['p99_ms']:.2f}ms, "
        f"{row['dispatches_per_request']:.2f} dispatches/req, "
        f"occupancy {row['occupancy']:.2f}"
    )
    return row


def bench_coalesce_vs_direct(index, total_rows, request_rows, repeats, emit):
    """Server-coalesced batch of B rows vs one pre-formed Index.search(B).

    Virtual clock — zero sleeps, so the wall-clock difference IS the
    serving overhead (submit/stage/scatter bookkeeping).  Also asserts the
    two hard contracts: exactly one device dispatch per micro-batch, and
    bit-identical per-request results.
    """
    n_requests = total_rows // request_rows
    queries = np.asarray(
        jax.random.normal(jax.random.PRNGKey(3), (total_rows, D))
    )
    parts = [
        queries[i * request_rows : (i + 1) * request_rows]
        for i in range(n_requests)
    ]
    server = SearchServer(
        index,
        ServeConfig(max_batch=total_rows, max_pending_rows=4 * total_rows),
        clock=VirtualClock(),
        warmup=True,
    )

    # contract pass (outside timing): one dispatch, bit-identical scatter
    backends.reset_dispatch_counts()
    tickets = [server.submit(p) for p in parts]
    server.run_until_idle()
    dispatches = sum(backends.DISPATCH_COUNTS.values())
    batches = server.stats()["batches"]
    direct = index.search(queries)
    dv, di = np.asarray(direct.values), np.asarray(direct.indices)
    for i, t in enumerate(tickets):
        vals, idxs = t.result()
        lo = i * request_rows
        np.testing.assert_array_equal(
            np.asarray(idxs), di[lo : lo + request_rows]
        )
        np.testing.assert_array_equal(
            np.asarray(vals), dv[lo : lo + request_rows]
        )

    def pass_server():
        t0 = time.perf_counter()
        for _ in range(repeats):
            ts = [server.submit(p) for p in parts]
            server.run_until_idle()
        assert ts[-1].done  # results are host-side after the drain
        return (time.perf_counter() - t0) / repeats

    def pass_direct():
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = index.search(queries)
        out.values.block_until_ready()
        return (time.perf_counter() - t0) / repeats

    def pass_per_request():
        # What serving WITHOUT the coalescing layer looks like: one
        # dispatch per request (the shape the paper's batch-efficiency
        # claim says must lose).
        t0 = time.perf_counter()
        for _ in range(repeats):
            outs = [index.search(p) for p in parts]
        outs[-1].values.block_until_ready()
        return (time.perf_counter() - t0) / repeats

    # warmups, then best-of-4 with the three modes INTERLEAVED per pass —
    # machine noise (CI neighbours, thermal) then biases every mode alike
    # instead of whichever mode happened to run during the spike.
    index.search(queries).values.block_until_ready()
    index.search(parts[0]).values.block_until_ready()
    wall_server = wall_direct = wall_per_request = float("inf")
    for _ in range(4):
        wall_server = min(wall_server, pass_server())
        wall_direct = min(wall_direct, pass_direct())
        wall_per_request = min(wall_per_request, pass_per_request())
    row = {
        "mode": "coalesce_vs_direct",
        "total_rows": total_rows,
        "request_rows": request_rows,
        "requests": n_requests,
        "dispatches_per_micro_batch": dispatches / max(1, batches),
        "server_wall_s": wall_server,
        "direct_wall_s": wall_direct,
        "per_request_wall_s": wall_per_request,
        "server_qps": total_rows / wall_server,
        "direct_qps": total_rows / wall_direct,
        "per_request_qps": total_rows / wall_per_request,
        "server_over_direct": wall_direct / wall_server,
        "server_over_per_request": wall_per_request / wall_server,
    }
    server.close()
    emit(
        f"coalesce-vs-direct B={total_rows} ({n_requests} x {request_rows} "
        f"rows): server {row['server_qps']:.0f} qps vs pre-formed batch "
        f"{row['direct_qps']:.0f} qps -> {row['server_over_direct']:.2f}x; "
        f"vs per-request dispatch {row['per_request_qps']:.0f} qps -> "
        f"{row['server_over_per_request']:.2f}x; "
        f"{row['dispatches_per_micro_batch']:.0f} dispatch/micro-batch"
    )
    return row, dispatches, batches


FAULT_RATES = (0.0, 0.01, 0.05)


def bench_fault_rate(index, fault_rate, clients, requests_per_client, emit,
                     seed=11):
    """Closed-loop load with seeded transient dispatch faults injected.

    The retry loop absorbs most faults (bounded retries + backoff); the
    rest fail their batch with the typed error.  Goodput counts only the
    rows of requests that actually returned results, and the latency
    percentiles are over successful requests — so this row answers the
    operator question directly: what does an x% flaky dispatch path do to
    delivered throughput and tail latency?
    """
    inj = FaultInjector(seed=seed, rates={"serve.dispatch": fault_rate})
    server = SearchServer(
        index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.001),
        warmup=True, faults=inj,
    )
    queries = [
        np.asarray(jax.random.normal(jax.random.PRNGKey(300 + c),
                                     (REQUEST_ROWS, D)))
        for c in range(clients)
    ]
    latencies, failures, errors = [], [], []

    def client(cid):
        try:
            for _ in range(requests_per_client):
                t = server.submit(queries[cid])
                try:
                    t.result(timeout=120)
                except InjectedFault:
                    failures.append(t)  # typed taxonomy: expected under load
                else:
                    latencies.append(t.latency_s)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    total = clients * requests_per_client
    s = server.stats()
    row = {
        "mode": "fault_rate",
        "fault_rate": fault_rate,
        "clients": clients,
        "requests": total,
        "request_rows": REQUEST_ROWS,
        "ok_requests": len(latencies),
        "failed_requests": len(failures),
        "wall_s": wall,
        "goodput_qps": len(latencies) * REQUEST_ROWS / wall,
        "transient_faults": s["transient_faults"],
        "dispatch_retries": s["dispatch_retries"],
        "failed_batches": s["failed_batches"],
        **_percentiles(latencies),
    }
    server.close()
    emit(
        f"fault-rate {fault_rate:.0%}: {row['goodput_qps']:.0f} qps goodput "
        f"({row['ok_requests']}/{total} ok), p50 {row['p50_ms']:.2f}ms "
        f"p99 {row['p99_ms']:.2f}ms, {row['dispatch_retries']} retries, "
        f"{row['failed_batches']} failed batches"
    )
    return row


def bench_snapshot(index, emit, repeats=3):
    """Crash-safe snapshot round-trip: save + restore wall time, with
    restored-replica bit-parity asserted (the recovery-correctness half
    of the ``time_to_restore_s`` story)."""
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (64, D)))
    direct = index.search(q)
    tmp = tempfile.mkdtemp(prefix="bench_snap_")
    path = os.path.join(tmp, "snap")
    try:
        save_s = restore_s = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            index.save(path)
            save_s = min(save_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            restored = Index.restore(path)
            out = restored.search(q)
            out.values.block_until_ready()  # restored replica is HOT here
            restore_s = min(restore_s, time.perf_counter() - t0)
        np.testing.assert_array_equal(
            np.asarray(out.indices), np.asarray(direct.indices)
        )
        np.testing.assert_array_equal(
            np.asarray(out.values), np.asarray(direct.values)
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    row = {
        "mode": "snapshot",
        "rows": int(index.size),
        "save_s": save_s,
        "time_to_restore_s": restore_s,  # load + repack-free first search
    }
    emit(
        f"snapshot: save {save_s * 1e3:.1f}ms, restore-to-first-result "
        f"{restore_s * 1e3:.1f}ms ({row['rows']} rows, bit-identical)"
    )
    return row


def _drive_closed_loop(server, clients, requests_per_client, seed=500):
    """Thread-per-client closed loop against a live server; returns
    ``(wall_s, latencies)``."""
    queries = [
        np.asarray(jax.random.normal(jax.random.PRNGKey(seed + c),
                                     (REQUEST_ROWS, D)))
        for c in range(clients)
    ]
    latencies, errors = [], []

    def client(cid):
        try:
            mine = []
            for _ in range(requests_per_client):
                t = server.submit(queries[cid])
                t.result(timeout=120)
                mine.append(t.latency_s)
            latencies.extend(mine)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return wall, latencies


def bench_telemetry(index, emit, clients=8, requests_per_client=10,
                    repeats=3):
    """Telemetry contract under a C=8 closed loop, plus tracing overhead.

    One traced run against a fresh registry answers the acceptance
    questions directly: how many Prometheus series a serving workload
    exports, what fraction of each request's measured latency its trace
    spans cover, whether the exported latency histogram agrees with the
    bench's own percentiles (they observe the very same ``latency_s``
    values), and whether the roofline-drift monitor sits inside its band
    at fault rate 0.  Tracing overhead is then measured the same way
    ``bench_coalesce_vs_direct`` measures serving overhead: interleaved
    best-of-N min-wall passes with ``trace_buffer`` at its default vs 0
    (tracing disabled).
    """
    total = clients * requests_per_client
    telemetry.reset_all()
    server = SearchServer(
        index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.001,
                           trace_buffer=max(256, total)),
        warmup=True,
    )
    wall, latencies = _drive_closed_loop(server, clients, requests_per_client)
    health = server.health()
    traces = server.traces()
    coverage = telemetry.trace_coverage(traces)
    chrome = telemetry.chrome_trace(traces)
    index.telemetry()  # fold the index gauges into the export
    prom = telemetry.export_prometheus()
    series = [ln for ln in prom.splitlines() if ln and not ln.startswith("#")]
    snap = telemetry.registry().histogram_snapshot(
        "repro_serve_request_latency_seconds"
    )
    server.close()

    measured = _percentiles(latencies)
    row = {
        "mode": "telemetry",
        "clients": clients,
        "requests": total,
        "request_rows": REQUEST_ROWS,
        "wall_s": wall,
        "qps": total * REQUEST_ROWS / wall,
        "prom_series": len(series),
        "traced_requests": len(traces),
        "trace_events": len(chrome["traceEvents"]),
        "trace_coverage": coverage,
        "hist_count": snap["count"] if snap else 0,
        "hist_p50_ms": snap["p50"] * 1e3 if snap else None,
        "hist_p99_ms": snap["p99"] * 1e3 if snap else None,
        "drift": health["drift"]["value"],
        "drift_in_band": health["drift"]["in_band"],
        "expected_recall_live": health["expected_recall_live"],
        **measured,
    }

    # Tracing overhead: interleaved min-wall, default tracing vs off.
    wall_on = wall_off = float("inf")
    for _ in range(repeats):
        for buf in (256, 0):
            s = SearchServer(
                index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.001,
                                   trace_buffer=buf),
                warmup=True,
            )
            w, _ = _drive_closed_loop(s, clients, requests_per_client)
            s.close()
            if buf:
                wall_on = min(wall_on, w)
            else:
                wall_off = min(wall_off, w)
    row["tracing_overhead"] = wall_on / wall_off - 1.0
    emit(
        f"telemetry C={clients}: {row['prom_series']} prom series, "
        f"span coverage {coverage:.1%} over {len(traces)} traces, "
        f"hist p50 {row['hist_p50_ms']:.2f}ms vs measured "
        f"{measured['p50_ms']:.2f}ms, drift {row['drift']:.2f} "
        f"({'in' if row['drift_in_band'] else 'OUT of'} band), "
        f"tracing overhead {row['tracing_overhead']:+.1%}"
    )
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="seconds per poisson load point")
    args = ap.parse_args()
    enable_compile_cache()

    index = _build_index()
    results = []

    parity, dispatches, batches = bench_coalesce_vs_direct(
        index, total_rows=512, request_rows=8,
        repeats=10 if args.smoke else 20, emit=print,
    )
    results.append(parity)

    if not args.smoke:
        for clients in CLOSED_LOOP_CLIENTS:
            results.append(
                bench_closed_loop(index, clients, requests_per_client=50,
                                  emit=print)
            )
        for rate in POISSON_RATES:
            results.append(
                bench_poisson(index, rate, args.duration, emit=print)
            )
        fault_rows = [
            bench_fault_rate(index, rate, clients=4, requests_per_client=50,
                             emit=print)
            for rate in FAULT_RATES
        ]
        results.extend(fault_rows)
        results.append(bench_snapshot(index, emit=print))
        telem = bench_telemetry(index, emit=print, clients=8,
                                requests_per_client=25)
        results.append(telem)
    else:
        results.append(
            bench_closed_loop(index, clients=4, requests_per_client=10,
                              emit=print)
        )
        fault_rows = [
            bench_fault_rate(index, rate, clients=2, requests_per_client=10,
                             emit=print)
            for rate in (0.0, 0.05)
        ]
        results.extend(fault_rows)
        snapshot_row = bench_snapshot(index, emit=print, repeats=1)
        results.append(snapshot_row)
        telem = bench_telemetry(index, emit=print, clients=8,
                                requests_per_client=10)
        results.append(telem)

    report = {
        "meta": {
            "jax": jax.__version__,
            "device": jax.default_backend(),
            "platform": platform.platform(),
            "n": N, "d": D, "k": K, "max_batch": MAX_BATCH,
            "smoke": args.smoke,
        },
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out} ({len(results)} load points)")

    if args.smoke:
        # Hard deterministic contracts (bit-parity already asserted inside
        # bench_coalesce_vs_direct): one device dispatch per micro-batch,
        # and coalesced serving is not grossly slower than a pre-formed
        # batch of the same rows (wall-clock slack for noisy CI machines).
        assert parity["dispatches_per_micro_batch"] == 1, parity
        assert dispatches == batches, (dispatches, batches)
        assert parity["server_over_direct"] > 0.8, (
            f"coalesced serving is {parity['server_over_direct']:.2f}x a "
            "pre-formed batch — serving overhead regression"
        )
        # Telemetry contracts (ISSUE 10 acceptance): a closed-loop run
        # exports a real Prometheus surface, the trace spans tile the
        # measured request latency, the exported histogram agrees with
        # the bench's own percentiles over the same latency samples, the
        # roofline-drift monitor is in band at fault rate 0, and tracing
        # is within the <5% overhead budget at C=8.
        assert telem["prom_series"] >= 20, telem["prom_series"]
        assert telem["trace_coverage"] >= 0.95, telem["trace_coverage"]
        assert telem["traced_requests"] == telem["requests"], telem
        assert telem["drift_in_band"], telem
        assert telem["hist_count"] == telem["requests"], telem
        for q in ("p50", "p99"):
            got, want = telem[f"hist_{q}_ms"], telem[f"{q}_ms"]
            assert abs(got - want) <= 0.05 * want + 0.05, (q, got, want)
        assert telem["tracing_overhead"] < 0.05, (
            f"tracing adds {telem['tracing_overhead']:+.1%} at C=8 "
            "closed-loop — over the 5% budget"
        )
        assert parity["server_over_per_request"] > 1.0, (
            f"coalesced serving is {parity['server_over_per_request']:.2f}x "
            "per-request dispatching — the coalescing win disappeared"
        )
        closed = next(r for r in results if r["mode"] == "closed_loop")
        assert closed["dispatches_per_request"] <= 1.0, (
            "closed-loop serving issued more than one dispatch per request "
            f"on average: {closed['dispatches_per_request']:.2f} — "
            "coalescing is not happening"
        )
        clean, faulty = fault_rows[0], fault_rows[-1]
        assert clean["fault_rate"] == 0.0
        assert clean["failed_requests"] == 0 and clean["dispatch_retries"] == 0, (
            f"fault-free serving saw retries/failures: {clean}"
        )
        # every request terminated (result or typed error) — none lost
        for row in fault_rows:
            assert row["ok_requests"] + row["failed_requests"] == row["requests"], row
        # the retry layer keeps delivering under a 5% flaky dispatch path
        assert faulty["goodput_qps"] > 0 and faulty["ok_requests"] > 0, faulty
        assert snapshot_row["time_to_restore_s"] > 0
        print("smoke contract OK")


if __name__ == "__main__":
    main()

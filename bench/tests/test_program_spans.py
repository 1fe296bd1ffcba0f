"""The program's spans and their four readers: held results, fetches and the
worker's host work per micro-batch, and the front door's host time per
search; on hand-built traces, and on two traces recorded on the chip."""
import json
import os

import pytest

import program_spans
import run
import traffic
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(run.BENCH, "metrics")
WORKER = ("/host:CPU", 1)
CLIENT = ("/host:CPU", 0)


def read(name, ctx):
    return traffic.load_module(METRICS, name).read(ctx)


def context(ops, spans, readings, window=(0, 1000)):
    logs = []
    trace = xplane.Trace(ops, [(*window, "bench.window")], [0])
    ctx = run.Context(trace=trace, span=window, readings=readings,
                      program_spans=spans, log=logs.append)
    return ctx, logs


def served():
    # One chip: batch 1 runs [100, 300), batch 2 [500, 700); the window is
    # [0, 1000).  The worker dispatches batch 1, holds with it in flight
    # over [90, 480) (busy until 300), dispatches batch 2, fetches batch 1,
    # holds again over [560, 890) (busy until 700), and fetches batch 2 in
    # a pass whose scatter runs past the window.  [890, 900) is in no span.
    ops = [(100, 300, "fusion.1", True, 0), (500, 700, "fusion.1", True, 0)]
    w = [(0, 10, "serve.coalesce"), (10, 30, "serve.stage"),
         (30, 85, "serve.dispatch[8]"), (32, 84, "index.search"),
         (85, 90, "serve.account"), (90, 480, "serve.hold.inflight"),
         (480, 485, "serve.coalesce"), (485, 495, "serve.stage"),
         (495, 503, "serve.dispatch[8]"), (496, 502, "index.search"),
         (503, 505, "serve.account"),
         (505, 506, "serve.ready"), (506, 520, "serve.fetch"),
         (520, 560, "serve.scatter"),
         (560, 890, "serve.hold.inflight"),
         (900, 905, "serve.coalesce"), (905, 950, "serve.ready"),
         (950, 990, "serve.fetch"), (990, 1010, "serve.scatter")]
    spans = sorted([(s, e, n, WORKER) for s, e, n in w]
                   + [(-500, -400, "index.search", CLIENT)])
    return ops, spans


def test_held_time_counts_only_while_the_device_is_idle():
    ops, spans = served()
    ctx, _ = context(ops, spans, {"batches": 2})
    # [90, 100) + [300, 480) and [700, 890): 380 ns over 2 batches
    assert read("serve_held_ms.served", ctx) == pytest.approx(190e-6)


def test_a_hold_while_the_device_runs_holds_no_result():
    ops = [(0, 1000, "fusion.1", True, 0)]
    spans = [(100, 900, "serve.hold.inflight", WORKER),
             (900, 950, "serve.coalesce", WORKER)]
    ctx, _ = context(ops, spans, {"batches": 1})
    assert read("serve_held_ms.served", ctx) == 0.0


def test_fetch_per_batch_and_the_longest_logged():
    ops, spans = served()
    ctx, logs = context(ops, spans, {"batches": 2})
    assert read("serve_fetch_ms.served", ctx) == pytest.approx(27e-6)
    assert logs == ["serve_fetch_ms.served: 2 fetches, longest 4e-05 ms "
                    "at 9.5e-07 s into the window"]


def test_worker_host_work_and_the_idle_it_covers():
    ops, spans = served()
    ctx, logs = context(ops, spans, {"batches": 2})
    # coalesce 10 + 5 + 5, stage 20 + 10, dispatch 55 + 8, account 5 + 2,
    # scatter 40 + 10 (clipped at the window's end): 170 ns over 2 batches
    assert read("serve_worker_ms.served", ctx) == pytest.approx(85e-6)
    # idle [0, 100) [300, 500) [700, 1000): 600 ns, all but [890, 900)
    # inside a worker span
    assert logs[0] == ("serve_worker_ms.served: 98.33333333333333% of the "
                       "window's device-idle time lies inside a worker span "
                       "(5.9e-07 s of 6e-07 s)")
    assert logs[1] == (
        "serve_worker_ms.served: longest device-idle gaps: "
        "serve.hold.inflight 3e-07 s, serve.hold.inflight 2e-07 s, "
        "index.search 1e-07 s")


def test_front_door_time_per_search():
    ops = [(0, 1000, "fusion.1", True, 0)]
    spans = [(10, 50, "index.search", CLIENT),
             (400, 450, "index.search", CLIENT),
             (980, 1020, "index.search", CLIENT)]
    ctx, _ = context(ops, spans, {"searches": 3})
    assert read("search_dispatch_ms.batch", ctx) == pytest.approx(110e-6 / 3)


@pytest.mark.parametrize("metric", ["serve_held_ms.served",
                                    "serve_fetch_ms.served",
                                    "serve_worker_ms.served",
                                    "search_dispatch_ms.batch"])
def test_no_reading_without_the_spans_or_the_chip(metric):
    ops, spans = served()
    readings = {"batches": 2, "searches": 2}
    # a program whose worker names only its dispatch
    older = [sp for sp in spans if sp[2].startswith("serve.dispatch[")]
    ctx, _ = context(ops, older, readings)
    assert read(metric, ctx) is None
    # a trace with no device op (off the chip), and no trace at all
    ctx, _ = context([], spans, readings)
    assert read(metric, ctx) is None
    ctx.trace = None
    assert read(metric, ctx) is None


def test_interval_helpers():
    assert program_spans.overlap_ns([(0, 10), (20, 30)],
                                    [(5, 25), (28, 40)]) == 12
    assert program_spans.total_ns([(0, 10, "a"), (5, 20, "b")], 2, 15) == 13
    t = xplane.Trace([(10, 20, "a", False, 0), (15, 30, "b", False, 0),
                      (0, 100, "c", False, 1)], [], [0, 1])
    assert program_spans.idle(t, 0, 50) == [(0, 10), (30, 50)]
    assert program_spans.label_at([(0, 10, "outer", 0), (2, 4, "inner", 0)],
                                  3) == "inner"
    assert program_spans.label_at([(0, 10, "outer", 0)], 10) == "none"


def test_recorded_batch_trace_has_no_program_span():
    """The batch trace was recorded before ``Index.search`` had a span: the
    front door's reader finds nothing there, as on an older program."""
    spans = program_spans.load(os.path.join(DATA, "small.xplane.pb"))
    assert spans == []


@pytest.fixture(scope="module")
def recorded_served():
    with open(os.path.join(DATA, "served_small.json")) as f:
        want = json.load(f)
    path = os.path.join(DATA, "served_small.xplane.pb")
    return xplane.load(path), program_spans.load(path), want


def test_recorded_served_trace_reads_as_on_the_chip(recorded_served):
    trace, spans, want = recorded_served
    span = xplane.window(trace)
    ctx = run.Context(trace=trace, span=span,
                      readings={"batches": want["batches"]},
                      program_spans=spans, log=lambda msg: None)
    for name in ("serve_held_ms.served", "serve_fetch_ms.served",
                 "serve_worker_ms.served"):
        assert read(name, ctx) == want["metrics"][name]["value"], name


def test_recorded_served_worker_spans_tile_its_loop(recorded_served):
    trace, spans, _ = recorded_served
    lo, hi = xplane.window(trace)
    worker = program_spans.worker(spans)
    names = {sp[2] for sp in worker}
    assert {"serve.hold.inflight", "serve.coalesce", "serve.stage",
            "serve.account", "serve.ready", "serve.fetch", "serve.scatter",
            "serve.release", "index.search"} <= names, names
    assert any(n.startswith("serve.dispatch[") for n in names)
    assert len({sp[3] for sp in worker}) == 1
    top = [sp for sp in worker if sp[2] != "index.search"]
    for prev, cur in zip(top, top[1:]):
        assert cur[0] >= prev[1], (prev, cur)
    idle = program_spans.idle(trace, lo, hi)
    inside = program_spans.overlap_ns(idle, worker)
    assert inside >= 0.95 * sum(e - s for s, e in idle)

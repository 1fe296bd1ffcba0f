"""The serve worker's and ``Index.search``'s profiler spans, and the
per-batch telemetry of the worker's scatter.

The wall-clock worker names every phase of its loop with a
``jax.profiler.TraceAnnotation`` so that, on the device trace's clock, a
device-idle gap reads as what the worker was doing.  These tests record a
real profiler trace of a tiny server on the CPU and read it back with
``jax.profiler.ProfileData``:

  * every span name appears, ``index.search`` inside each dispatch;
  * the worker's spans never overlap and each loop pass keeps the order
    wait -> coalesce -> stage -> dispatch -> account -> ready -> fetch ->
    scatter -> release;
  * ``serve.hold.inflight`` appears only while a dispatched batch is still
    unfetched, ``serve.hold`` and ``serve.idle`` only while none is;
  * a retried transient fault and an injected worker death leave no span
    open (a recording wrapper counts every enter and exit).

The last section pins the two per-batch costs the scatter no longer pays:
the drift gauge reads a value ``DriftMonitor.record`` keeps current, and a
batch's request latencies enter the registry in one call.
"""
import glob
import os
import re
import threading
import time
import weakref

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

from repro.search import Index, SearchServer, ServeConfig, serve
from repro.search.index import SearchResult
from repro.search.faults import FaultInjector
from repro.search.telemetry import DriftMonitor, MetricsRegistry

D = 16
K = 4
MAX_BATCH = 8
# One letter per worker span, for matching a pass's order as a pattern.
LETTERS = {"serve.idle": "I", "serve.hold": "H", "serve.hold.inflight": "J",
           "serve.coalesce": "C", "serve.stage": "S", "serve.account": "A",
           "serve.ready": "R", "serve.fetch": "F", "serve.scatter": "X",
           "serve.release": "L"}
# A pass: a wait, or a (possibly empty) pop with its stage, dispatch and
# accounting (restaged after a retry), then the previous batch's
# ready/fetch/scatter and the release of the dispatched batch's staged
# copy.  A pass cut by a fault or a worker death may stop after any span.
PASS = r"(I|[HJ]?(C(S)?)+(DA?)?(RF?X?)?L?|[HJ]|RF?X?)"


@pytest.fixture(scope="module")
def index():
    db = jax.random.normal(jax.random.PRNGKey(3), (512, D))
    return Index.build(db, metric="mips", k=K, backend="xla")


def _rows(seed, m):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (m, D)))


def _server(index, faults=None):
    return SearchServer(
        index, ServeConfig(max_batch=MAX_BATCH, max_delay_s=0.01,
                           retry_backoff_s=0.002),
        warmup=True, faults=faults,
    )


def _traced(log_dir, drive):
    """Host spans named ``serve.*`` / ``index.*`` recorded while ``drive()``
    runs, as [(start_ns, end_ns, name)] per host thread line."""
    options = ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = sorted(
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                 ev.name)
                for ev in line.events
                if ev.name.startswith(("serve.", "index."))
            )
            if spans:
                lines.append(spans)
    return lines


def _worker_line(lines):
    (line,) = [ln for ln in lines if any(n.startswith("serve.")
                                         for _, _, n in ln)]
    return line


def _check_worker_line(line):
    """The worker's spans: disjoint, each pass in order, ``index.search``
    inside a dispatch, and hold.inflight only with a batch in flight.
    Returns the pass pattern."""
    top = [sp for sp in line if not sp[2].startswith("index.")]
    for prev, cur in zip(top, top[1:]):
        assert cur[0] >= prev[1], f"{prev} overlaps {cur}"
    dispatches = [sp for sp in top if sp[2].startswith("serve.dispatch[")]
    for s, e, name in line:
        if name == "index.search":
            assert any(ds <= s and e <= de for ds, de, _ in dispatches), (
                f"index.search at {s} outside every dispatch")
    letters = "".join("D" if n.startswith("serve.dispatch[") else LETTERS[n]
                      for _, _, n in top)
    assert re.fullmatch(f"{PASS}*", letters), letters
    inflight = 0
    for _, _, name in top:
        if name.startswith("serve.dispatch["):
            inflight += 1
        elif name == "serve.ready":
            inflight -= 1
        elif name == "serve.hold.inflight":
            assert inflight == 1, letters
        elif name in ("serve.hold", "serve.idle"):
            assert inflight == 0, letters
        assert 0 <= inflight <= 2, letters
    return letters


def test_worker_spans_tile_the_loop_on_the_profiler_clock(index, tmp_path):
    # The third scatter (of the third batch, in a pass that pops nothing)
    # is slowed, so a request submitted meanwhile finds the worker back at
    # the top of its loop with nothing in flight: a hold with none.
    inj = FaultInjector(schedule=[("serve.scatter", 3, "delay")], delay_s=0.2)
    server = _server(index, inj)
    full = [_rows(i, MAX_BATCH) for i in range(3)]
    late = _rows(9, 2)
    tickets = []

    def drive():
        time.sleep(0.12)                    # idle waits
        # The gate holds the first batch between stage and dispatch while
        # two more full batches queue: the worker then holds the window
        # with the first batch in flight.
        with server.mutation():
            tickets.extend(server.submit(q) for q in full)
        while inj.hits["serve.scatter"] < 3:     # the slowed scatter began
            time.sleep(0.001)
        tickets.append(server.submit(late))
        for t in tickets:
            t.result(timeout=60)
        time.sleep(0.06)

    try:
        lines = _traced(tmp_path, drive)
    finally:
        server.close()
    line = _worker_line(lines)
    names = {n for _, _, n in line}
    assert set(LETTERS) | {f"serve.dispatch[{MAX_BATCH}]", "index.search"} \
        <= names, names
    letters = _check_worker_line(line)
    # the full batches held with the first in flight, the last with none
    assert letters.count("J") >= 2 and "XHCSDAL" in letters, letters
    for t in tickets:
        assert t.done and t._error is None
    # the server-clock traces keep their stage names
    for tr in server.traces():
        assert [sp.name for sp in tr.spans] == [
            "submit", "queue", "coalesce", "stage", "dispatch", "scatter"]


class _Recording:
    """``TraceAnnotation`` that also records, per thread, what is open."""

    open_spans = {}
    lock = threading.Lock()

    def __init__(self, name):
        self.name = name
        self._real = TraceAnnotation(name)

    def __enter__(self):
        with self.lock:
            self.open_spans.setdefault(threading.get_ident(), []).append(
                self.name)
        return self._real.__enter__()

    def __exit__(self, *exc):
        with self.lock:
            stack = self.open_spans[threading.get_ident()]
            assert stack.pop() == self.name
        return self._real.__exit__(*exc)


@pytest.mark.parametrize("point,kind", [
    ("serve.dispatch", "transient"),
    ("serve.transfer", "transient"),
    ("serve.dispatch", "death"),
    ("serve.scatter", "death"),
    ("serve.worker", "death"),
])
def test_faults_leave_no_worker_span_open(index, tmp_path, monkeypatch,
                                          point, kind):
    monkeypatch.setattr(serve, "TraceAnnotation", _Recording)
    _Recording.open_spans = {}
    inj = FaultInjector(schedule=[(point, 2, kind)])
    server = _server(index, faults=inj)
    rows = [_rows(20 + i, 3) for i in range(4)]
    outcomes = []

    def drive():
        for q in rows:
            t = server.submit(q)
            try:
                t.result(timeout=60)
                outcomes.append("ok")
            except Exception as e:  # the batch a death at scatter failed
                outcomes.append(type(e).__name__)

    try:
        lines = _traced(tmp_path, drive)
    finally:
        server.close()
    assert all(stack == [] for stack in _Recording.open_spans.values()), (
        _Recording.open_spans)
    stats = server.stats()
    if kind == "transient":
        assert stats["dispatch_retries"] == 1 and outcomes == ["ok"] * 4
    else:
        assert stats["worker_restarts"] == 1
        assert outcomes.count("ok") >= 3
    _check_worker_line(_worker_line(lines))


class _Tagged(SearchResult):
    """A search result that can carry a marker (a NamedTuple subclass
    without ``__slots__`` has a ``__dict__``)."""


def test_a_batch_result_is_released_inside_its_scatter_span(index,
                                                            monkeypatch):
    """The worker drops its last reference to a batch's result, and so
    the device buffers, inside ``serve.scatter``: on a TPU host releasing
    them costs as much as the scatter, and outside a span it would read
    as unexplained device-idle time."""
    monkeypatch.setattr(serve, "TraceAnnotation", _Recording)
    _Recording.open_spans = {}
    server = _server(index)
    released = []
    search = index.search

    def tagged(q):
        out = _Tagged(*search(q))
        marker = out.marker = type("Marker", (), {})()
        weakref.finalize(marker, lambda: released.append(list(
            _Recording.open_spans.get(threading.get_ident(), []))))
        return out

    monkeypatch.setattr(index, "search", tagged)
    rows = [_rows(40 + i, 3) for i in range(4)]
    full = [_rows(50 + i, MAX_BATCH) for i in range(2)]
    try:
        for q in rows:
            server.submit(q).result(timeout=60)
        with server.mutation():             # two batches back to back
            tickets = [server.submit(q) for q in full]
        for t in tickets:
            t.result(timeout=60)
    finally:
        server.close()
    assert len(released) == 6
    assert all(stack and stack[-1] == "serve.scatter" for stack in released), (
        released)


def test_index_search_span_wraps_each_search(index, tmp_path):
    qb = index.spec.query_block

    def drive():
        index.search(_rows(30, 4))
        index.search(_rows(31, qb + 5))     # the streamed multi-block path
        with pytest.raises(ValueError):
            index.search(_rows(32, 4)[:, :3])

    lines = _traced(tmp_path, drive)
    spans = [sp for line in lines for sp in line if sp[2] == "index.search"]
    assert len(spans) == 3, spans
    assert all(e > s for s, e, _ in spans)


# -- per-batch telemetry costs ----------------------------------------------


def _reference_value(records, band=(0.25, 4.0), warmup=3, alpha=0.25):
    """The headline drift recomputed from scratch: per bucket the EWMA of
    measured/predicted over the median of the first ``warmup`` ratios, the
    bucket furthest from 1 in |log| (first in key order on a tie)."""
    ewma, warm, base = {}, {}, {}
    for key, measured, predicted in records:
        r = measured / predicted
        ewma[key] = (r if key not in ewma
                     else alpha * r + (1 - alpha) * ewma[key])
        if key not in base:
            warm.setdefault(key, []).append(r)
            if len(warm[key]) >= warmup:
                base[key] = float(np.median(warm[key]))
    worst = None
    for key in sorted(ewma):
        if key in base:
            d = ewma[key] / base[key]
            if worst is None or abs(np.log(d)) > abs(np.log(worst)):
                worst = d
    return 1.0 if worst is None else worst


def test_drift_value_is_kept_current_by_record():
    rng = np.random.default_rng(5)
    mon = DriftMonitor()
    records = []
    assert mon.value == mon.report()["value"] == 1.0
    for step in range(300):
        key = str(int(rng.choice([8, 16, 64, 256])))
        slow = 6.0 if 150 <= step < 200 and key == "16" else 1.0
        rec = (key, float(rng.uniform(0.5, 1.5)) * slow * 1e-3, 1e-5)
        records.append(rec)
        mon.record(*rec)
        assert mon.value == mon.report()["value"] == _reference_value(records)
        if step == 199:
            assert not mon.report()["in_band"]
    mon.reset()
    assert mon.value == 1.0 and not mon.report()["calibrated"]


def test_observe_many_matches_observe_one_by_one():
    values = np.random.default_rng(6).exponential(0.004, 700).tolist()
    one, many = MetricsRegistry(histogram_window=512), MetricsRegistry(
        histogram_window=512)
    for v in values:
        one.observe("latency_s", v)
        one.observe("wall_s", v, bucket=8)
    many.observe_many("latency_s", values)
    many.observe_many("wall_s", values, bucket=8)
    many.observe_many("empty_s", [])
    assert many.histogram_snapshot("empty_s")["count"] == 0
    snap = many.export_json()
    del snap["histograms"]["empty_s"]
    assert snap == one.export_json()

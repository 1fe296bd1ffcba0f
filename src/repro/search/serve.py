"""Concurrent query serving: async micro-batching over one shared ``Index``.

The paper's peak-FLOP/s claim (Eq. 10/20) only materializes when queries
reach the device as large single-dispatch batches — but serving traffic
arrives as many small concurrent requests.  ``SearchServer`` closes that
gap: it accepts per-request queries (each with its own ``k`` sub-budget
against the shared index), coalesces them into planner-sized micro-batches,
executes each coalesced batch as ONE device dispatch over the packed /
streamed steady-state path, and scatters per-request slices back.  Results
are bit-identical to a direct ``Index.search`` of the same rows — padding
to a bucket shape only adds dead rows, it never reorders reductions.

The moving parts, and the contracts tests pin down:

  * **Bucketed batch shapes.**  A coalesced batch is padded up to the
    smallest *bucket* (``SearchSpec.serve_buckets``, planner-derived via
    ``repro.search.plan.plan_buckets``), so the server only ever dispatches
    a small fixed set of pre-compilable shapes — serving traffic never
    retraces.  A request larger than the largest bucket is dispatched solo
    through the streaming executor (still one dispatch), padded to a
    power-of-two multiple of the largest bucket so oversize shapes stay
    bounded too.
  * **Admission / backpressure.**  The queue holds at most
    ``ServeConfig.max_pending_rows`` query rows.  Wall-clock servers block
    ``submit`` (up to ``admission_timeout_s``) until the worker frees
    space; virtual-clock servers raise :class:`QueueFull` immediately
    (there is no concurrent worker to wait for).
  * **Deterministic scheduling mode.**  Pass ``clock=VirtualClock()`` and
    the server runs no threads and never sleeps: the test (or simulator)
    drives it with ``step()`` / ``run_until_idle()``, one micro-batch per
    ``step``, FIFO whole-request coalescing — fully reproducible, and
    latency accounting follows the virtual clock.
  * **Double-buffered staging.**  Each bucket owns two reusable host
    buffers; the next micro-batch is gathered into one while the previous
    dispatch is still in flight on the device, and the previous batch's
    scatter happens after the next dispatch is enqueued.  Host-side
    gather/scatter work therefore overlaps device compute instead of
    serializing with it.
  * **Fault tolerance.**  Every request can carry a deadline
    (``submit(..., deadline_s=...)``): expired tickets fail fast with
    :class:`DeadlineExceeded` and are NEVER dispatched (no dead work on
    the device).  Transient dispatch faults (``repro.search.faults``
    taxonomy, or anything in ``ServeConfig.retryable``) are retried with
    exponential backoff up to ``max_dispatch_retries``; exhausted retries
    fail the batch's tickets with the typed error.  A dead worker (thread
    exception / injected :class:`~repro.search.faults.WorkerDeath`) is
    restarted by a watchdog without dropping queued tickets — the popped
    batch is requeued at the front.  Sustained overload (admission queue
    full past ``overload_grace_s``) sheds load with a structured
    :class:`Overloaded` error carrying a ``retry_after_s`` estimate —
    callers get an explicit backpressure signal, never silent recall
    loss.  ``SearchServer.health()`` reports status
    ("ok" / "degraded" / "overloaded"), worker liveness, the failure
    counters, and the served-query cluster-miss estimate.
  * **Served-query cluster-miss monitor.**  On clustered indexes, every
    ``miss_sample_every``-th batch samples ``miss_sample_rows`` real
    query rows through ``repro.search.cluster.query_miss_rate``; the
    running estimate surfaces in ``health()["cluster_miss"]`` and
    ``Index.explain()["cluster"]["served_miss"]``.  A rate above the
    ``miss_check_threshold`` warn level flags an out-of-distribution
    query stream (the documented ``cluster="off"`` case).

Typical use::

    from repro.search import Index
    from repro.search.serve import SearchServer

    server = SearchServer(Index.build(db, k=10), warmup=True)
    ticket = server.submit(q, deadline_s=0.1)   # from any thread
    values, indices = ticket.result()  # (m_i, k) slices of one big dispatch
    server.close()

``SERVE_EVENTS`` counts batches / coalesced requests / padded rows /
oversize batches — plus the failure taxonomy: "deadline_expired",
"transient_faults", "dispatch_retries", "failed_batches",
"worker_deaths", "worker_restarts", "requeued_tickets", "load_shed",
"miss_sampled_rows" — globally (same taxonomy style as
``DISPATCH_COUNTS`` / ``PACK_EVENTS``); ``SearchServer.stats()`` reports
the per-server view.  ``docs/operations.md`` is the runbook mapping each
counter to its failure mode and operator action.

Telemetry (``repro.search.telemetry``): the global metrics registry
carries these counters plus queue-depth / occupancy gauges and latency
histograms; every submitted request gets a ticket-scoped trace of stage
spans (``queue -> coalesce -> stage -> dispatch -> scatter``) retained
in a bounded ring buffer (``SearchServer.traces(n)``, Chrome-trace
export via ``telemetry.chrome_trace``); and a roofline-drift monitor
compares each dispatch's measured wall against the plan's Eq. 10/20
prediction, degrading ``health()`` when the calibrated ratio leaves
``ServeConfig.drift_band``.  Span timings follow the server's clock, so
virtual-clock servers produce deterministic traces.

Profiler spans (``jax.profiler.TraceAnnotation``, on the device trace's
clock, recorded only while a profiler trace runs): the wall-clock worker
names every phase of its loop, so a device-idle gap reads as what the
worker was doing.  Per loop pass, in order: ``serve.idle`` (waiting on an
empty queue), ``serve.hold`` / ``serve.hold.inflight`` (holding the
``max_delay_s`` window with nothing / a dispatched batch in flight),
``serve.coalesce`` (popping the batch and choosing its bucket; after a
retryable fault, the backoff and re-forming), ``serve.stage`` (host gather
and put), ``serve.dispatch[<bucket>]`` (the enqueue), ``serve.account``
(request traces, counters and registry of the dispatch), then for the
previous batch ``serve.ready`` (waiting on the device), ``serve.fetch``
(the device-to-host copies) and ``serve.scatter`` (ticket completion,
registry, drift, and the release of the batch's device buffers), and last
``serve.release`` (dropping the dispatched batch's staged query copy, whose
device buffer can wait until the device has read it).
``Index.search`` adds ``index.search``.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.search import backends
from repro.search import cluster as clusterlib
from repro.search import faults as faultslib
from repro.search import telemetry as telemetrylib
from repro.search.index import Index, SearchResult
from repro.search.plan import plan_buckets

__all__ = [
    "DeadlineExceeded",
    "Overloaded",
    "QueueFull",
    "SERVE_EVENTS",
    "SearchServer",
    "SearchTicket",
    "ServeConfig",
    "VirtualClock",
    "reset_serve_events",
]

# event name -> count across every server (test observability hook, same
# reset-act-assert style as backends.DISPATCH_COUNTS / packed.PACK_EVENTS):
# "batches", "coalesced_requests", "padded_rows", "oversize_batches", plus
# the failure taxonomy listed in the module docstring.
SERVE_EVENTS = telemetrylib.AtomicCounter()
telemetrylib.registry().register_counter_dict(
    "repro_serve_events_total", SERVE_EVENTS, "event",
    "SearchServer lifecycle and failure events (docs/operations.md)",
)


def reset_serve_events() -> None:
    """Zero ``SERVE_EVENTS`` (tests: reset, act, assert — no arithmetic).

    Deprecated thin alias: ``repro.search.telemetry.reset_all()`` zeroes
    this and every other global series in one call."""
    SERVE_EVENTS.clear()


class QueueFull(RuntimeError):
    """Admission control rejected a request: the pending-row queue is full."""


class Overloaded(QueueFull):
    """Sustained-overload load shed: the queue has been full past
    ``ServeConfig.overload_grace_s``.  Subclasses :class:`QueueFull` (old
    handlers keep working) and adds ``retry_after_s`` — the server's
    estimate of when queued work will have drained — so callers can back
    off intelligently instead of hammering a saturated server."""

    def __init__(self, rows_pending: int, retry_after_s: float):
        self.rows_pending = rows_pending
        self.retry_after_s = retry_after_s
        super().__init__(
            f"server overloaded: {rows_pending} rows pending past the "
            f"overload grace window; retry in ~{retry_after_s:.3f}s"
        )


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before its batch was dispatched.

    Raised through ``SearchTicket.result()``.  The contract is strict:
    an expired ticket is failed at batch-formation (or retry) time and
    its rows are NEVER dispatched — deadlines exist to stop dead work
    from reaching the device, not just to time out the caller."""

    def __init__(self, rows: int, deadline: float, now: float):
        self.deadline = deadline
        super().__init__(
            f"deadline {deadline:.6f} passed (now {now:.6f}) before "
            f"dispatch; request of {rows} rows was never dispatched"
        )


class VirtualClock:
    """Deterministic, manually-advanced clock for tests and simulation.

    A server built with ``clock=VirtualClock()`` runs no threads and never
    sleeps; latency accounting (``SearchTicket.latency_s``) reads this
    clock, so a test or a load simulator controls time exactly.

    >>> clock = VirtualClock()
    >>> clock.advance(0.5)
    0.5
    >>> clock.now()
    0.5
    """

    def __init__(self, start: float = 0.0):
        self._now = float(start)

    def now(self) -> float:
        return self._now

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot advance time backwards (dt={dt})")
        self._now += dt
        return self._now


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Frozen serving policy for one :class:`SearchServer`.

    Attributes:
      max_batch: most query rows one micro-batch holds.  ``None`` defers to
        the planner-resolved ``SearchSpec.query_block`` — the batch size the
        kernel plan was sized for.
      buckets: ascending pre-compiled batch shapes; a coalesced batch pads
        up to the smallest bucket holding it.  ``None`` defers to
        ``SearchSpec.serve_buckets`` (planner-derived ladder), clipped to
        ``max_batch``.
      max_pending_rows: admission bound — most query rows queued (not yet
        dispatched) at once; ``submit`` beyond it blocks (wall clock) or
        raises :class:`QueueFull` (virtual clock).
      max_delay_s: wall-clock coalescing window — how long the worker holds
        an under-full batch open for more arrivals.  Irrelevant under a
        virtual clock (the driver decides when to ``step``).
      admission_timeout_s: longest a wall-clock ``submit`` blocks for queue
        space before raising :class:`QueueFull`.
      max_dispatch_retries: redispatch attempts after a retryable fault
        (0 disables retries); exhausted retries fail the batch's tickets
        with the typed error.
      retry_backoff_s: base backoff before the first retry, doubled per
        attempt.  Wall-clock servers sleep; virtual-clock servers advance
        the clock (so backoff interacts with deadlines deterministically).
      retryable: exception types the retry loop redispatches on.  Default
        :class:`repro.search.faults.TransientFault` — extend with runtime
        exception types known to be transient on your platform.
      overload_grace_s: how long the admission queue must stay full before
        ``submit`` sheds load with :class:`Overloaded` instead of
        blocking/raising :class:`QueueFull`.  0 sheds immediately on a
        full queue.
      miss_sample_every: on clustered indexes, sample the served-query
        cluster-miss rate every Nth dispatched batch (0 disables the
        monitor).
      miss_sample_rows: query rows scored per sample (clipped to the
        batch's live rows).
      trace_buffer: how many completed request traces the ring buffer
        keeps (``SearchServer.traces(n)``); 0 disables per-request
        tracing entirely (no trace objects are allocated).
      drift_band: (lo, hi) band for the roofline-drift monitor's
        normalized measured/predicted ratio; outside it ``health()``
        degrades.  The ratio is baseline-calibrated, so ~1.0 is "on
        model" on any platform.
      drift_warmup: dispatches per bucket used to fix the drift
        baseline (the median of their measured/predicted ratios).
      drift_alpha: EWMA weight of the newest dispatch's ratio.
    """

    max_batch: Optional[int] = None
    buckets: Optional[Tuple[int, ...]] = None
    max_pending_rows: int = 4096
    max_delay_s: float = 0.002
    admission_timeout_s: float = 5.0
    max_dispatch_retries: int = 2
    retry_backoff_s: float = 0.001
    retryable: Tuple[type, ...] = (faultslib.TransientFault,)
    overload_grace_s: float = 0.25
    miss_sample_every: int = 32
    miss_sample_rows: int = 8
    trace_buffer: int = 256
    drift_band: Tuple[float, float] = (0.25, 4.0)
    drift_warmup: int = 3
    drift_alpha: float = 0.25

    def __post_init__(self):
        if self.max_batch is not None and self.max_batch <= 0:
            raise ValueError(f"max_batch must be positive, got {self.max_batch}")
        if self.max_pending_rows <= 0:
            raise ValueError(
                f"max_pending_rows must be positive, got {self.max_pending_rows}"
            )
        if self.max_delay_s < 0 or self.admission_timeout_s < 0:
            raise ValueError("delays/timeouts must be non-negative")
        if self.max_dispatch_retries < 0:
            raise ValueError(
                f"max_dispatch_retries must be >= 0, got "
                f"{self.max_dispatch_retries}"
            )
        if self.retry_backoff_s < 0 or self.overload_grace_s < 0:
            raise ValueError("backoff/grace must be non-negative")
        if self.miss_sample_every < 0 or self.miss_sample_rows <= 0:
            raise ValueError(
                "miss_sample_every must be >= 0 and miss_sample_rows > 0"
            )
        if self.trace_buffer < 0:
            raise ValueError(
                f"trace_buffer must be >= 0, got {self.trace_buffer}"
            )
        lo, hi = self.drift_band
        if not 0.0 < lo < hi:
            raise ValueError(f"drift_band must be 0 < lo < hi, got "
                             f"{self.drift_band}")
        if self.drift_warmup < 1:
            raise ValueError(
                f"drift_warmup must be >= 1, got {self.drift_warmup}"
            )
        if not 0.0 < self.drift_alpha <= 1.0:
            raise ValueError(
                f"drift_alpha must be in (0, 1], got {self.drift_alpha}"
            )
        if self.buckets is not None:
            object.__setattr__(
                self, "buckets", tuple(int(b) for b in self.buckets)
            )


class SearchTicket:
    """Handle for one submitted request; resolves to a ``SearchResult``.

    ``result()`` returns ``(values, indices)`` of shape ``(rows, k)`` — the
    request's slice of its coalesced micro-batch, bit-identical to a direct
    ``Index.search`` of the same query rows.  The arrays are host-side
    numpy views (results cross the device boundary once per micro-batch,
    not once per request).
    """

    __slots__ = (
        "rows", "k", "deadline", "submitted_at", "completed_at", "trace",
        "_queries", "_offset", "_server", "_done", "_event", "_result",
        "_error",
    )

    def __init__(self, server: "SearchServer", queries: np.ndarray, k: int,
                 deadline: Optional[float] = None):
        self._server = server
        self._queries = queries
        self.rows = queries.shape[0]
        self.k = k
        # Absolute deadline on the server's clock; None = no deadline.
        self.deadline = deadline
        self.submitted_at = server._now()
        self.completed_at: Optional[float] = None
        # Ticket-scoped stage trace (None when ServeConfig.trace_buffer=0).
        self.trace: Optional[telemetrylib.RequestTrace] = None
        self._offset = 0
        self._done = False
        # Allocated lazily (under the server lock) only when a thread
        # actually blocks in ``result()``: at thousands of requests per
        # second, per-ticket Event construction is measurable overhead and
        # the virtual-clock mode never waits at all.
        self._event: Optional[threading.Event] = None
        self._result: Optional[SearchResult] = None
        self._error: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self._done

    @property
    def latency_s(self) -> Optional[float]:
        """Submit-to-completion latency on the server's clock (None while
        pending)."""
        if self.completed_at is None:
            return None
        return self.completed_at - self.submitted_at

    def result(self, timeout: Optional[float] = None) -> SearchResult:
        """The request's ``(values (rows, k), indices (rows, k))``.

        Wall-clock servers block until the worker completes the request;
        virtual-clock servers drive their own queue to idle (equivalent to
        ``server.run_until_idle()``), so a plain submit-then-result flow
        works in both modes.
        """
        if not self._done and self._server._manual:
            self._server.run_until_idle()
        if not self._done:
            with self._server._lock:  # completion holds the same lock
                event = self._event
                if event is None and not self._done:
                    event = self._event = threading.Event()
            if event is not None and not event.wait(timeout):
                raise TimeoutError(
                    f"request ({self.rows} rows) still pending after "
                    f"{timeout}s"
                )
        if self._error is not None:
            raise self._error
        return self._result

    def _complete(self, result: SearchResult, now: float) -> None:
        """Caller must hold the server lock (see ``result``)."""
        self._result = result
        self.completed_at = now
        self._queries = None  # staging copy done; free the host rows
        self._done = True
        if self.trace is not None:
            self.trace.status = "done"
            self.trace.completed_at = now
            self._server._store_trace(self.trace)
        if self._event is not None:
            self._event.set()

    def _fail(self, error: BaseException, now: float) -> None:
        """Caller must hold the server lock."""
        self._error = error
        self.completed_at = now
        self._queries = None
        self._done = True
        tr = self.trace
        if tr is not None:
            tr.status = "failed"
            tr.completed_at = now
            last = max((sp.end for sp in tr.spans), default=self.submitted_at)
            tr.span("failed", last, now)
            self._server._store_trace(tr)
        if self._event is not None:
            self._event.set()


class SearchServer:
    """Async micro-batching front end over one shared :class:`Index`.

    ``clock=None`` (default) starts a background worker thread that
    coalesces on the wall clock (``ServeConfig.max_delay_s`` window);
    passing a :class:`VirtualClock` selects the deterministic single-
    threaded mode where the caller drives ``step()`` /
    ``run_until_idle()``.  ``warmup=True`` pre-compiles every bucket shape
    before the first request (otherwise each bucket compiles on first use).
    """

    def __init__(
        self,
        index: Index,
        config: Optional[ServeConfig] = None,
        *,
        clock: Optional[VirtualClock] = None,
        warmup: bool = False,
        faults: Optional[faultslib.FaultInjector] = None,
    ):
        self.index = index
        # Per-server injector for the serve.* points; None falls through
        # to the process-global ``faults.active()`` registry.
        self._faults = faults
        self.config = config or ServeConfig()
        spec = index.spec
        if not spec.aggregate_to_topk:
            raise ValueError(
                "SearchServer requires aggregate_to_topk=True: per-request "
                "k budgets are column slices of the coalesced dispatch, "
                "which is only correct over sorted top-k rows — not the "
                "raw unsorted bin winners"
            )
        qb = spec.query_block or 4096
        self.max_batch = self.config.max_batch or qb
        buckets = (
            self.config.buckets
            or spec.serve_buckets
            or plan_buckets(self.max_batch)
        )
        buckets = sorted({int(b) for b in buckets if b <= self.max_batch})
        if not buckets or buckets[-1] != self.max_batch:
            buckets.append(self.max_batch)
        self.buckets: Tuple[int, ...] = tuple(buckets)
        self._qdtype = np.dtype(spec.dtype or index._db.dtype)

        self._clock = clock
        self._manual = clock is not None
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._pending_rows = 0
        self._closed = False
        # (result, batch, bucket, t_disp0): dispatched, not yet scattered
        # (t_disp0 = perf_counter at dispatch; closes the drift window).
        self._inflight: Optional[tuple] = None
        # Serializes index.search dispatches against out-of-band Index
        # mutations (see ``mutation()``) — Index is not thread-safe.
        self._dispatch_gate = threading.Lock()
        self._staging: Dict[int, list] = {}
        # AtomicCounter: the worker thread increments while operator
        # threads read stats()/health()/exports — see repro.search.telemetry.
        self._stats = telemetrylib.AtomicCounter()
        self._latency_sum = 0.0
        self._worker: Optional[threading.Thread] = None
        # Overload tracking: when the admission queue first went (and
        # stayed) full; None while there is space.
        self._full_since: Optional[float] = None
        # EWMA of wall seconds per service cycle — the Overloaded
        # retry-after estimate's drain rate.
        self._service_ema = 0.0
        self._miss_sample_countdown = self.config.miss_sample_every
        self._started_at = self._now()
        # Completed-trace ring buffer (bounded; None = tracing disabled).
        self._traces: Optional[collections.deque] = (
            collections.deque(maxlen=self.config.trace_buffer)
            if self.config.trace_buffer > 0 else None
        )
        self._trace_seq = 0
        # Roofline-drift monitor: measured dispatch wall vs the plan's
        # Eq. 10/20 prediction per bucket (health()["drift"]).
        self._drift = telemetrylib.DriftMonitor(
            band=self.config.drift_band,
            warmup=self.config.drift_warmup,
            alpha=self.config.drift_alpha,
        )
        self._predicted_cache: Dict[int, Optional[float]] = {}
        self._last_fault: Optional[dict] = None

        if warmup:
            self.precompile()
        if not self._manual:
            self._worker = threading.Thread(
                target=self._worker_main, name="SearchServer", daemon=True
            )
            self._worker.start()

    # -- time / fault plumbing -----------------------------------------------

    def _now(self) -> float:
        return self._clock.now() if self._manual else time.monotonic()

    def _fire(self, point: str) -> None:
        """Hit a serve.* injection point (per-server injector first, then
        the process-global registry; no-op when neither is installed)."""
        inj = self._faults if self._faults is not None else faultslib.active()
        if inj is not None:
            inj.fire(point)

    def _backoff(self, delay: float) -> None:
        """Retry backoff: sleep on the wall clock, advance a virtual one
        (so backoff-vs-deadline interactions stay deterministic in tests)."""
        if delay <= 0:
            return
        if self._manual:
            self._clock.advance(delay)
        else:
            time.sleep(delay)

    # -- admission -----------------------------------------------------------

    @property
    def pending_rows(self) -> int:
        """Query rows admitted but not yet dispatched (the queue depth the
        backpressure bound applies to)."""
        return self._pending_rows

    def submit(self, queries, k: Optional[int] = None,
               deadline_s: Optional[float] = None) -> SearchTicket:
        """Enqueue one request: ``(rows, D)`` (or a single ``(D,)`` row).

        ``k`` is the request's own neighbour budget — it must not exceed
        the index's ``spec.k`` (the coalesced dispatch computes ``spec.k``
        winners once; per-request budgets are slices of that, which is what
        lets requests with different ``k`` share a batch).  ``deadline_s``
        is a relative deadline on the server's clock: if it passes before
        the request's batch dispatches, the ticket fails with
        :class:`DeadlineExceeded` and its rows are never dispatched.
        Returns a :class:`SearchTicket`; raises :class:`QueueFull` when
        admission control rejects the request, or its subclass
        :class:`Overloaded` (with a ``retry_after_s`` estimate) under
        sustained overload.
        """
        q = np.asarray(queries, self._qdtype)
        if q.ndim == 1:
            q = q[None, :]
        if q.ndim != 2 or q.shape[0] == 0:
            raise ValueError(f"queries must be (rows>0, D), got {q.shape}")
        if q.shape[1] != self.index.dim:
            raise ValueError(
                f"query dim {q.shape[1]} != index dim {self.index.dim}"
            )
        k = self.index.spec.k if k is None else int(k)
        if not 0 < k <= self.index.spec.k:
            raise ValueError(
                f"per-request k={k} must be in [1, spec.k={self.index.spec.k}]"
                " — build the index with the largest k any request needs"
            )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        rows = q.shape[0]
        with self._lock:
            if self._closed:
                raise RuntimeError("server is closed")
            if rows > self.config.max_pending_rows:
                raise QueueFull(
                    f"request of {rows} rows exceeds the admission capacity "
                    f"({self.config.max_pending_rows} rows)"
                )
            if self._pending_rows + rows > self.config.max_pending_rows:
                now = self._now()
                if self._full_since is None:
                    self._full_since = now
                if now - self._full_since >= self.config.overload_grace_s:
                    self._shed_locked()  # raises Overloaded
                if self._manual:
                    raise QueueFull(
                        f"{self._pending_rows} rows pending; admitting {rows} "
                        f"more exceeds max_pending_rows="
                        f"{self.config.max_pending_rows}"
                    )
                timeout = time.monotonic() + self.config.admission_timeout_s
                while self._pending_rows + rows > self.config.max_pending_rows:
                    remaining = timeout - time.monotonic()
                    if remaining <= 0 or self._closed:
                        raise QueueFull(
                            f"no queue space for {rows} rows within "
                            f"{self.config.admission_timeout_s}s"
                        )
                    self._not_full.wait(remaining)
                    if (
                        self._pending_rows + rows
                        > self.config.max_pending_rows
                        and self._full_since is not None
                        and self._now() - self._full_since
                        >= self.config.overload_grace_s
                    ):
                        # The queue stayed full past the grace window while
                        # this thread waited: fail fast with the structured
                        # signal instead of stacking blocked submitters.
                        self._shed_locked()
                if self._closed:
                    # close() may have drained the queue and retired the
                    # worker while this thread waited for space; enqueueing
                    # now would strand the ticket forever.
                    raise RuntimeError("server is closed")
            deadline = (
                None if deadline_s is None else self._now() + deadline_s
            )
            ticket = SearchTicket(self, q, k, deadline)
            if self._traces is not None:
                self._trace_seq += 1
                tr = telemetrylib.RequestTrace(
                    self._trace_seq, rows, k, ticket.submitted_at
                )
                tr.span("submit", ticket.submitted_at, ticket.submitted_at)
                ticket.trace = tr
            self._queue.append(ticket)
            self._pending_rows += rows
            self._stats["peak_pending_rows"] = max(
                self._stats["peak_pending_rows"], self._pending_rows
            )
            telemetrylib.registry().set_gauge(
                "repro_serve_pending_rows", self._pending_rows
            )
            self._work.notify()
        return ticket

    def search(self, queries, k: Optional[int] = None,
               timeout: Optional[float] = None) -> SearchResult:
        """Synchronous convenience: ``submit`` + ``result`` in one call."""
        return self.submit(queries, k=k).result(timeout=timeout)

    def resolve(self, tickets: Sequence[SearchTicket],
                timeout: Optional[float] = None) -> List[SearchResult]:
        """Resolve many tickets (driving the queue first in virtual mode)."""
        if self._manual:
            self.run_until_idle()
        return [t.result(timeout=timeout) for t in tickets]

    # -- micro-batch formation and dispatch ----------------------------------

    def _shed_locked(self) -> None:
        """Raise :class:`Overloaded` with a drain-time estimate (caller
        must hold the lock)."""
        batches = max(1, -(-self._pending_rows // self.max_batch))
        per_batch = max(
            self._service_ema, self.config.max_delay_s, 1e-3
        )
        self._stats.inc("load_shed")
        SERVE_EVENTS.inc("load_shed")
        raise Overloaded(self._pending_rows, batches * per_batch)

    def _fail_expired_locked(self, t: SearchTicket, now: float) -> None:
        """Fail one deadline-expired ticket (caller must hold the lock)."""
        t._fail(DeadlineExceeded(t.rows, t.deadline, now), now)
        self._stats.inc("deadline_expired")
        SERVE_EVENTS.inc("deadline_expired")

    def _take_batch_locked(self, now: float) -> Optional[List[SearchTicket]]:
        """Pop the next FIFO micro-batch: whole requests only, up to
        ``max_batch`` rows (a request bigger than ``max_batch`` ships solo
        through the streaming executor).  Deadline-expired tickets are
        failed here — popped and skipped, never staged or dispatched."""
        batch: List[SearchTicket] = []
        total = 0
        while self._queue:
            head = self._queue[0]
            if head.deadline is not None and now >= head.deadline:
                self._queue.popleft()
                self._pending_rows -= head.rows
                self._fail_expired_locked(head, now)
                continue
            if batch and total + head.rows > self.max_batch:
                break
            self._queue.popleft()
            batch.append(head)
            total += head.rows
            if total >= self.max_batch:
                break
        self._pending_rows -= total
        if self._pending_rows < self.config.max_pending_rows:
            self._full_since = None
        telemetrylib.registry().set_gauge(
            "repro_serve_pending_rows", self._pending_rows
        )
        return batch or None

    def _expire_batch(
        self, batch: List[SearchTicket], now: float
    ) -> List[SearchTicket]:
        """Drop (and fail) tickets whose deadline passed — re-checked
        before every retry so backoff never redispatches dead work."""
        live = [
            t for t in batch if t.deadline is None or now < t.deadline
        ]
        if len(live) != len(batch):
            with self._lock:
                for t in batch:
                    if t.deadline is not None and now >= t.deadline:
                        self._fail_expired_locked(t, now)
        return live

    def _fail_batch(self, batch: List[SearchTicket],
                    error: BaseException) -> None:
        """Fail every ticket of a batch with one typed error."""
        now = self._now()
        self._last_fault = {
            "error": type(error).__name__,
            "point": getattr(error, "point", None),
            "detail": str(error),
            "at": now,
        }
        with self._lock:
            for t in batch:
                t._fail(error, now)
        self._stats.inc("failed_batches")
        SERVE_EVENTS.inc("failed_batches")

    def _requeue(self, batch: List[SearchTicket]) -> None:
        """Put a popped-but-undispatched batch back at the queue front
        (FIFO order preserved) — the worker-death no-ticket-loss leg."""
        with self._lock:
            for t in reversed(batch):
                self._queue.appendleft(t)
                self._pending_rows += t.rows
        self._stats.inc("requeued_tickets", len(batch))
        SERVE_EVENTS.inc("requeued_tickets", len(batch))

    def _bucket_for(self, rows: int) -> int:
        """Smallest pre-compiled shape holding ``rows``; oversize requests
        double up from ``max_batch`` so even their shapes stay bounded."""
        if rows <= self.max_batch:
            return self.buckets[bisect.bisect_left(self.buckets, rows)]
        bucket = self.max_batch
        while bucket < rows:
            bucket *= 2
        self._stats.inc("oversize_batches")
        SERVE_EVENTS.inc("oversize_batches")
        return bucket

    def _stage(self, bucket: int, batch: List[SearchTicket]) -> np.ndarray:
        """Gather the batch's query rows into a reusable host buffer.

        Two buffers per bucket, used alternately: the buffer being filled
        here is never the one whose device copy the in-flight dispatch was
        fed from, so host gather overlaps device compute (the
        double-buffering leg of the pipeline).
        """
        if bucket > self.max_batch:
            # Oversize batches ship solo and are rare: a transient buffer,
            # never cached — caching would pin two bucket-sized host
            # buffers per oversize shape for the server's lifetime.
            buf = np.zeros((bucket, self.index.dim), self._qdtype)
        else:
            pair = self._staging.get(bucket)
            if pair is None:
                pair = self._staging[bucket] = [
                    np.zeros((bucket, self.index.dim), self._qdtype),
                    np.zeros((bucket, self.index.dim), self._qdtype),
                    0,
                ]
            buf = pair[pair[2]]
            pair[2] ^= 1
            self._stats.inc("staging_swaps")
        offset = 0
        for t in batch:
            buf[offset : offset + t.rows] = t._queries
            t._offset = offset
            offset += t.rows
        buf[offset:] = 0.0  # bucket padding: dead rows, sliced away at scatter
        return buf

    def _service_once(self) -> bool:
        """Dispatch ONE coalesced micro-batch; then scatter the previous.

        Pipeline order is deliberate: stage the new batch (host work) while
        the previous dispatch runs on device, enqueue the new dispatch,
        *then* block on the previous result and scatter it — so the device
        is never idle waiting for host gather/scatter bookkeeping.

        Faults: retryable exceptions (``ServeConfig.retryable``) redispatch
        the batch after exponential backoff, re-checking deadlines each
        attempt; exhausted retries (and non-retryable errors) fail the
        batch's tickets with the typed error.  :class:`WorkerDeath` requeues
        the batch (nothing was dispatched) and propagates — the watchdog /
        ``step()`` restart path handles it without ticket loss.
        """
        # Death here = nothing popped yet; the queue is untouched.
        self._fire("serve.worker")
        cfg = self.config
        t_start = time.perf_counter()
        with TraceAnnotation("serve.coalesce"):
            with self._lock:
                batch = self._take_batch_locked(self._now())
                if batch is not None:
                    self._not_full.notify_all()
            if batch is not None:
                t_pop = self._now()
                rows = sum(t.rows for t in batch)
                bucket = self._bucket_for(rows)
        if batch is None:
            self._finalize(self._pop_inflight())
            return False
        attempt = 0
        while True:
            try:
                # stage inside the guard: an allocation failure on a huge
                # oversize request must fail its tickets, not kill the
                # worker thread with the popped batch stranded.
                self._fire("serve.staging_alloc")
                t_coalesced = self._now()
                with TraceAnnotation("serve.stage"):
                    buf = self._stage(bucket, batch)
                    self._fire("serve.transfer")
                    q = jnp.asarray(buf)
                t_staged = self._now()
                # perf_counter BEFORE the injection point: an injected
                # delay lands inside the drift monitor's measured window.
                t_disp0 = time.perf_counter()
                # Fired OUTSIDE the gate: a death injected here while the
                # main thread holds ``mutation()`` must not deadlock the
                # restarted worker on a gate its dead self never took.
                self._fire("serve.dispatch")
                with self._dispatch_gate:
                    # Names the dispatch in device profiles.
                    with TraceAnnotation(f"serve.dispatch[{bucket}]"):
                        result = self.index.search(q)  # ONE dispatch
                break
            except faultslib.WorkerDeath:
                # This thread is about to die; nothing was dispatched for
                # this batch, so hand it back intact for the next worker.
                self._requeue(batch)
                raise
            except cfg.retryable as e:
                self._stats.inc("transient_faults")
                SERVE_EVENTS.inc("transient_faults")
                self._last_fault = {
                    "error": type(e).__name__,
                    "point": getattr(e, "point", None),
                    "detail": str(e),
                    "at": self._now(),
                }
                if attempt >= cfg.max_dispatch_retries:
                    self._fail_batch(batch, e)
                    return True
                attempt += 1
                self._stats.inc("dispatch_retries")
                SERVE_EVENTS.inc("dispatch_retries")
                with TraceAnnotation("serve.coalesce"):
                    self._backoff(cfg.retry_backoff_s * (2 ** (attempt - 1)))
                    # Deadlines keep ticking through backoff: drop expired
                    # tickets rather than dispatch dead work on the retry.
                    batch = self._expire_batch(batch, self._now())
                    if batch:
                        rows = sum(t.rows for t in batch)
                        bucket = self._bucket_for(rows)
                if not batch:
                    return True
            except Exception as e:  # scatter the failure, keep serving
                self._fail_batch(batch, e)
                return True
        with TraceAnnotation("serve.account"):
            t_dispatched = self._now()
            for t in batch:
                tr = t.trace
                if tr is not None:
                    # Contiguous stage spans on the server clock: together
                    # with "scatter" (closed at completion) they tile the
                    # request's [submit, complete] window end to end.
                    tr.bucket = bucket
                    tr.retries = attempt
                    tr.span("queue", t.submitted_at, t_pop)
                    tr.span("coalesce", t_pop, t_coalesced)
                    tr.span("stage", t_coalesced, t_staged)
                    tr.span("dispatch", t_staged, t_dispatched)
                    tr.dispatched_at = t_dispatched
            self._stats.inc("batches")
            self._stats.inc("coalesced_requests", len(batch))
            self._stats.inc("dispatched_rows", rows)
            self._stats.inc("padded_rows", bucket - rows)
            SERVE_EVENTS.inc("batches")
            SERVE_EVENTS.inc("coalesced_requests", len(batch))
            SERVE_EVENTS.inc("padded_rows", bucket - rows)
            reg = telemetrylib.registry()
            reg.observe("repro_serve_batch_rows", rows, bucket=bucket)
            live = self._stats["dispatched_rows"] + self._stats["padded_rows"]
            if live:
                reg.set_gauge(
                    "repro_serve_occupancy",
                    self._stats["dispatched_rows"] / live,
                )
        self._finalize(self._pop_inflight((result, batch, bucket, t_disp0)))
        with TraceAnnotation("serve.release"):
            # The staged query copy of the batch just dispatched: freeing
            # its device buffer can wait until the device has read it.
            del q
        self._maybe_sample_miss(buf, rows)
        # EWMA of service time feeds the Overloaded retry-after estimate.
        elapsed = time.perf_counter() - t_start
        self._service_ema = (
            elapsed if self._service_ema == 0.0
            else 0.8 * self._service_ema + 0.2 * elapsed
        )
        return True

    def _pop_inflight(
        self, replace: Optional[tuple] = None
    ) -> Optional[tuple]:
        """The in-flight entry, which ``replace`` takes the place of.  Hand
        it straight to ``_finalize`` (no caller variable keeps it), so that
        the batch's device buffers are released inside its spans."""
        entry, self._inflight = self._inflight, replace
        return entry

    def _finalize(self, entry: Optional[tuple]) -> None:
        """Block on a dispatched batch and scatter per-request slices.

        The batch result crosses to the host ONCE (``np.asarray`` — a view
        on CPU, one transfer on accelerators); tickets then receive numpy
        views, not per-request device slices.  Scattering R requests as
        2R device slice programs would cost more than the search itself.
        """
        if entry is None:
            return
        result, batch, bucket, t_disp0 = entry
        del entry  # ``result`` alone holds the device buffers now
        try:
            with TraceAnnotation("serve.ready"):
                self._fire("serve.scatter")
                result.values.block_until_ready()
            # Dispatch-to-ready wall: the measured side of the roofline
            # drift ratio for this bucket.
            measured_s = time.perf_counter() - t_disp0
            with TraceAnnotation("serve.fetch"):
                values = np.asarray(result.values)
                indices = np.asarray(result.indices)
        except faultslib.WorkerDeath as e:
            # The dispatch already ran; its device-side work is lost with
            # the dying worker.  Fail the tickets with the typed error
            # (never silently re-dispatch completed work) and let the
            # watchdog restart the worker for the still-queued rest.
            self._fail_batch(batch, e)
            raise
        except Exception as e:
            # Accelerator errors surface asynchronously, at the block — a
            # bare raise here would kill the worker thread and strand every
            # waiter; fail the batch's tickets instead and keep serving.
            self._fail_batch(batch, e)
            return
        with TraceAnnotation("serve.scatter"):
            now = self._now()
            latencies = []
            with self._lock:  # one acquisition per batch, not per ticket
                for t in batch:
                    tr = t.trace
                    if tr is not None and tr.dispatched_at is not None:
                        tr.span("scatter", tr.dispatched_at, now)
                    t._complete(
                        SearchResult(
                            values[t._offset : t._offset + t.rows, : t.k],
                            indices[t._offset : t._offset + t.rows, : t.k],
                        ),
                        now,
                    )
                    if t.latency_s is not None:
                        self._latency_sum += t.latency_s
                        latencies.append(t.latency_s)
                self._stats.inc("completed_requests", len(batch))
            reg = telemetrylib.registry()
            reg.observe_many("repro_serve_request_latency_seconds", latencies)
            reg.observe(
                "repro_serve_dispatch_wall_seconds", measured_s,
                bucket=bucket,
            )
            self._record_drift(bucket, measured_s)
            # Release the batch's device buffers here, inside the span: on
            # a TPU host that takes about as long as the scatter itself.
            del result

    def _maybe_sample_miss(self, buf: np.ndarray, live_rows: int) -> None:
        """Served-query cluster-miss monitor: every Nth batch, score a few
        real query rows through ``cluster.query_miss_rate`` and fold the
        counts into the ``ClusterState`` accumulators.

        Uses the *live* front of the staging buffer (padding rows would
        bias the estimate toward the all-zeros query).  Best-effort by
        design: the monitor must never take serving down, so any failure
        is swallowed — the signal just stays stale."""
        if self.config.miss_sample_every <= 0:
            return
        pk = getattr(self.index, "_packed", None)
        cs = pk.cluster if pk is not None else None
        if cs is None:
            return
        self._miss_sample_countdown -= 1
        if self._miss_sample_countdown > 0:
            return
        self._miss_sample_countdown = self.config.miss_sample_every
        m = min(self.config.miss_sample_rows, live_rows)
        try:
            rows, bias = pk.exact_rows_bias()
            missed, checked = clusterlib.query_miss_rate(
                cs, jnp.asarray(np.array(buf[:m])), rows, bias,
                self.index.spec.k,
            )
        except Exception:
            return
        cs.served_miss_checked += checked
        cs.served_miss_missed += missed
        rate = cs.served_miss_rate
        if rate is not None:
            telemetrylib.registry().set_gauge(
                "repro_serve_cluster_miss_rate", rate
            )
        self._stats.inc("miss_sampled_rows", m)
        SERVE_EVENTS.inc("miss_sampled_rows", m)

    # -- deterministic (virtual-clock) driving -------------------------------

    def step(self) -> bool:
        """Virtual-clock driver: dispatch one micro-batch (scattering the
        previously dispatched one).  Returns False — after finalizing any
        leftover in-flight batch — once the queue is empty."""
        if not self._manual:
            raise RuntimeError(
                "step() is the virtual-clock driver; wall-clock servers "
                "run their own worker thread"
            )
        try:
            return self._service_once()
        except faultslib.WorkerDeath:
            # The virtual-clock analogue of the wall watchdog: the "worker"
            # (this step) died and is instantly "restarted" — queued tickets
            # were requeued by the dying service pass, so the next step
            # picks them up.  Returns True: there may still be work.
            self._record_restart()
            return True

    def run_until_idle(self) -> None:
        """Drive the queue to empty and scatter everything in flight."""
        while self.step():
            pass

    # -- wall-clock worker ---------------------------------------------------

    def _record_restart(self) -> None:
        self._stats.inc("worker_deaths")
        self._stats.inc("worker_restarts")
        SERVE_EVENTS.inc("worker_deaths")
        SERVE_EVENTS.inc("worker_restarts")
        self._last_fault = {
            "error": "WorkerDeath",
            "point": "serve.worker",
            "detail": "worker died and was restarted by the watchdog",
            "at": self._now(),
        }

    def _worker_main(self) -> None:
        """Watchdog wrapper: restart a dead worker loop in place.

        A worker death (injected :class:`~repro.search.faults.WorkerDeath`
        or any escaped exception) would otherwise strand every queued
        ticket forever.  Restarting *inside the same thread* keeps
        ``close()``'s join working unchanged, and the dying service pass
        already requeued any popped-but-undispatched batch — so no ticket
        is lost across a restart."""
        while True:
            try:
                self._worker_loop()
                return
            except BaseException:
                with self._lock:
                    done = (
                        self._closed
                        and not self._queue
                        and self._inflight is None
                    )
                self._record_restart()
                if done:
                    return

    def _worker_loop(self) -> None:
        cfg = self.config
        while True:
            with self._lock:
                if self._closed and not self._queue:
                    break
                if not self._queue:
                    # idle: scatter any in-flight batch, then sleep on work
                    if self._inflight is None:
                        with TraceAnnotation("serve.idle"):
                            self._work.wait(0.05)
                else:
                    # coalescing window: hold the batch open for late
                    # arrivals until it fills or the head request's window
                    # expires.  Only this thread sets ``_inflight``, so the
                    # span's name holds for the whole wait.
                    deadline = (
                        self._queue[0].submitted_at + cfg.max_delay_s
                    )
                    held = ("serve.hold" if self._inflight is None
                            else "serve.hold.inflight")
                    with TraceAnnotation(held):
                        while (
                            self._queue
                            and self._pending_rows < self.max_batch
                            and not self._closed
                        ):
                            remaining = deadline - self._now()
                            if remaining <= 0:
                                break
                            self._work.wait(remaining)
            self._service_once()
        self._finalize(self._pop_inflight())

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Stop accepting requests, drain the queue, join the worker."""
        with self._lock:
            self._closed = True
            self._work.notify_all()
            self._not_full.notify_all()
        if self._worker is not None:
            self._worker.join(timeout)
            self._worker = None
        elif self._manual:
            self.run_until_idle()

    def __enter__(self) -> "SearchServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- out-of-band index mutations -----------------------------------------

    @contextlib.contextmanager
    def mutation(self):
        """Serialize an ``Index`` mutation against in-flight dispatches.

        ``Index`` is not thread-safe, and a wall-clock server's worker
        calls ``index.search`` from its own thread — so ``add`` / ``delete``
        (or anything else that rebinds the packed state) issued while the
        server runs must take this gate::

            with server.mutation():
                server.index.add(rows)

        ``KNNDatastore.extend`` / ``forget`` do this automatically when a
        server is attached.  Already-dispatched batches are unaffected
        (JAX arrays are immutable — updates rebind new buffers, they never
        write into operands a running program reads); the gate only
        excludes the *start* of a dispatch while index state is mid-update.
        Virtual-clock servers are single-threaded, where this is a no-op
        by construction (but still safe to use).
        """
        with self._dispatch_gate:
            yield

    # -- observability -------------------------------------------------------

    def _store_trace(self, trace: telemetrylib.RequestTrace) -> None:
        """Push a completed trace into the bounded ring buffer (deque
        append is atomic; callers already hold the server lock)."""
        if self._traces is not None:
            self._traces.append(trace)

    def traces(self, n: Optional[int] = None) -> List[telemetrylib.RequestTrace]:
        """The most recent completed request traces, oldest first (at most
        ``ServeConfig.trace_buffer`` are retained; ``n`` limits further).
        Feed them to ``repro.search.telemetry.chrome_trace`` for a
        flame-graph JSON, or ``trace_coverage`` for the span-coverage
        fraction."""
        if self._traces is None:
            return []
        with self._lock:
            out = list(self._traces)
        return out if n is None else out[-int(n):]

    def drift(self) -> dict:
        """The roofline-drift monitor's report (see ``health()["drift"]``)."""
        return self._drift.report()

    def _predicted_s(self, bucket: int) -> Optional[float]:
        """Plan-predicted wall seconds (Eq. 10/20) for one ``bucket``-row
        dispatch, memoized per bucket; None when the planner cannot price
        this shape (drift recording is then skipped)."""
        if bucket in self._predicted_cache:
            return self._predicted_cache[bucket]
        try:
            plan = self.index.kernel_plan
            if plan.m == bucket:
                pred = plan.predicted_s
            else:
                pred = self.index._replan(
                    n=plan.n, m=bucket, backend=plan.backend, pin_from=plan
                ).predicted_s
            pred = float(pred) if pred and pred > 0 else None
        except Exception:
            pred = None
        self._predicted_cache[bucket] = pred
        return pred

    def _record_drift(self, bucket: int, measured_s: float) -> None:
        predicted = self._predicted_s(bucket)
        if predicted is None or measured_s <= 0:
            return
        self._drift.record(str(bucket), measured_s, predicted)
        telemetrylib.registry().set_gauge(
            "repro_serve_drift", self._drift.value
        )

    def precompile(self) -> int:
        """Compile every bucket shape ahead of traffic (one dummy dispatch
        per bucket); returns the number of buckets warmed."""
        for bucket in self.buckets:
            with self._dispatch_gate:  # may be called on a live server
                self.index.search(
                    jnp.zeros((bucket, self.index.dim), self._qdtype)
                ).values.block_until_ready()
        self._stats["precompiled_buckets"] = len(self.buckets)
        return len(self.buckets)

    def stats(self) -> dict:
        """Serving counters: batching efficiency, queue pressure, cache."""
        s = dict(self._stats)
        out = {
            "buckets": self.buckets,
            "max_batch": self.max_batch,
            "batches": s.get("batches", 0),
            "coalesced_requests": s.get("coalesced_requests", 0),
            "completed_requests": s.get("completed_requests", 0),
            "dispatched_rows": s.get("dispatched_rows", 0),
            "padded_rows": s.get("padded_rows", 0),
            "oversize_batches": s.get("oversize_batches", 0),
            "failed_batches": s.get("failed_batches", 0),
            "staging_swaps": s.get("staging_swaps", 0),
            "peak_pending_rows": s.get("peak_pending_rows", 0),
            "precompiled_buckets": s.get("precompiled_buckets", 0),
            "deadline_expired": s.get("deadline_expired", 0),
            "transient_faults": s.get("transient_faults", 0),
            "dispatch_retries": s.get("dispatch_retries", 0),
            "worker_deaths": s.get("worker_deaths", 0),
            "worker_restarts": s.get("worker_restarts", 0),
            "requeued_tickets": s.get("requeued_tickets", 0),
            "load_shed": s.get("load_shed", 0),
            "miss_sampled_rows": s.get("miss_sampled_rows", 0),
            "pending_rows": self._pending_rows,
            "uptime_s": self._now() - self._started_at,
            "traced_requests": len(self._traces) if self._traces else 0,
            "cache": self.index.cache_info(),
            # Process-wide, like DISPATCH_COUNTS: dispatches that ran the
            # fused scan→select kernel, by its select mode.
            "fused_select": dict(backends.FUSED_SELECT_COUNTS),
            # ... and by the operand form of the Pallas scan's dot.
            "scan_operands": dict(backends.SCAN_OPERAND_COUNTS),
        }
        live = out["dispatched_rows"] + out["padded_rows"]
        out["occupancy"] = out["dispatched_rows"] / live if live else 0.0
        done = out["completed_requests"]
        out["mean_latency_s"] = self._latency_sum / done if done else 0.0
        return out

    def health(self) -> dict:
        """Liveness / degradation report for operators and load balancers.

        ``status`` is the headline: ``"ok"``, ``"degraded"`` (dead worker
        on an open server, or the served-query cluster-miss estimate past
        its warn threshold), or ``"overloaded"`` (admission queue full past
        ``overload_grace_s`` — submits are being shed).  The rest is the
        evidence: worker liveness, ``uptime_s``, ``last_fault`` (the most
        recent failure's type/point/time), queue depth, the failure
        counters, the ``drift`` block (roofline-drift monitor: normalized
        measured/predicted dispatch wall per bucket — out of
        ``ServeConfig.drift_band`` degrades), ``expected_recall_live``
        (analytic bin-collision term x the *served* cluster-miss
        estimate), and (clustered indexes) the ``cluster_miss`` block
        mirroring ``Index.explain()["cluster"]["served_miss"]``.  See
        ``docs/operations.md`` for the counter-by-counter runbook.
        """
        with self._lock:
            pending = self._pending_rows
            queued = len(self._queue)
            full_since = self._full_since
            closed = self._closed
        now = self._now()
        worker_alive = self._manual or (
            self._worker is not None and self._worker.is_alive()
        )
        overloaded = (
            full_since is not None
            and now - full_since >= self.config.overload_grace_s
        )
        s = self._stats
        report = {
            "worker_alive": worker_alive,
            "closed": closed,
            "uptime_s": now - self._started_at,
            "last_fault": self._last_fault,
            "pending_rows": pending,
            "queued_requests": queued,
            "deadline_expired": s.get("deadline_expired", 0),
            "transient_faults": s.get("transient_faults", 0),
            "dispatch_retries": s.get("dispatch_retries", 0),
            "failed_batches": s.get("failed_batches", 0),
            "worker_deaths": s.get("worker_deaths", 0),
            "worker_restarts": s.get("worker_restarts", 0),
            "load_shed": s.get("load_shed", 0),
            "requeued_tickets": s.get("requeued_tickets", 0),
        }
        miss_warning = False
        pk = getattr(self.index, "_packed", None)
        cs = pk.cluster if pk is not None else None
        if cs is not None:
            report["cluster_miss"] = cs.served_miss_report()
            miss_warning = report["cluster_miss"]["warning"]
        drift = self._drift.report()
        report["drift"] = drift
        drift_warning = drift["calibrated"] and not drift["in_band"]
        try:
            recall_live = float(self.index.expected_recall_live)
        except Exception:
            recall_live = None
        report["expected_recall_live"] = recall_live
        reg = telemetrylib.registry()
        reg.set_gauge("repro_serve_uptime_seconds", report["uptime_s"])
        if recall_live is not None:
            reg.set_gauge("repro_serve_expected_recall_live", recall_live)
        degraded = (
            (not worker_alive and not closed) or miss_warning or drift_warning
        )
        report["status"] = (
            "overloaded" if overloaded
            else ("degraded" if degraded else "ok")
        )
        return report

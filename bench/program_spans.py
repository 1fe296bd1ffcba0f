"""The program's own host spans in a traced run, and the interval arithmetic
their readers share.

``SearchServer``'s worker thread names each phase of its loop with a
``jax.profiler.TraceAnnotation`` (``serve.idle``, ``serve.hold``,
``serve.hold.inflight``, ``serve.coalesce``, ``serve.stage``,
``serve.dispatch[<bucket>]``, ``serve.account``, ``serve.ready``,
``serve.fetch``, ``serve.scatter``, ``serve.release``), and
``Index.search`` its body (``index.search``).  They
lie on the device trace's clock.  ``xplane.load`` keeps only the benchmark's
own spans and ``serve.dispatch[...]``; this module reads every ``serve.*``
and ``index.*`` event of the same ``.xplane.pb``, with the host thread line
it ran on, once per traced run.

A program without these spans gives no such events, and its readers then
return None.  All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import os

import xplane

__all__ = ["PREFIXES", "TRACE_DIR", "idle", "label_at", "load", "of_run",
           "overlap_ns", "total_ns", "worker"]

PREFIXES = ("serve.", "index.")
# Where ``run.py`` has the profiler write the traced window.
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".trace")


def load(path: str):
    """[(start, end, name, line)] of the program's spans in one
    ``.xplane.pb``, sorted; ``line`` names the host thread the span ran on
    as ``(plane name, index of the line in the plane)``."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    start = int(ev.start_ns)
                    spans.append((start, start + int(ev.duration_ns),
                                  ev.name, (plane.name, i)))
    return sorted(spans)


def of_run(ctx):
    """The program's spans of the run ``ctx`` describes, read from the
    trace the first time a reader asks and kept on ``ctx``; None where the
    run has no trace with device ops (an untraced run, or one off the
    chip)."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    if getattr(ctx, "program_spans", None) is None:
        ctx.program_spans = load(xplane.find(TRACE_DIR))
    return ctx.program_spans


def worker(spans):
    """The spans of the thread lines that hold a ``serve.coalesce`` span:
    the server's worker, with the ``index.search`` spans inside its
    dispatches.  Empty where the worker names no phase but its dispatch."""
    lines = {sp[3] for sp in spans if sp[2] == "serve.coalesce"}
    return [sp for sp in spans if sp[3] in lines]


def total_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end, ...)`` intervals within
    ``[lo, hi)``."""
    return sum(e - s for s, e in xplane.merge(
        xplane.clip([iv[:2] for iv in intervals], lo, hi)))


def idle(trace, lo: int, hi: int):
    """The stretches of ``[lo, hi)`` in which no op ran on the first chip,
    sorted and disjoint."""
    dev = trace.devices[0] if trace.devices else None
    busy = xplane.merge(xplane.clip(
        [(s, e) for s, e, _, _, d in trace.ops if d == dev], lo, hi))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


def overlap_ns(a, b) -> int:
    """Length of the intersection of two unions of intervals."""
    a = xplane.merge([iv[:2] for iv in a])
    b = xplane.merge([iv[:2] for iv in b])
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def label_at(spans, t: int) -> str:
    """Name of the innermost span that covers ``t``; ``"none"`` if none
    does."""
    cover = [sp for sp in spans if sp[0] <= t < sp[1]]
    return min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover else "none"

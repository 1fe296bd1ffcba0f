"""Reduction of a profiler trace (``.xplane.pb``) to what the readers need.

Reads the trace with ``jax.profiler.ProfileData`` alone.  Keeps:

- the device operations: every event on the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, named by its HLO instruction (the event's
  name up to `` = ``), and whether it is a Mosaic custom call (a Pallas
  kernel, ``custom_call_target="tpu_custom_call"``).  Ops nest: a
  ``while`` op spans the ops of its body;
- the benchmark's own host spans: events whose name starts with
  ``bench.`` (``jax.profiler.TraceAnnotation`` around each call into the
  program), and the server's ``serve.dispatch[...]`` annotations;
- JAX's host events of each copy between host and device
  (``TRANSFERS``).

All times are nanoseconds on the trace's one clock.  The functions below
are pure, so the tests run them on a small recorded trace.
"""
from __future__ import annotations

import glob
import os

__all__ = ["Trace", "busy_ns", "find", "idle_gaps", "idle_percent", "load",
           "merge", "op_ns", "self_ns", "top_ops", "window"]

HOST_PREFIXES = ("bench.", "serve.dispatch")
TRANSFERS = ("np.asarray(jax.Array)", "DevicePutWithSharding")
MOSAIC = 'custom_call_target="tpu_custom_call"'


class Trace:
    """Device ops and host spans of one traced window.

    ``ops``: ``(start, end, name, custom, device)``; ``spans`` and
    ``transfers``: ``(start, end, name)``.
    """

    def __init__(self, ops, spans, devices, transfers=()):
        self.ops = sorted(ops)
        self.spans = sorted(spans)
        self.devices = devices
        self.transfers = sorted(transfers)


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str, device_ids=None) -> Trace:
    """The :class:`Trace` of one ``.xplane.pb``; ``device_ids`` limits the
    device planes to those chips (default: every TPU plane)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans, devices, transfers = [], [], [], []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev = int(name.rsplit(":", 1)[1].split()[0])
            if device_ids is not None and dev not in device_ids:
                continue
            devices.append(dev)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append((start, start + int(ev.duration_ns),
                                ev.name.split(" = ", 1)[0].lstrip("%"),
                                MOSAIC in ev.name, dev))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    kept = (spans if ev.name.startswith(HOST_PREFIXES)
                            else transfers if ev.name in TRANSFERS else None)
                    if kept is not None:
                        start = int(ev.start_ns)
                        kept.append((start, start + int(ev.duration_ns),
                                     ev.name))
    return Trace(ops, spans, sorted(devices), transfers)


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _union_ns(trace: Trace, lo: int, hi: int, dev, keep) -> int:
    """Nanoseconds of ``[lo, hi)`` in which an op of chip ``dev`` that
    ``keep(name, custom)`` keeps ran."""
    iv = [(s, e) for s, e, name, c, d in trace.ops
          if d == dev and keep(name, c)]
    return sum(e - s for s, e in merge(clip(iv, lo, hi)))


def busy_ns(trace: Trace, lo: int, hi: int, *, custom=None) -> int:
    """Nanoseconds of ``[lo, hi)`` in which some device op ran, averaged
    over the chips.  ``custom=True`` counts only the time a Mosaic kernel
    ran; ``custom=False`` only the busy time in which none did."""
    total = 0
    for dev in trace.devices:
        busy = _union_ns(trace, lo, hi, dev, lambda name, c: True)
        kernels = _union_ns(trace, lo, hi, dev, lambda name, c: c)
        total += {None: busy, True: kernels, False: busy - kernels}[custom]
    return total // max(1, len(trace.devices))


def op_ns(trace: Trace, lo: int, hi: int, named) -> int:
    """Nanoseconds of ``[lo, hi)`` in which an op whose name satisfies
    ``named(name)`` ran, averaged over the chips."""
    total = sum(_union_ns(trace, lo, hi, dev, lambda name, c: named(name))
                for dev in trace.devices)
    return total // max(1, len(trace.devices))


def idle_percent(trace: Trace, lo: int, hi: int) -> float:
    """100 · (1 - busy / window) over ``[lo, hi)``."""
    return 100.0 * (1.0 - busy_ns(trace, lo, hi) / (hi - lo))


def self_ns(ops):
    """Each op's own time: its duration less that of the ops nested in it
    (a ``while`` op less its body), for ops of one chip sorted by start."""
    own = [e - s for s, e, *_ in ops]
    stack = []
    for i, (s, e, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10):
    """[(name, seconds)] of the ``n`` op names with the most own device
    time in ``[lo, hi)`` (nested ops not counted twice), over all chips."""
    by_name = {}
    for dev in trace.devices:
        ops = [(max(s, lo), min(e, hi), name) for s, e, name, _, d
               in trace.ops if d == dev and e > lo and s < hi]
        ops.sort(key=lambda op: (op[0], -op[1]))
        for op, ns in zip(ops, self_ns(ops)):
            by_name[op[2]] = by_name.get(op[2], 0) + ns
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: int, hi: int, n: int = 10):
    """[(label, seconds)] of the ``n`` longest stretches of ``[lo, hi)`` in
    which no op ran on the first chip, each labelled by the innermost
    host span that covers the gap's middle (``"none"`` if none does)."""
    dev = trace.devices[0] if trace.devices else None
    busy = merge(clip([(s, e) for s, e, _, _, d in trace.ops if d == dev],
                      lo, hi))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        cover = [sp for sp in trace.spans if sp[0] <= mid < sp[1]]
        label = min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover else "none"
        out.append([label, (e - s) / 1e9])
    return out


def window(trace: Trace, name: str = "bench.window"):
    """(start, end) of the host span ``name``, which the harness puts
    around the traced window."""
    for s, e, span in trace.spans:
        if span == name:
            return s, e
    raise RuntimeError(f"no {name!r} span in the trace")

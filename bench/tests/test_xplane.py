"""The trace reduction: busy union, idle share, kernel against other op
time, own time of nested ops, and idle gaps labelled by host spans."""
import json
import os

import numpy as np
import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    # One chip.  A while op [0, 100) holds a kernel [10, 60) and an op
    # [60, 70); one more op [120, 130).  Host: the window [0, 200), a
    # search [0, 15), a fetch [15, 200).
    ops = [(0, 100, "while.1", False, 0), (10, 60, "kernel.1", True, 0),
           (60, 70, "pad.1", False, 0), (120, 130, "copy.1", False, 0)]
    spans = [(0, 200, "bench.window"), (0, 15, "bench.search"),
             (15, 200, "bench.fetch")]
    return xplane.Trace(ops, spans, [0])


def test_busy_is_the_union_and_splits_into_kernel_and_other_time():
    t = synthetic()
    lo, hi = xplane.window(t)
    assert (lo, hi) == (0, 200)
    assert xplane.busy_ns(t, lo, hi) == 110
    assert xplane.busy_ns(t, lo, hi, custom=True) == 50
    assert xplane.busy_ns(t, lo, hi, custom=False) == 60
    assert xplane.idle_percent(t, lo, hi) == pytest.approx(45.0)
    # clipped to a part of the window
    assert xplane.busy_ns(t, 50, 125) == 55


def test_top_ops_count_nested_time_once():
    t = synthetic()
    top = dict(xplane.top_ops(t, 0, 200))
    assert top == {"kernel.1": 50e-9, "while.1": 40e-9, "pad.1": 10e-9,
                   "copy.1": 10e-9}
    assert sum(top.values()) == pytest.approx(xplane.busy_ns(t, 0, 200) / 1e9)


def test_idle_gaps_longest_first_labelled_by_the_innermost_span():
    t = synthetic()
    assert xplane.idle_gaps(t, 0, 200) == [["bench.fetch", 70e-9],
                                           ["bench.fetch", 20e-9]]
    assert xplane.idle_gaps(t, 0, 200, n=1) == [["bench.fetch", 70e-9]]


def test_merge_and_busy_over_two_chips():
    assert xplane.merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    ops = [(0, 10, "a", True, 0), (0, 30, "a", True, 1)]
    t = xplane.Trace(ops, [], [0, 1])
    assert xplane.busy_ns(t, 0, 40) == 20      # averaged over the chips


def test_named_op_time_is_a_union_averaged_over_two_chips():
    # chip 0: an all-gather's start [0, 10) and done [5, 20) overlap, a
    # scan [20, 50); chip 1: a fused all-gather [0, 40)
    ops = [(0, 10, "all-gather-start.1", False, 0),
           (5, 20, "all-gather-done.1", False, 0),
           (20, 50, "fusion.3", False, 0), (0, 40, "all-gather.2", False, 1)]
    t = xplane.Trace(ops, [], [0, 1])
    gathers = lambda name: "all-gather" in name  # noqa: E731
    assert xplane.op_ns(t, 0, 100, gathers) == (20 + 40) // 2
    assert xplane.op_ns(t, 10, 30, gathers) == (10 + 20) // 2
    assert xplane.op_ns(t, 0, 100, lambda name: False) == 0


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.json")) as f:
        return xplane.load(path), json.load(f)


def test_recorded_trace_reduces_as_on_the_chip(recorded):
    t, want = recorded
    lo, hi = xplane.window(t)
    assert t.devices == [0]
    assert any(c for *_, c, _ in t.ops), "no Mosaic kernel in the trace"
    assert (hi - lo) / 1e9 == want["device"]["window_s"]
    assert xplane.busy_ns(t, lo, hi) / 1e9 == want["device"]["busy_s"]
    assert (xplane.busy_ns(t, lo, hi, custom=True)
            + xplane.busy_ns(t, lo, hi, custom=False)
            == xplane.busy_ns(t, lo, hi))
    assert xplane.top_ops(t, lo, hi) == want["breakdown"]["device_ops"]
    assert xplane.idle_gaps(t, lo, hi) == want["breakdown"]["idle_gaps"]
    # every fetch of results copies them to the host inside bench.fetch
    fetches = [sp for sp in t.spans if sp[2] == "bench.fetch"]
    copies = [tr for tr in t.transfers if tr[2] == "np.asarray(jax.Array)"]
    assert copies and all(any(f[0] <= c[0] and c[1] <= f[1] for f in fetches)
                          for c in copies if lo <= c[0] and c[1] <= hi)
    labels = {label for label, _ in want["breakdown"]["idle_gaps"]}
    assert labels <= {"bench.window", "bench.search", "bench.fetch", "none"}


def test_recorded_busy_matches_a_sweep_count(recorded):
    """The union, counted a second way: sweep the op boundaries and add
    the stretches in which at least one op is open."""
    t, _ = recorded
    lo, hi = xplane.window(t)
    edges = sorted([(max(s, lo), 1) for s, e, *_ in t.ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e, *_ in t.ops
                      if e > lo and s < hi])
    times = np.array([x for x, _ in edges])
    open_ops = np.cumsum([d for _, d in edges])
    busy = int(np.sum(np.diff(times)[open_ops[:-1] > 0]))
    assert xplane.busy_ns(t, lo, hi) == busy

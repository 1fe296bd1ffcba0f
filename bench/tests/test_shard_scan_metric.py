"""``shard_scan_pallas.batch``: the share of sharded dispatches whose shards
ran the fused Pallas kernel, read from the program's counter; nothing
without the counter or off the chip."""
import pytest

import run
import xplane
from repro.search import backends

METRIC = "shard_scan_pallas.batch"


def context(ops):
    window = (0, 1000)
    trace = xplane.Trace(ops, [(*window, "bench.window")], [0])
    return run.Context(trace=trace, span=window, readings={})


ON_CHIP = [(100, 300, "fusion.1", False, 0)]


@pytest.fixture
def counts(monkeypatch):
    monkeypatch.setattr(backends, "SHARD_SCAN_COUNTS",
                        {"pallas": 3, "xla": 1}, raising=False)
    monkeypatch.setitem(backends.DISPATCH_COUNTS, "sharded", 4)


@pytest.mark.parametrize("ops, want", [(ON_CHIP, 75.0), ([], None)],
                         ids=["on_chip", "no_device_ops"])
def test_reads_the_pallas_share_of_sharded_dispatches(counts, ops, want):
    assert run.read_metric(METRIC, context(ops)) == want


def test_reads_nothing_without_the_counter(counts, monkeypatch):
    monkeypatch.delattr(backends, "SHARD_SCAN_COUNTS")
    assert run.read_metric(METRIC, context(ON_CHIP)) is None


def test_reads_nothing_without_sharded_dispatches(counts, monkeypatch):
    monkeypatch.setitem(backends.DISPATCH_COUNTS, "sharded", 0)
    assert run.read_metric(METRIC, context(ON_CHIP)) is None

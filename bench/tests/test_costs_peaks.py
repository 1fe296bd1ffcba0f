"""The work and bytes of a search from a configuration's shapes, and the
peaks table."""
import json
import os

import pytest

import costs
import peaks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


V5E = peaks.peaks_for("TPU v5 lite")


def test_sift1m_f32_is_compute_bound_at_the_bf16_peak():
    cost = costs.search_cost(config("sift1m-f32"), 10_000)
    assert cost["ops"] == 2 * 10_000 * 1_000_000 * 128
    seconds, bound = costs.least_time(cost, V5E)
    assert bound == "compute"
    assert seconds == pytest.approx(2.56e12 / 197e12)        # 13.0 ms
    # the database once (512 MB) plus queries and results: 0.63 ms
    assert cost["bytes"] / V5E["hbm_bytes_per_s"] == pytest.approx(
        0.632e-3, rel=0.01)


def test_glove_int8_is_compute_bound_at_the_int8_peak():
    cost = costs.search_cost(config("glove1.2m-int8"), 10_000)
    seconds, bound = costs.least_time(cost, V5E)
    assert bound == "compute"
    assert seconds == pytest.approx(2 * 10_000 * 1_183_514 * 100 / 393e12)
    assert seconds == pytest.approx(6.0e-3, rel=0.01)
    # int8 rows (D 100, not the padded 128), queries, results and the
    # rescore's least gather of k float32 rows per query
    want = 1_183_514 * 100 + 10_000 * 100 * 4 + 10_000 * 10 * 8 \
        + 10_000 * 10 * 100 * 4
    assert cost["bytes"] == want


def test_least_time_on_four_chips_is_a_quarter_of_one():
    cost = costs.search_cost(config("deep50m-f32"), 10_000)
    assert cost["ops"] == 2 * 10_000 * 50_000_000 * 96
    one, bound_one = costs.least_time(cost, V5E)
    four, bound_four = costs.least_time(cost, V5E, chips=4)
    assert bound_one == bound_four == "compute"
    assert four == pytest.approx(one / 4)
    assert four == pytest.approx(9.6e13 / (4 * 197e12))      # 0.122 s
    # one-chip readings are what they were
    assert costs.least_time(cost, V5E, chips=1) == (one, bound_one)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_every_peak_names_its_source():
    for kind, entry in peaks.PEAKS.items():
        assert entry["source"] and entry["flops"]["bf16"] > 0, kind

"""The bursty served mix: its schedule, and its cell rehearsed end to end
on the CPU."""
import numpy as np
import pytest

import run
import traffic
from repro.search import telemetry

OPEN = traffic.load_module(traffic.HERE + "/kinds", "open_loop")
CELL = "sift1m-f32.served-bursty"
TINY = {"config": {"n": 4096, "queries": 256},
        "mix": {"rate_per_s": 50, "query_pool_rows": 1024}}


def test_every_request_is_due_inside_a_burst_at_the_mean_rate():
    mix = traffic.load_mix("served_bursty")
    on, off = mix["on_off_s"]
    seconds = 51.0
    due, sizes, _ = OPEN.schedule(mix, seconds, 2**33 + 7,
                                  mix["query_pool_rows"])
    assert np.all(sizes == 1)
    assert np.all(due % (on + off) < on) and due[-1] < seconds
    assert len(due) / seconds == mix["rate_per_s"] == 6000
    # 25 whole bursts and the first half of a 26th: each at twice the mean
    bursts = np.bincount((due // (on + off)).astype(int))
    assert len(bursts) == 26
    assert abs(np.median(bursts[:25]) / on - 2 * mix["rate_per_s"]) < 200


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_rehearsal_of_the_cell(traced):
    # The counters are the process's: start from none, as a run does.
    telemetry.reset_all()
    result = run.run(CELL, 2**33 + 23, 1.0, traced, require_chip=False,
                     overrides=TINY)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = ({"batch_rows.served", "transfer_max_ms.served",
             "latency_p99_ms.served"} if traced
            else {"served_p50_ms", "setup_s"})
    assert set(result["metrics"]) == want

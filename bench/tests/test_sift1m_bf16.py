"""sift1m-bf16 against the plain reference at a size a test run holds, its
cell rehearsed end to end on the CPU, and the reader of
``scan_bf16_operands.batch``."""
import numpy as np
import pytest

import checks
import reference
import run
from repro.search import Index, backends, telemetry

CELL = "sift1m-bf16.batch"
TINY = {"config": {"n": 4096, "queries": 256}}


@pytest.fixture(autouse=True)
def fresh_counters():
    """The counters are the process's: each test starts from none, as a
    run does, and leaves none behind for the next test in the process."""
    telemetry.reset_all()
    yield
    telemetry.reset_all()


def _config(n):
    return {**run.load_json(run.BENCH, "configs", "sift1m-bf16.json"), "n": n}


@pytest.fixture(scope="module")
def draw65k():
    """65,536 x 128 rows and 512 queries, from the benchmark's own draw."""
    cfg = _config(65536)
    draw = run.make_draw(13)
    db = draw("database", [(cfg["n"], cfg["d"])])[0]
    queries = np.asarray(draw("queries", [(512, cfg["d"])])[0])
    _, ref_ids = reference.top_k(queries, db, cfg["k"], cfg["metric"])
    return cfg, db, queries, ref_ids


def _judge(cfg, queries, vals, ids, db, ref_ids):
    numbers = checks.compare(cfg, queries, vals, ids, db, ref_ids)
    return numbers, checks.judge(numbers, {n: cfg["limits"][n]
                                           for n in numbers})


def test_pallas_bf16_tier_meets_the_limits(draw65k):
    """The one-pass bf16 scan (Pallas, interpret mode) with its exact
    rescore: recall at the target, values within the limit, no bad id."""
    cfg, db, queries, ref_ids = draw65k
    index = Index.build(db, metric=cfg["metric"], k=cfg["k"],
                        recall_target=cfg["recall_target"],
                        storage=cfg["storage"], backend="pallas",
                        cluster=cfg["cluster"])
    vals, ids = index.search(queries)
    numbers, judged = _judge(cfg, queries, vals, ids, db, ref_ids)
    assert all(j["ok"] for j in judged.values()), judged
    assert numbers["bad_ids"] == 0


@pytest.mark.parametrize("fault", ["scan_values_without_rescore",
                                   "reference_at_high"])
def test_a_planted_fault_fails_value_err(draw65k, fault):
    """Values from the bf16 scan with the rescore left out, and the
    reference at ``high`` (three bf16 passes) in the program's place, both
    fail ``value_err``."""
    cfg, db, queries, ref_ids = draw65k
    if fault == "reference_at_high":
        vals, ids = reference.top_k(queries, db, cfg["k"], cfg["metric"],
                                    precision="high")
    else:
        index = Index.build(db, metric=cfg["metric"], k=cfg["k"],
                            recall_target=cfg["recall_target"],
                            storage=cfg["storage"], backend="xla",
                            cluster=cfg["cluster"], rescore=False)
        vals, ids = index.search(queries)
    _, judged = _judge(cfg, queries, vals, ids, db, ref_ids)
    assert not judged["value_err"]["ok"], judged


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_rehearsal_of_the_cell(traced):
    result = run.run(CELL, 2**33 + 19, 1.0, traced, require_chip=False,
                     overrides=TINY)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {"recall.batch"} if traced else {"batch_qps", "setup_s"}
    assert set(result["metrics"]) == want


def test_scan_bf16_operands_reads_every_pallas_dispatch():
    """On the Pallas backend (interpret mode here) every dispatch of the
    cell, set-up included, gives the scan bf16 operands."""
    tiny = {"config": {**TINY["config"], "backend": "pallas"}}
    result = run.run(CELL, 5, 1.0, True, require_chip=False, overrides=tiny)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["scan_bf16_operands.batch"]["value"] == 100.0


def test_scan_bf16_operands_reads_nothing_without_the_counter(monkeypatch):
    metric = "scan_bf16_operands.batch"
    monkeypatch.setitem(backends.DISPATCH_COUNTS, "pallas", 3)
    assert run.read_metric(metric, None) is not None
    monkeypatch.delattr(backends, "SCAN_OPERAND_COUNTS")
    assert run.read_metric(metric, None) is None

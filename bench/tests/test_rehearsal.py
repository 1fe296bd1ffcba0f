"""Each traffic mix end to end at a tiny size on the CPU, and the command
line's refusal to measure off the chip."""
import os
import subprocess
import sys

import pytest

import run

BENCH = run.BENCH
CELLS = ["sift1m-f32.batch", "glove1.2m-int8.batch", "sift1m-f32.served"]
# The four-chip cell rehearses on four virtual devices (test_sharded.py).
SHARDED = ["deep50m-f32.sharded4"]
TINY = {"config": {"n": 4096, "queries": 256},
        "mix": {"rate_per_s": 50, "query_pool_rows": 1024}}


@pytest.mark.parametrize("cell", CELLS + SHARDED)
def test_command_line_off_the_chip_exits_nonzero_without_a_result(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not measured" in proc.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    """A checkout of BENCHMARK.json and bench/ without the program."""
    import shutil

    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**{k: v for k, v in os.environ.items()
                              if k != "PYTHONPATH"}, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_tiny_rehearsal_of_each_mix(cell, traced):
    result = run.run(cell, 2**33 + 17, 1.0, traced, require_chip=False,
                     overrides=TINY)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    names = set(result["metrics"])
    batch = run.load_cell(cell)[3]["kind"] == "closed_batch"
    if not traced:
        want = ({"batch_qps", "setup_s"} if batch
                else {"served_p50_ms", "setup_s"})
        assert names == want
    else:
        # Off the chip the trace holds no device ops: only the metrics
        # that need none are read.
        want = ({"recall.batch"} if batch
                else {"serve_host_ms.served", "batch_rows.served",
                      "transfer_max_ms.served", "latency_p99_ms.served"})
        assert names == want

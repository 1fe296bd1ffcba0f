"""The four-chip cell on four virtual CPU devices (``four_devices.py``, in
a process of its own), and the refusal of a cell on more chips than its
configuration shards over or than are found."""
import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "deep50m-f32.sharded4"


@pytest.fixture(scope="module")
def four():
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "four_devices.py")], cwd=run.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_rehearsal_of_the_sharded_cell(four, traced):
    result = four["traced" if traced else "untraced"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["last_key"] == "checks"
    assert result["count"] == 4
    # drawn straight into four shards of a quarter each
    assert result["db"] == {"rows": [1024] * 4, "devices": 4}
    # Off the chip the trace holds no device ops.
    want = ["recall.batch"] if traced else ["batch_qps", "setup_s"]
    assert result["metrics"] == want


@pytest.mark.parametrize("fault", ["answer_altered", "exchange_left_out",
                                   "half_the_batch_left_out"])
def test_a_broken_sharded_search_is_not_correct(four, fault):
    result = four["faults"][fault]
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_sharded_reference_equals_the_one_device_reference(four, metric):
    parity = four["parity"][metric]
    assert parity["sharded"]
    assert parity["ties_across_shards"] > 0
    assert parity["ids_equal"] and parity["values_equal"], parity
    assert parity["rows_equal"]


def test_sharded_control_at_high_precision_fails_value_err(four):
    control = four["control"]
    assert control["highest"]["ok"] is True, control
    assert control["high"]["ok"] is False, control


def test_a_cell_on_chips_its_configuration_does_not_shard_over_exits_2(
        monkeypatch, capsys):
    bench, cell, _, mix = run.load_cell(CELL)
    _, _, one_chip, _ = run.load_cell("sift1m-f32.batch")
    assert cell["chips"] == 4
    monkeypatch.setattr(run, "load_cell",
                        lambda name: (bench, cell, one_chip, mix))
    rc = run.main(["--workload", CELL, "--seed", "3", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out.strip() == ""
    assert "holds the index on one" in out.err


def test_a_cell_on_more_chips_than_found_is_refused():
    _, cell, cfg, _ = run.load_cell(CELL)
    with pytest.raises(run.NoChip, match="found 1"):
        run.check_device(cell, cfg, False)

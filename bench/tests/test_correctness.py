"""The comparison that decides ``correct`` fails the control and every
fault a search cell can have, at a size a test run holds."""
import numpy as np
import pytest

import checks
import reference
import run
from repro.search import Index

# Served requests arrive fast enough that the server coalesces several
# into each batch, as it does at the cell's own rate.
TINY = {"config": {"n": 4096, "queries": 256},
        "mix": {"rate_per_s": 3000, "query_pool_rows": 1024}}


@pytest.mark.parametrize("config", ["sift1m-f32", "glove1.2m-int8",
                                    "deep50m-f32"])
def test_control_at_high_precision_fails_value_err(config):
    """The reference at ``high`` (three bf16 passes) in the program's
    place fails; the reference at ``highest`` passes."""
    import jax

    cfg = run.load_json(run.BENCH, "configs", f"{config}.json")
    cfg = {**cfg, "n": 65536}
    draw = run.make_draw(11)
    db = draw("database", [(cfg["n"], cfg["d"])])[0]
    queries = np.asarray(draw("queries", [(512, cfg["d"])])[0])
    _, ref_ids = reference.top_k(queries, db, cfg["k"], cfg["metric"])
    limits = cfg["limits"]
    for precision, passes in (("highest", True), ("high", False)):
        vals, ids = reference.top_k(queries, db, cfg["k"], cfg["metric"],
                                    precision=precision)
        numbers = checks.compare(cfg, queries, vals, ids, db, ref_ids)
        judged = checks.judge(numbers, {n: limits[n] for n in numbers})
        assert all(j["ok"] for j in judged.values()) is passes, (
            precision, judged)
    assert jax.default_backend() == "cpu"


def _half_left_out(search):
    """Only the first half of each batch is searched; the other half is
    handed the first half's answers."""
    import jax.numpy as jnp

    def faulty(self, queries):
        half = max(1, queries.shape[0] // 2)
        vals, ids = search(self, queries[:half])
        rest = queries.shape[0] - half
        reps = -(-rest // half) + 1
        return (jnp.tile(vals, (reps, 1))[:queries.shape[0]],
                jnp.tile(ids, (reps, 1))[:queries.shape[0]])
    return faulty


def _answer_altered(search):
    def faulty(self, queries):
        vals, ids = search(self, queries)
        return vals, ids.at[:, -1].set((ids[:, -1] + 1) % self.capacity)
    return faulty


FAULTS = {"half_the_batch_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["sift1m-f32.batch", "glove1.2m-int8.batch",
                                  "sift1m-f32.served"])
def test_a_broken_search_path_is_not_correct(monkeypatch, cell, fault):
    from repro.search.index import SearchResult

    search = Index.search

    def broken(self, queries):
        vals, ids = FAULTS[fault](lambda s, q: tuple(search(s, q)))(
            self, queries)
        return SearchResult(vals, ids)

    monkeypatch.setattr(Index, "search", broken)
    result = run.run(cell, 5, 1.0, False, require_chip=False, overrides=TINY)
    assert result["correct"] is False, result["checks"]

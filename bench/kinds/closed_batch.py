"""``closed_batch``: one client runs ``Index.search`` on M-query batches
back to back and fetches each result to the host before the next search;
M is the configuration's ``queries``.  Each search takes the next of
``pool`` query batches drawn on the device at set-up; ``warm_searches``
searches warm the shapes up."""
from __future__ import annotations

import time

import numpy as np

from traffic import annotate


class Client:
    def __init__(self, cfg, mix, index, draw, seed):
        self.cfg, self.mix, self.index = cfg, mix, index
        self.seed = seed
        m, d, pool = cfg["queries"], cfg["d"], mix["pool"]
        self.pool = draw("queries", [(m, d)] * pool)
        self.outputs = []      # (pool slot, values, ids) per search
        self.readings = {}

    def _search(self, slot: int):
        with annotate("bench.search"):
            out = self.index.search(self.pool[slot])
        with annotate("bench.fetch"):
            vals, ids = np.asarray(out.values), np.asarray(out.indices)
        return vals, ids

    def setup(self) -> None:
        for _ in range(self.mix["warm_searches"]):
            self._search(0)

    def run(self, seconds: float) -> None:
        t0 = time.perf_counter()
        i = 0
        while True:
            slot = i % len(self.pool)
            vals, ids = self._search(slot)
            self.outputs.append((slot, vals, ids))
            i += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        m = self.cfg["queries"]
        self.readings = {
            "searches": i, "queries": i * m, "window_s": elapsed,
            "attempted": i, "failed": 0, "unanswered": 0,
        }

    def sample(self, rows: int):
        """(queries, values, ids) of ``rows`` answered query rows drawn
        from the seed across every search of the window."""
        rng = np.random.default_rng(self.seed % 2**63)
        m = self.cfg["queries"]
        total = len(self.outputs) * m
        pick = np.sort(rng.choice(total, size=min(rows, total), replace=False))
        qs, vs, ids = [], [], []
        for flat in pick:
            slot, v, i = self.outputs[flat // m]
            qs.append((slot, flat % m))
            vs.append(v[flat % m])
            ids.append(i[flat % m])
        pools = {s: np.asarray(self.pool[s]) for s in {s for s, _ in qs}}
        queries = np.stack([pools[s][r] for s, r in qs])
        return queries, np.stack(vs), np.stack(ids)

    def close(self) -> None:
        self.pool = None

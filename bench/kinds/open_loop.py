"""``open_loop``: independent clients send requests to ``SearchServer`` at
``rate_per_s`` on an open loop, whatever the server does.

- Rows per request are drawn from the table ``rows``, a list of
  ``[rows, weight]`` pairs; each request asks for ``k`` neighbours, its
  rows taken from a pool of ``query_pool_rows`` drawn on the device.
- Arrivals are Poisson.  With ``on_off_s: [on, off]`` they come only in
  the ``on`` seconds of each period, at the rate that keeps the mean at
  ``rate_per_s``.
- The sizes and the gaps between arrivals are one fixed set, drawn from
  ``schedule_seed``; the run's seed only orders them and picks the query
  rows, so every seed offers the same work.
- A request is timed from when it was due to when the client sees its
  answer, so a generator that runs late shows in the latency.
"""
from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

from traffic import DRAIN_S

# Requests whose answers are kept for the comparison: drawn from the seed
# before the window, so the client holds few answers.
KEEP_REQUESTS = 2000


def schedule(mix: dict, seconds: float, seed: int, pool_rows: int):
    """(due offsets, rows, first pool row) of every request: one fixed
    multiset of sizes and gaps, in the seed's order."""
    n = int(round(mix["rate_per_s"] * seconds))
    base = np.random.default_rng(mix["schedule_seed"])
    table = np.asarray(mix["rows"], float)
    sizes = base.choice(table[:, 0].astype(int), size=n,
                        p=table[:, 1] / table[:, 1].sum())
    gaps = base.exponential(1.0, size=n + 1)
    on, off = mix.get("on_off_s", (seconds, 0.0))
    gaps *= seconds * on / (on + off) / gaps.sum()
    rng = np.random.default_rng(seed % 2**63)
    sizes = sizes[rng.permutation(n)]
    live = np.cumsum(gaps[rng.permutation(n + 1)])[:n]
    due = (live // on) * (on + off) + live % on
    starts = rng.integers(0, pool_rows - sizes + 1)
    return due, sizes, starts


class Client:
    def __init__(self, cfg, mix, index, draw, seed):
        self.cfg, self.mix, self.index = cfg, mix, index
        self.seed = seed
        self.rows_pool = draw("queries", [(mix["query_pool_rows"], cfg["d"])])[0]
        self.server = None
        self.readings = {}
        self.answers = {}

    def setup(self) -> None:
        from repro.search import SearchServer, ServeConfig

        self.host_rows = np.asarray(self.rows_pool)
        self.rows_pool = None
        self.server = SearchServer(self.index, ServeConfig(), warmup=True)

    def run(self, seconds: float) -> None:
        from repro.search.serve import QueueFull

        due, sizes, starts = schedule(self.mix, seconds, self.seed,
                                      self.host_rows.shape[0])
        n, k = len(due), self.mix["k"]
        rng = np.random.default_rng((self.seed + 1) % 2**63)
        longest = int(np.argmax(sizes))
        self.keep = [longest] + [int(i) for i in rng.permutation(n)
                                 if i != longest][:KEEP_REQUESTS - 1]
        keep = set(self.keep)
        seen = np.full(n, np.nan)
        late = np.zeros(n)
        failed = np.zeros(n, bool)
        handed = queue.Queue()
        backlog = {}
        server, rows = self.server, self.host_rows
        t0 = time.perf_counter()
        give_up = t0 + seconds + DRAIN_S

        def generate():
            mid = False
            for i in range(n):
                at = t0 + due[i]
                wait = at - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if not mid and due[i] >= seconds / 2:
                    backlog["mid"] = server.pending_rows
                    mid = True
                now = time.perf_counter()
                late[i] = now - at
                try:
                    ticket = server.submit(
                        rows[starts[i]:starts[i] + sizes[i]], k=k)
                except QueueFull:
                    failed[i] = True
                    ticket = None
                handed.put((i, ticket))
            backlog["end"] = server.pending_rows
            handed.put(None)

        def collect():
            while True:
                item = handed.get()
                if item is None:
                    return
                i, ticket = item
                if ticket is None:
                    continue
                try:
                    res = ticket.result(
                        timeout=max(0.0, give_up - time.perf_counter()))
                except TimeoutError:
                    continue
                except Exception:  # a refused or failed request
                    failed[i] = True
                    continue
                seen[i] = time.perf_counter()
                if i in keep:
                    self.answers[i] = res

        pauses = []

        def on_gc(phase, info):
            if phase == "start":
                pauses.append([time.perf_counter(), info["generation"]])
            elif pauses:
                pauses[-1][0] = time.perf_counter() - pauses[-1][0]

        stats0 = server.stats()
        gc.callbacks.append(on_gc)
        threads = [threading.Thread(target=generate, name="bench.generate"),
                   threading.Thread(target=collect, name="bench.collect")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + DRAIN_S + 30)
        gc.callbacks.remove(on_gc)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a traffic thread did not finish")
        stats1 = server.stats()
        stopped = time.perf_counter()
        unanswered = int(np.sum(np.isnan(seen) & ~failed))
        # A failed request misses every limit: it counts at the longest
        # wait the run could see.
        latency = np.where(np.isnan(seen), stopped, seen) - (t0 + due)
        self.sizes, self.starts = sizes, starts
        self.readings = {
            "requests": n, "attempted": n, "failed": int(failed.sum()),
            "unanswered": unanswered, "latency_s": latency,
            "lateness_s": late, "backlog_rows": backlog,
            "batches": stats1["batches"] - stats0["batches"],
            "dispatched_rows": (stats1["dispatched_rows"]
                                - stats0["dispatched_rows"]),
            "traces": server.traces(),
            "window_s": seconds,
            "gc_pauses": pauses,
        }

    def sample(self, rows: int):
        """(queries, values, ids) of answered requests, in the order drawn
        from the seed before the window (the longest request first), until
        ``rows`` rows."""
        qs, vs, ids, total = [], [], [], 0
        for i in self.keep:
            if total >= rows:
                break
            if i not in self.answers:
                continue
            res = self.answers[i]
            qs.append(self.host_rows[self.starts[i]:self.starts[i] + self.sizes[i]])
            vs.append(np.asarray(res.values))
            ids.append(np.asarray(res.indices))
            total += self.sizes[i]
        if not qs:
            return None
        return np.concatenate(qs), np.concatenate(vs), np.concatenate(ids)

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None

#!/usr/bin/env python3
"""The knee sweep of a served cell: one set-up, then windows at each rate.

    python3 bench/sweep.py --workload sift1m-f32.served --seeds 5 6 7 --seconds 20 --rates 2000 4000 8000

For each rate and each seed, in this one process and on one server: the
cell's open-loop mix at that rate for ``--seconds``, the seed ordering
its arrivals and picking its rows, then a JSON line with the latency
percentiles, the rows queued at the middle and at the end of the
arrivals, how late the generator ran, failed and unanswered requests,
and the mean rows per dispatch.  The knee is read from the readings that
the server's rare long copy stalls leave alone: the median latency, the
backlog and the generator's median lateness.  Rates above the first at
which every seed's median latency passes ``--stop-ms`` are not run.
"""
import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--stop-ms", type=float, default=100.0)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = run.load_cell(args.workload)
    used, _ = run.check_device(cell, cfg, True)
    _, _, client = run.build(cfg, mix, args.seeds[0], used)
    for rate in args.rates:
        p50s = []
        for seed in args.seeds:
            client.mix = {**mix, "rate_per_s": rate}
            client.seed = seed
            client.answers = {}
            client.run(args.seconds)
            r = client.readings
            lat, late = r["latency_s"] * 1e3, r["lateness_s"] * 1e3
            p50s.append(float(np.percentile(lat, 50)))
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "requests": r["requests"],
                "p50_ms": p50s[-1],
                "p99_ms": float(np.percentile(lat, 99)),
                "max_ms": float(lat.max()),
                "backlog_rows": r["backlog_rows"],
                "late_p50_ms": float(np.percentile(late, 50)),
                "late_p99_ms": float(np.percentile(late, 99)),
                "late_max_ms": float(late.max()),
                "failed": r["failed"], "unanswered": r["unanswered"],
                "rows_per_batch": r["dispatched_rows"] / max(1, r["batches"]),
            }), flush=True)
        if min(p50s) > args.stop_ms:
            break
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

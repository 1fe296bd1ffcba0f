#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip.

    python3 bench/control.py --workload sift1m-f32.batch --seconds 3 --seeds 1 2 3

For each seed, in this one process: one run of the cell at its own sizes
and load, with a short window (``run.run``), whose compared numbers are
the program's reading; then the control, the reference at ``high`` (three
bf16 passes, ``bench/reference.py``) put in the program's place for the
same sampled queries.  ``--storage int4`` runs the program on that storage
tier instead, the nearest lower precision of its own.  Prints one JSON line
per seed, then the largest program reading and the smallest control
reading of each number.
"""
import argparse
import json
import sys

import checks
import reference
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--storage", default=None,
                    help="run the program on this storage tier")
    args = ap.parse_args(argv)
    overrides = {"config": {"storage": args.storage}} if args.storage else {}
    program, control = {}, {}

    def on_sample(cfg, queries, db, ref_ids):
        vals, ids = reference.top_k(queries, db, cfg["k"], cfg["metric"],
                                    precision="high")
        control.update(checks.compare(cfg, queries, vals, ids, db, ref_ids))

    rows = []
    for seed in args.seeds:
        control.clear()
        result = run.run(args.workload, seed, args.seconds, False,
                         overrides=overrides, on_sample=on_sample)
        got = {k: v["value"] for k, v in result["checks"].items()}
        row = {"seed": seed, "correct": result["correct"], "program": got,
               "control": dict(control), "metrics": result["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    for name in ("recall", "value_err", "bad_ids"):
        values = [r["program"][name] for r in rows]
        ctl = [r["control"][name] for r in rows]
        print(json.dumps({"number": name,
                          "program": [min(values), max(values)],
                          "control": [min(ctl), max(ctl)]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

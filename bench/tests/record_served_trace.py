#!/usr/bin/env python3
"""Records the small served TPU trace that ``test_program_spans.py`` reads.

    python3 bench/tests/record_served_trace.py     # on one TPU chip

Runs the ``served_poisson`` mix of ``sift1m-f32`` at a small size (65,536
rows) for 0.5 s under the profiler, and keeps the trace as
``data/served_small.xplane.pb`` with the result's device numbers and
per-layer metrics in ``data/served_small.json``, beside the micro-batch
count the readers divide by.
"""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
import xplane  # noqa: E402

DATA = os.path.join(HERE, "data")


def main() -> int:
    clients = []
    make_client = run.traffic.make_client

    def keep(*args):
        clients.append(make_client(*args))
        return clients[-1]

    run.traffic.make_client = keep
    result = run.run("sift1m-f32.served", 13, 0.5, True,
                     overrides={"config": {"n": 65536}})
    os.makedirs(DATA, exist_ok=True)
    shutil.copy(xplane.find(run.TRACE_DIR),
                os.path.join(DATA, "served_small.xplane.pb"))
    with open(os.path.join(DATA, "served_small.json"), "w") as f:
        json.dump({"device": result["device"],
                   "batches": clients[0].readings["batches"],
                   "metrics": result["metrics"]}, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

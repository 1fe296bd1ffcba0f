#!/usr/bin/env python3
"""Records the small TPU trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py     # on one TPU chip

Runs the ``batch`` mix of ``sift1m-f32`` at a small size (65,536 rows,
1,024 queries per search) for 0.3 s under the profiler, and keeps the
trace as ``data/small.xplane.pb`` with the harness's own reduction of it
in ``data/small.json``.
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
    result = run.run("sift1m-f32.batch", 12, 0.3, True,
                     overrides={"config": {"n": 65536, "queries": 1024}})
    os.makedirs(DATA, exist_ok=True)
    shutil.copy(xplane.find(run.TRACE_DIR),
                os.path.join(DATA, "small.xplane.pb"))
    with open(os.path.join(DATA, "small.json"), "w") as f:
        json.dump({"device": result["device"],
                   "breakdown": result["breakdown"],
                   "metrics": result["metrics"]}, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

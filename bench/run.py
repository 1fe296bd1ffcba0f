#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process finds.

    python3 bench/run.py --workload sift1m-f32.batch --seed 7 --seconds 20 --trace 0

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<mix>.json`` (driven by the client
``bench/kinds/<kind>.py`` that the mix names), and each metric's reader
in ``bench/metrics/<metric>.py``.  A run:

1. checks the device: a TPU whose ``device_kind`` has published peaks
   (``bench/peaks.py``) and as many chips as the cell asks for, else it
   exits 2 and prints no result.  A cell on more chips than one needs a
   configuration whose ``backend`` is ``"sharded"``;
2. draws the database and the queries on the device from ``--seed``,
   builds the ``Index`` and warms up the mix's shapes: ``setup_s``.  A
   ``"sharded"`` configuration draws its rows straight into their shards
   over a one-axis mesh of the cell's chips and builds the index as a
   user of such a host would: ``Index.build(..., backend="sharded")``
   then ``.shard(mesh, db_axis=DB_AXIS)``;
3. runs the mix for ``--seconds`` (under the profiler with ``--trace 1``);
4. reads the peak device memory, frees the program's state, and compares
   a sample of what the window answered with the plain reference
   (``bench/checks.py``);
5. prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
   metrics (``--trace 1``) as the last line of standard output, and each
   number compared beside its limit as the last lines of standard error.

JAX's compilation cache is the one ``repro.launch.compile_cache`` keeps
inside the checkout, so only a cell's first run there compiles.
"""
import time

START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import checks  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402

TRACE_DIR = os.path.join(BENCH, ".trace")
# Query rows of the window's answers compared with the reference.
SAMPLE_ROWS = 2000
# Data tags folded into the seed's key.
TAGS = {"database": 1, "queries": 2}
# The mesh axis a sharded configuration splits its rows over.
DB_AXIS = "db"


class NoChip(RuntimeError):
    """The process found no chip it may measure on."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str):
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = traffic.load_mix(cell["traffic"])
    return bench, cell, cfg, mix


def cell_metrics(bench: dict, cell: dict, traced: bool):
    """The metrics this cell reports: its end-to-end ones, or with a trace
    its per-layer ones."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def read_metric(name: str, ctx):
    return traffic.load_module(os.path.join(BENCH, "metrics"), name).read(ctx)


class CompileCounter:
    """Counts the programs JAX lowers and compiles inside the ``with``."""

    def __enter__(self):
        import jax.monitoring

        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


class Context:
    """What a metric reader reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def seed_key(seed: int):
    """A PRNG key that tells apart every seed, those past 32 bits too."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_draw(seed: int):
    """draw(tag, shapes, sharding=None) -> standard Gaussian float32 arrays
    of ``shapes``, made on the device in one jitted call from the seed and
    the tag; with ``sharding`` each array is made in its shards, so no
    device ever holds more than its own."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    base = seed_key(seed)

    def draw(tag, shapes, sharding=None):
        shapes = [tuple(s) for s in shapes]

        def gen(key):
            keys = jax.random.split(key, len(shapes))
            return tuple(jax.random.normal(k, s, jnp.float32)
                         for k, s in zip(keys, shapes))

        placed = (jax.jit(gen) if sharding is None else
                  jax.jit(gen, out_shardings=(sharding,) * len(shapes)))
        with TraceAnnotation("bench.draw"):
            out = placed(jax.random.fold_in(base, TAGS[tag]))
            jax.block_until_ready(out)
        return list(out)

    return draw


def finite(x):
    return x if isinstance(x, (int, bool)) or math.isfinite(x) else None


def log_served(readings: dict) -> None:
    """Earlier lines of an open-loop run: how late the generator ran, the
    backlog, the latency tail and the collector's pauses."""
    late, lat = readings["lateness_s"], readings["latency_s"] * 1e3
    log(f"generator lateness: p50 {float(np.percentile(late, 50))!r} s, "
        f"p99 {float(np.percentile(late, 99))!r} s, max {float(late.max())!r}"
        f" s; backlog rows {readings['backlog_rows']}")
    log("latency ms: " + ", ".join(
        f"p{q} {float(np.percentile(lat, q))!r}" for q in (50, 90, 95, 99))
        + f", max {float(lat.max())!r}")
    pauses = readings["gc_pauses"]
    log(f"gc in the window: {len(pauses)} collections, "
        f"{sum(g == 2 for _, g in pauses)} of generation 2, longest "
        f"{max((p for p, _ in pauses), default=0.0)!r} s")


def check_device(cell: dict, cfg: dict, require_chip: bool):
    """(devices the cell uses, their peaks); raises :class:`NoChip` or
    ``peaks.UnknownDevice`` unless the chips are there.  Off the chip
    (``require_chip=False``) the devices may be the CPU's, as many as the
    cell asks for."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if cell["chips"] > 1 and cfg["backend"] != "sharded":
        raise NoChip(f"cell {cell['name']} asks for {cell['chips']} chips; "
                     f"its configuration {cell['config']} holds the index "
                     f"on one")
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_chip:
        if platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {platform!r})")
        chip_peaks = peaks.peaks_for(kind)
    else:
        chip_peaks = peaks.PEAKS["TPU v5 lite"]
    if len(devices) < cell["chips"]:
        raise NoChip(f"cell {cell['name']} needs {cell['chips']} chips, "
                     f"found {len(devices)}")
    used = devices[:cell["chips"]]
    log(f"device {platform} {kind!r} x{len(used)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    return used, chip_peaks


def build(cfg: dict, mix: dict, seed: int, devices):
    """(database, index, client) with the client's shapes warmed up; a
    ``"sharded"`` configuration's database and index are sharded over
    ``devices``."""
    from repro.search import Index

    draw = make_draw(seed)
    sharded = cfg["backend"] == "sharded"
    if not sharded:
        db = draw("database", [(cfg["n"], cfg["d"])])[0]
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((len(devices),), (DB_AXIS,), devices=devices)
        db = draw("database", [(cfg["n"], cfg["d"])],
                  sharding=NamedSharding(mesh, P(DB_AXIS, None)))[0]
    index = Index.build(
        db, metric=cfg["metric"], k=cfg["k"],
        recall_target=cfg["recall_target"], backend=cfg["backend"],
        storage=cfg["storage"], plan=cfg["plan"], cluster=cfg["cluster"],
    )
    if sharded:
        index = index.shard(mesh, db_axis=DB_AXIS)
        log(f"rows per chip {[s.data.shape[0] for s in db.addressable_shards]}")
    plan = index.kernel_plan
    log(f"plan block_m {plan.block_m} block_n {plan.block_n} k_scan "
        f"{plan.k_scan or plan.k} query_block {plan.query_block} "
        f"E[recall] {index.expected_recall:.4f}")
    client = traffic.make_client(cfg, mix, index, draw, seed)
    client.setup()
    return db, index, client


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, overrides=None, on_sample=None) -> dict:
    """One run; returns the result line as a dict.

    ``require_chip=False`` and ``overrides`` (keys of the configuration
    and of the mix) are for rehearsals at a tiny size off the chip and for
    the control; ``on_sample(cfg, queries, db, ref_ids)`` is called with
    the sample that was compared.  The command line uses neither."""
    bench, cell, cfg, mix = load_cell(workload)
    overrides = overrides or {}
    cfg = {**cfg, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("mix", {})}

    import jax
    from jax.profiler import TraceAnnotation

    used, chip_peaks = check_device(cell, cfg, require_chip)
    platform, kind = used[0].platform, used[0].device_kind
    db, index, client = build(cfg, mix, seed, used)
    setup_s = time.monotonic() - START
    log(f"setup_s {setup_s!r}")

    if trace:
        from jax.profiler import ProfileOptions

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with CompileCounter() as counter, TraceAnnotation("bench.window"):
            client.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window: {counter.lowered} programs lowered, {counter.compiled} "
        f"compiled")
    readings = client.readings
    if "lateness_s" in readings:
        log_served(readings)

    tr, span = None, None
    if trace:
        tr = xplane.load(xplane.find(TRACE_DIR),
                         device_ids={d.id for d in used})
        span = xplane.window(tr)

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    sample = client.sample(SAMPLE_ROWS)
    client.close()
    del client, index
    if sample is not None:
        queries, vals, ids = sample
        _, ref_ids = reference.top_k(queries, db, cfg["k"], cfg["metric"])
        numbers = checks.compare(cfg, queries, vals, ids, db, ref_ids)
        if on_sample is not None:
            on_sample(cfg, queries, db, ref_ids)
    else:
        numbers = {"recall": 0.0, "value_err": float("inf"), "bad_ids": 0}
    numbers["unanswered"] = readings["unanswered"]
    judged = checks.judge(numbers, cfg["limits"])
    correct = all(j["ok"] for j in judged.values())

    ctx = Context(cfg=cfg, mix=mix, cell=cell, peaks=chip_peaks,
                  setup_s=setup_s, readings=readings, numbers=numbers,
                  trace=tr, span=span, log=log)
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": readings["attempted"],
              "failed": readings["failed"], "metrics": metrics,
              "device": device}
    if trace and tr.ops:
        lo, hi = span
        device["busy_s"] = xplane.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": xplane.top_ops(tr, lo, hi),
            "idle_gaps": xplane.idle_gaps(tr, lo, hi),
        }
    result["checks"] = {name: {"value": finite(j["value"]), "limit": j["limit"]}
                        for name, j in judged.items()}
    for name, j in judged.items():
        rel = ">=" if "min" in cfg["limits"][name] else "<="
        log(f"check {name} {j['value']!r} limit {rel} {j['limit']!r} "
            f"{'ok' if j['ok'] else 'FAILED'}")
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, peaks.UnknownDevice) as e:
        log(f"not measured: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

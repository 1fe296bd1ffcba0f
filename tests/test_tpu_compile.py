"""Compile the Pallas scan kernels for a TPU v5e that is described, not
attached.

Every other test runs the kernels in interpret mode, which accepts block
shapes and VMEM footprints that the TPU compiler (Mosaic) refuses.  Here
each kernel is lowered and compiled by the real TPU compiler at the tiles
the planner picks for the paper's Table 2 workloads on ``tpu_v5e``, and
the compiled program must contain the kernel (``tpu_custom_call``).
Nothing runs, so this says nothing about results or speed.

The topology is described inside a module-scoped fixture (never at import
time): only one process at a time may load the TPU compiler library, and
pytest-xdist workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.knn_workloads import KNN_WORKLOADS
from repro.core.binning import round_up
from repro.kernels.partial_reduce import (
    partial_reduce_fused,
    partial_reduce_packed,
)
from repro.search.plan import plan_search

_STORED = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
           "int4": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _kernel_args(workload: str, storage: str, sharding, rows=None):
    """Planner-chosen tiles and packed operand shapes, as ``Index.build``
    lays them out (``repro.search.packed._layout``) for one query block,
    or for a served batch of ``rows`` queries."""
    w = KNN_WORKLOADS[workload]
    p = plan_search(n=w.n, d=w.d, k=w.k, metric=w.metric,
                    recall_target=w.recall_target, backend="pallas",
                    device="tpu_v5e", storage=storage)
    block_n = p.bin_size * max(1, p.block_n // p.bin_size)
    n_pad = round_up(max(w.n, block_n), block_n)
    int4 = storage == "int4"
    d_pad = round_up(w.d, 256 if int4 else 128)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    operands = [
        shaped((rows or p.query_block, w.d), jnp.float32),
        shaped((n_pad, d_pad // 2 if int4 else d_pad), _STORED[storage]),
        shaped((1, n_pad), jnp.float32),
    ]
    if storage in ("int8", "int4"):
        operands.append(shaped((1, n_pad), jnp.float32))
    tiles = dict(bin_size=p.bin_size, block_m=p.block_m, block_n=block_n,
                 int4_packed=int4)
    return p, operands, tiles


def _assert_kernel_compiles(fn, operands):
    compiled = jax.jit(fn).lower(*operands).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("workload,storage", [
    ("sift1m", "f32"),
    ("sift1m", "bf16"),
    ("sift1m", "int8"),
    ("sift1m", "int4"),
    ("glove1.2m", "f32"),
    ("glove1.2m", "int8"),
])
def test_fused_kernel_compiles_at_planned_tiles(
    one_chip, no_persistent_cache, workload, storage
):
    p, operands, tiles = _kernel_args(workload, storage, one_chip)
    _assert_kernel_compiles(
        lambda *ops: partial_reduce_fused(*ops, k_scan=p.k_scan, **tiles),
        operands,
    )


def test_two_pass_kernel_compiles_at_planned_tiles(
    one_chip, no_persistent_cache
):
    _, operands, tiles = _kernel_args("sift1m", "f32", one_chip)
    _assert_kernel_compiles(
        lambda *ops: partial_reduce_packed(*ops, **tiles), operands
    )


@pytest.mark.parametrize("rows", [8, 32])
def test_fused_kernel_compiles_for_served_buckets(
    one_chip, no_persistent_cache, rows
):
    """A served micro-batch runs the same kernel with block_m clamped to
    its bucket (``partial_reduce._effective_block_m``)."""
    p, operands, tiles = _kernel_args("sift1m", "f32", one_chip, rows=rows)
    _assert_kernel_compiles(
        lambda *ops: partial_reduce_fused(*ops, k_scan=p.k_scan, **tiles),
        operands,
    )


@pytest.mark.parametrize("rows", [None, 8, 32])
def test_bf16_tier_gives_the_kernel_bf16_queries(
    one_chip, no_persistent_cache, rows
):
    """sift1m at bf16, for a query block and for served buckets: the kernel
    takes the queries rounded to bf16 (its first operand), so its dot is
    bf16 x bf16, and a bucket of 8 rows runs at the bf16 floor of 16."""
    import re

    p, operands, tiles = _kernel_args("sift1m", "bf16", one_chip, rows=rows)
    assert p.block_m % 16 == 0
    compiled = jax.jit(
        lambda *ops: partial_reduce_fused(*ops, k_scan=p.k_scan, **tiles)
    ).lower(*operands).compile().as_text()
    kernel = [line for line in compiled.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernel) == 1
    first = re.search(r"operand_layout_constraints=\{(\w+)\[(\d+),128\]",
                      kernel[0])
    assert first and first.group(1) == "bf16"
    assert int(first.group(2)) == (p.query_block if rows is None
                                   else max(16, rows))


# deep50m-f32 on a four-chip host: 12.5M rows of 96 f32 lanes a chip.
DEEP_N, DEEP_D, DEEP_SHARDS = 50_000_000, 96, 4


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh

    return Mesh(topo.devices, ("db",))


def test_sharded_search_runs_the_fused_kernel_in_each_shard(
    four_chips, no_persistent_cache
):
    """The sharded search a 10,000-query batch streams (three query blocks)
    over the per-shard kernel layout at the planner's tiles: one Mosaic
    kernel, the all-gather of the winners, and each chip's share of the
    padded rows with no database-sized temporary."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.search import backends

    p = plan_search(n=DEEP_N, d=DEEP_D, k=10, metric="l2",
                    backend="sharded", db_shards=DEEP_SHARDS,
                    device="tpu_v5e")
    rows = p.padded_n // DEEP_SHARDS
    searcher = backends.make_sharded_search_fn(
        four_chips, metric="l2", k=10, db_axis="db",
        kernel=backends.ShardKernel(
            n_local=DEEP_N // DEEP_SHARDS, block_m=p.block_m,
            block_n=p.block_n, interpret=False),
        bin_size=p.bin_size,
    )

    def shaped(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(four_chips, spec))

    compiled = jax.jit(lambda blocks, db, bias: jax.lax.map(
        lambda q: searcher(q, db, bias), blocks)).lower(
        shaped((3, p.query_block, DEEP_D), P(None, None, None)),
        shaped((DEEP_SHARDS * rows, p.d_pad), P("db", None)),
        shaped((1, DEEP_SHARDS * rows), P(None, "db")),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "all-gather" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 1.01 * rows * p.d_pad * 4
    assert mem.temp_size_in_bytes < 2**26


def test_per_shard_relayout_copies_a_chunk_at_a_time(
    four_chips, no_persistent_cache
):
    """Each chip pads its lane-major (12.5M, 96) shard into its block of
    the kernel layout with one chunk's temporary: a one-shot pad would
    copy the whole shard row-major first, 6.4 GB more than it holds."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.search import packed

    rows, spec = 12_500_992, P("db", None)
    pad = functools.partial(packed._pad_rows, rows=rows, width=128, fill=0.0)
    compiled = jax.jit(jax.shard_map(
        pad, mesh=four_chips, in_specs=spec, out_specs=spec,
        check_vma=False,
    )).lower(jax.ShapeDtypeStruct(
        (DEEP_N, DEEP_D), jnp.float32,
        sharding=NamedSharding(four_chips, spec),
    )).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < 1.01 * rows * 128 * 4
    # A chunk as sliced and as padded: 0.27 GB, not 6.4 GB.
    assert mem.temp_size_in_bytes < 3 * packed.PAD_CHUNK_ROWS * 128 * 4

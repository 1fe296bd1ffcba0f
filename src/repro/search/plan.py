"""Model-driven kernel planner: the paper's performance model as a subsystem.

The paper's central claim (abstract, §4–5) is that every kernel parameter of
the search can be derived *analytically* — from the accelerator performance
model (Eq. 4–10) and the recall guarantee (Eq. 13–14) — with no empirical
index tuning.  This module is where that happens: ``plan_search`` maps a
workload description ``(M, N, D, k, dtype, metric, recall_target)`` plus a
device profile onto a frozen :class:`Plan` holding

  * the bin layout ``(L, W)`` from the recall guarantee
    (``repro.core.binning``, Eq. 13–14),
  * the kernel tiles ``block_m`` / ``block_n`` sized against the device's
    on-chip memory budget and the MXU/VPU tiling contract,
  * the host-level ``query_block`` (bounding the (query_block, N) score
    tile of the XLA backend) and the ``stream`` decision,
  * roofline predictions — FLOPs, HBM bytes, COPs, the two operational
    intensities, attainable FLOP/s and the binding wall (Eq. 4–6, Eq. 20) —
    via ``repro.core.roofline``.

``Index.build(..., plan="model")`` (the default) consumes a Plan instead of
hard-coded tile sizes; ``plan="measure"`` refines the model's pick with a
short on-device sweep (:func:`tune_plan`, persisted in a :class:`PlanCache`);
``Index.explain()`` reports the plan with predicted — and optionally
measured — roofline position.

The planner is deliberately conservative where the model and the legacy
defaults agree: when the memory budget allows the historical (256, 1024)
tiles, it picks exactly those, so model-planned searches are bit-identical
to the previous hard-coded configuration (tested in ``tests/test_plan.py``).

Doctest — planning is pure math, no device needed:

>>> p = plan_search(n=1_000_000, d=128, k=10, m=10_000, metric="l2",
...                 recall_target=0.95, device="tpu_v4")
>>> p.num_bins >= 10 and p.expected_recall >= 0.95
True
>>> p.block_n % p.bin_size == 0 and p.d_pad % 128 == 0
True
>>> p.bottleneck in ("compute", "memory", "instruction")
True
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Dict, Optional, Tuple

from repro.core.binning import BinPlan, expected_recall, plan_bins, round_up
from repro.core.roofline import (
    HARDWARE,
    Hardware,
    KernelCost,
    attainable_flops,
    bottleneck,
    cops_per_dot,
    partial_reduce_cost,
    partial_reduce_fused_cost,
)
from repro.kernels.partial_reduce import winner_buffer_bytes
from repro.search import cluster as clusterlib
from repro.search import quant
from repro.search import telemetry
from repro.search.spec import SearchSpec

__all__ = [
    "Plan",
    "PlanCache",
    "plan_search",
    "plan_buckets",
    "plan_clusters",
    "plan_segments",
    "shard_bin_plan",
    "shard_bins",
    "SEGMENT_ALIGN",
    "tune_plan",
    "detect_device",
    "hlo_check",
    "DEFAULT_BLOCK_M",
    "DEFAULT_BLOCK_N",
    "DEFAULT_QUERY_BLOCK",
    "SCORE_TILE_BUDGET",
    "MIN_SERVE_BUCKET",
    "CLUSTER_GATHER_PENALTY",
    "CLUSTER_SPEEDUP_BAR",
]

# The legacy hard-coded tiles, now the *anchors* the model shrinks from when
# the workload or the device budget demands it.  256 query rows keep the
# 128x128 MXU fed across two passes; 1024 database rows per tile is the
# empirically-validated VMEM sweet spot the paper's open-source kernels use.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 1024
DEFAULT_QUERY_BLOCK = 4096

# The XLA backend materializes the (query_block, N) score tile in HBM before
# ApproxTopK consumes it; the planner bounds that tile to this many bytes.
SCORE_TILE_BUDGET = 64 * 2**20

# Smallest serving micro-batch shape the bucket ladder compiles: one sublane
# tile of query rows, so a lone 1-row request is not padded to a full
# query_block.
MIN_SERVE_BUCKET = 8

# Host-tier segment rows round up to this multiple so capacity growth
# (Index.add) lands on whole waves — the compiled wave program's shapes
# never change, keeping the zero-retrace steady state.
SEGMENT_ALIGN = 1024

# Cluster-pruning cost model (repro.search.cluster).  A gathered candidate
# row costs more than a streamed one — the pruned scan trades the fused
# kernel's sequential database stream for random row gathers — so pruned
# rows are priced at this multiple of a full-scan row when deciding the
# crossover.  4x is deliberately pessimistic for HBM gather granularity;
# it keeps the planner from enabling pruning on workloads where the win
# would be marginal.
CLUSTER_GATHER_PENALTY = 4.0

# Pruning is enabled only when the modeled row cost (C centroid dots +
# gather-penalized scanned rows) beats the full scan by at least this
# factor: below it, the bit-identical full scan is the better default.
CLUSTER_SPEEDUP_BAR = 2.0

_DTYPE_BYTES = {
    "float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
    "float64": 8, "f32": 4, "bf16": 2,
}

# Minimum second-to-last-dim tile (sublane count) per dtype on TPU; the last
# dim is always 128 lanes (see the Pallas tiling contract).  The 0.5 entry is
# the int4 tier: its Pallas layout packs two nibbles per int8 byte, so the
# stored tile is int8-shaped and tiles at 32 sublanes.
_SUBLANE = {4: 8, 2: 16, 1: 32, 8: 8, 0.5: 32}


def _dtype_bytes(dtype: Optional[str]) -> int:
    if dtype is None:
        return 4
    return _DTYPE_BYTES.get(str(dtype), 4)


# TPU ``device_kind`` (lower-cased) -> HARDWARE profile.  A TPU whose kind
# is not here has no profile: planning against another chip's walls would
# mis-tile every kernel, so detect_device raises instead of guessing.
TPU_KINDS = {
    "tpu v3": "tpu_v3",
    "tpu v4": "tpu_v4",
    "tpu v5 lite": "tpu_v5e",
    "tpu v5e": "tpu_v5e",
}


def detect_device(name: Optional[str] = None) -> str:
    """Resolve a device-profile name against ``repro.core.roofline.HARDWARE``.

    ``None`` auto-detects from the live JAX backend: TPUs map onto their
    profile by device kind (``TPU_KINDS``; an unknown kind raises), GPUs
    onto A100, anything else onto the ``"cpu"`` host profile (whose tile
    budget mirrors the TPU so host-planned layouts match device-planned
    ones).

    >>> detect_device("tpu_v4")
    'tpu_v4'
    """
    if name is not None:
        if name not in HARDWARE:
            raise ValueError(
                f"unknown device profile {name!r}; known: {sorted(HARDWARE)}"
            )
        return name
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        try:
            return TPU_KINDS[kind.lower()]
        except KeyError:
            raise ValueError(
                f"no hardware profile for TPU kind {kind!r}; known kinds: "
                f"{sorted(TPU_KINDS)} -> profiles {sorted(HARDWARE)}"
            ) from None
    if backend == "gpu":
        return "a100"
    return "cpu"


@dataclasses.dataclass(frozen=True)
class Plan:
    """The analytically-derived kernel configuration for one search workload.

    Everything ``Index.build`` needs to lay out the packed state and compile
    the search — plus the roofline prediction explaining *why* these numbers
    (Eq. 4–10), so ``Index.explain()`` can report predicted vs measured
    position against the three walls.

    Workload: ``m`` (query batch; 0 = unknown, predictions then assume one
    ``query_block``), ``n`` rows, ``d`` dims, ``k`` neighbours, ``metric``,
    ``dtype``, ``recall_target``, ``backend``, ``device`` (profile name).

    Derived layout: ``num_bins``/``log2_bin_size``/``padded_n`` (Eq. 13–14
    via ``repro.core.binning``), ``d_pad`` (lane padding), ``block_m`` /
    ``block_n`` (kernel tiles), ``query_block``, ``stream``.

    Predictions (per ``query_block``-sized dispatch): ``flops``,
    ``hbm_bytes``, ``cops`` (Appendix A.5), the intensities
    ``i_mem``/``i_cop``, ``attainable_flops`` and the binding ``bottleneck``
    wall (Eq. 6), and ``predicted_s``/``predicted_qps``.  The cost model
    matches the backend that runs: the fused-kernel Eq. 20 traffic model
    over the padded layout for ``pallas`` and over one shard's block for
    ``sharded`` (whose bin fields count every shard; a mesh whose shards
    run the XLA scan over the same bins is priced as the kernel), the
    unfused Level-3-BLAS shape over the raw operands for ``xla``.

    ``source`` records provenance: ``"model"`` (analytic), ``"measure"``
    (refined by :func:`tune_plan`), or ``"user"`` (explicit overrides pinned
    every choice).
    """

    # workload
    m: int
    n: int
    d: int
    k: int
    metric: str
    dtype: str
    recall_target: float
    backend: str
    device: str
    # bin layout (Eq. 13-14)
    num_bins: int
    log2_bin_size: int
    padded_n: int
    expected_recall: float
    # kernel layout
    d_pad: int
    block_m: int
    block_n: int
    query_block: int
    stream: bool
    # roofline prediction (Eq. 4-10)
    flops: float
    hbm_bytes: float
    cops: float
    i_mem: float
    i_cop: float
    attainable_flops: float
    bottleneck: str
    predicted_s: float
    predicted_qps: float
    source: str = "model"
    # the tile fields ("block_m", "block_n") the caller pinned; the others
    # are the planner's, which ``Index.shard`` re-plans for a shard's rows.
    pinned_tiles: Tuple[str, ...] = ()
    # recall-accounting N override (paper §7); carried so re-plans (growth,
    # shard, explain) keep the same accounting as the packed layout.
    reduction_input_size_override: int = -1
    # storage tier of the database rows (repro.search.quant): decides the
    # bytes/row the memory-wall terms above were computed with.
    storage: str = "f32"
    # whether the two-pass exact rescore runs (quantized tiers); its
    # O(M·L·D) cost is included in the prediction when True.
    rescore: bool = False
    # the over-fetched k the scan's bin layout was planned for (== k for
    # the f32 tier; quant.scan_k otherwise).
    k_scan: int = 0
    # cluster-pruned front-end (repro.search.cluster): None when the build
    # asked for cluster="off"; a ClusterPlan otherwise — with
    # ``enabled=False`` recording that ``cluster="auto"`` evaluated the
    # crossover and rejected pruning for this N (the bit-identical case).
    # When enabled, ``expected_recall`` above is the *product* bound
    # (collision term over the scanned slots x the cluster-miss term) and
    # the roofline numbers model the gathered pruned program.
    cluster: Optional[clusterlib.ClusterPlan] = None
    # database shard count for backend="sharded" (1 = unsharded/1-device):
    # the scan cost above is then priced per shard — O(min(M, N/shards)),
    # the §7 traffic contract — and the all-gather below is the only
    # cross-device term.
    db_shards: int = 1
    # bytes crossing the ICI per dispatch (each shard contributes its
    # O(k_scan) (f32 value, int32 global id) winners to the all-gather)
    # and the resulting collective wall time at the profile's
    # ici_bandwidth; both 0 when db_shards == 1.
    ici_bytes: float = 0.0
    ici_s: float = 0.0
    # host-RAM cold tier (spec.residency="host"): the segment-wave
    # schedule — fixed segment_rows per wave, num_segments waves per
    # search, two segments HBM-resident at once (scan + double-buffered
    # prefetch) inside hbm_budget_bytes.  All 0 for residency="hbm".
    residency: str = "hbm"
    segment_rows: int = 0
    num_segments: int = 0
    hbm_budget_bytes: float = 0.0

    @property
    def bin_size(self) -> int:
        return 1 << self.log2_bin_size

    @property
    def bin_plan(self) -> BinPlan:
        """The recall-guarantee layout as a ``repro.core.binning.BinPlan``."""
        return BinPlan(
            n=self.n, k=self.k, num_bins=self.num_bins,
            log2_bin_size=self.log2_bin_size, padded_n=self.padded_n,
            expected_recall=self.expected_recall,
        )

    @property
    def cost(self) -> KernelCost:
        return KernelCost(
            flops=self.flops, hbm_bytes=self.hbm_bytes, cops=self.cops
        )

    @property
    def hardware(self) -> Hardware:
        return HARDWARE[self.device]

    @property
    def serve_buckets(self) -> Tuple[int, ...]:
        """Micro-batch bucket ladder for the concurrent serving layer
        (``repro.search.serve``): pre-compiled coalesced-batch shapes up to
        one ``query_block`` — the planner-sized micro-batch."""
        return plan_buckets(self.query_block)

    def to_spec(self, base: Optional[SearchSpec] = None) -> SearchSpec:
        """Materialize a concrete ``SearchSpec`` from this plan.

        Block fields the ``base`` spec already pins (non-``None``) win over
        the plan — explicit user overrides are never silently replaced.
        """
        base = base or SearchSpec(
            metric=self.metric, k=self.k, recall_target=self.recall_target,
            backend=self.backend, storage=self.storage,
            # self.rescore is always resolved (never None) and False for
            # the f32 tier, which SearchSpec accepts — pass it verbatim so
            # an explicit rescore=False footprint plan stays rescore-off.
            rescore=self.rescore,
            residency=self.residency,
        )
        return dataclasses.replace(
            base,
            block_m=base.block_m or self.block_m,
            max_block_n=base.max_block_n or self.block_n,
            query_block=base.query_block or self.query_block,
            serve_buckets=base.serve_buckets
            or plan_buckets(base.query_block or self.query_block),
            segment_rows=base.segment_rows or (self.segment_rows or None),
        )

    def summary(self) -> dict:
        """Flat dict view (what ``Index.explain()`` embeds)."""
        out = dataclasses.asdict(self)
        out["bin_size"] = self.bin_size
        return out


def _vmem_budget(hw: Hardware) -> float:
    """Usable on-chip bytes per grid step: the operand tiles are
    double-buffered but the score/winner scratch is not, so ~3/4 of VMEM
    is the practical ceiling."""
    return 0.75 * hw.vmem_bytes


def _vmem_need(block_m: int, block_n: int, d_pad: int, dtype_bytes: int,
               bin_size: int, db_bytes: Optional[float] = None,
               fused_n: int = 0) -> float:
    """On-chip bytes one (block_m, block_n) grid step holds.

    ``db_bytes`` is the stored database tile's bytes/element (quantized
    tiers stream and hold narrower rows; int4 holds 0.5 — two nibbles per
    stored byte); default: ``dtype_bytes``.  ``fused_n`` (the database
    rows, for the fused kernel; 0 for the two-pass kernel) charges the
    fused kernel's winner buffer — an f32 value and an int32 id for every
    bin winner of the block-padded database, per query row
    (``kernels.partial_reduce.winner_buffer_bytes``) — which lives in
    VMEM across the whole database stream, so it is budgeted alongside
    the per-step tiles.

    A stored tier narrower than the compute dtype is dequantized in VMEM
    (``kernels.partial_reduce._load_db_tile``): that costs a compute-dtype
    copy of the database tile, and int4 first unpacks its nibbles into
    int32 low/high halves — block_n x d_pad int32 in all.  The f32 tier's
    dot runs at full precision, for which the TPU compiler splits both
    operand tiles into bf16 high and low halves (2.2 MiB more at the
    sift1m tiles block_m 512, block_n 4096, where 17 MiB broke the 16 MiB
    scoped limit on a v5e).
    """
    if db_bytes is None:
        db_bytes = dtype_bytes
    unpack = 0.0
    if db_bytes != dtype_bytes:
        unpack += block_n * d_pad * dtype_bytes     # dequantized tile
    if db_bytes == 0.5:
        unpack += block_n * d_pad * 4               # int32 nibble halves
    if db_bytes == dtype_bytes == 4:
        unpack += (block_m + block_n) * d_pad * 4   # bf16 hi/lo splits
    return (
        d_pad * (block_m * dtype_bytes + block_n * db_bytes)  # operand tiles
        + unpack
        + block_m * block_n * 4                     # score tile (f32)
        + 2 * block_m * max(1, block_n // bin_size) * 4  # winners (val+idx)
        + (winner_buffer_bytes(block_m, round_up(fused_n, block_n), bin_size)
           if fused_n else 0)                       # fused winner buffer
    )


def _plan_tiles(
    n: int,
    d_pad: int,
    bin_size: int,
    m: Optional[int],
    dtype_bytes: int,
    hw: Hardware,
    *,
    block_m: Optional[int] = None,
    max_block_n: Optional[int] = None,
    db_bytes: Optional[float] = None,
    fused_n: int = 0,
) -> Tuple[int, int]:
    """Initial kernel tile sizes from the on-chip memory model.

    VMEM per grid step holds the query tile (block_m, d_pad), the database
    tile (block_n, d_pad), the score tile (block_m, block_n) and the bin
    winners; the fused kernel (``fused_n`` rows) also holds its winner
    buffer, which only a smaller ``block_m`` shrinks.  Tiles honour the
    TPU tiling contract (sublane-multiple rows, 128-lane columns) and
    never exceed the data: ``block_n`` stops at the bin-aligned database
    size, so a small database is not padded up to a full default tile.
    ``block_m`` may subsequently be *escalated* by :func:`plan_search` to
    push the kernel off the memory wall (Eq. 10).
    """
    if db_bytes is None:
        db_bytes = dtype_bytes
    sublane = _SUBLANE.get(dtype_bytes, 8)
    pinned_m = block_m is not None
    if block_m is None:
        block_m = DEFAULT_BLOCK_M if m is None else min(
            DEFAULT_BLOCK_M, max(sublane, round_up(m, sublane))
        )

    if max_block_n is not None:
        # Pinned: honour it exactly the way the packed layout will
        # (packed._layout derives block_n = bin_size * (max_block_n //
        # bin_size)), so the plan always describes the executed tile.
        return block_m, bin_size * max(1, max_block_n // bin_size)

    budget = _vmem_budget(hw)
    # block_n must be a multiple of the bin size (the kernel's
    # (bm, bn) -> (bm, bins, bin_size) reshape) AND of the *stored*
    # dtype's sublane count (TPU second-to-minor tiling; int8 rows tile at
    # 32 sublanes); both are powers of two, so their lcm is the max.
    db_sublane = _SUBLANE.get(db_bytes, 8)
    unit = max(bin_size, db_sublane)
    n_aligned = round_up(n, unit)
    g_data = max(1, n_aligned // unit)
    g_anchor = max(1, DEFAULT_BLOCK_N // unit)
    g = min(g_data, g_anchor)
    while g > 1 and _vmem_need(
        block_m, g * unit, d_pad, dtype_bytes, bin_size, db_bytes, fused_n
    ) > budget:
        g -= 1
    while not pinned_m and block_m > sublane and _vmem_need(
        block_m, g * unit, d_pad, dtype_bytes, bin_size, db_bytes, fused_n
    ) > budget:
        block_m = max(sublane, round_up(block_m // 2, sublane))
    return block_m, g * unit


def shard_bin_plan(n_local: int, k_scan: int, recall_target: float,
                   max_bin: int, accounting_n: int) -> BinPlan:
    """One shard's bin layout on the sharded backend: the recall plan of
    its ``n_local`` rows against ``accounting_n`` (paper §7), with bins no
    wider than ``max_bin`` (rounded down to a power of two).

    The recall plan against the global N lays a shard's bins as wide as
    ``n / (shards · L)`` rows (2^18 at 12.5M rows a shard), but a kernel
    tile holds whole bins, so the plan's tile caps them — for the XLA
    scan in a shard as for the fused kernel, so both scan the same bins.
    Every row is still scored, and narrower bins only add bins, so
    E[recall], counted over every shard's bins, only rises.
    """
    recall = plan_bins(n_local, min(k_scan, n_local), recall_target,
                       reduction_input_size_override=accounting_n)
    log2_bin = int(math.log2(max_bin))
    if recall.log2_bin_size <= log2_bin:
        return recall
    bins = -(-n_local // (1 << log2_bin))
    return BinPlan(
        n=n_local, k=recall.k, num_bins=bins, log2_bin_size=log2_bin,
        padded_n=bins << log2_bin,
        expected_recall=expected_recall(
            bins * max(1, accounting_n // n_local), recall.k),
    )


def shard_bins(n: int, shards: int, k_scan: int, recall_target: float,
               block_n: int, reduction_input_size_override: int = -1
               ) -> BinPlan:
    """Bin layout of the sharded backend over ``shards`` blocks of
    ``n // shards`` rows (:func:`shard_bin_plan`, capped at ``block_n``),
    with ``num_bins`` and ``padded_n`` counting all shards; ``padded_n``
    is the kernel layout's, each shard's block rounded to the tile.

    >>> b = shard_bins(50_000_000, 4, 10, 0.95, 4096)
    >>> b.bin_size, b.num_bins, round(b.expected_recall, 5)
    (4096, 12208, 0.99926)
    """
    n_local = n // shards
    local = shard_bin_plan(
        n_local, k_scan, recall_target, block_n,
        reduction_input_size_override
        if reduction_input_size_override > 0 else n,
    )
    tile = local.bin_size * max(1, block_n // local.bin_size)
    return dataclasses.replace(
        local, n=n, num_bins=local.num_bins * shards,
        padded_n=shards * round_up(max(n_local, tile), tile),
    )


def _plan_shard_tiles(
    n_local: int,
    d_pad: int,
    bin_size: int,
    m: Optional[int],
    dtype_bytes: int,
    hw: Hardware,
    *,
    block_m: Optional[int] = None,
    max_block_n: Optional[int] = None,
    db_bytes: Optional[float] = None,
) -> Tuple[int, int]:
    """Tiles of the fused kernel over one shard's ``n_local`` rows.

    ``bin_size`` is the recall plan's; the layout caps bins at the tile
    (:func:`shard_bins`), so the tile width is free.  Each candidate
    width, a power of two from the recall plan's bin down to
    ``DEFAULT_BLOCK_N``, is sized by :func:`_plan_tiles` with the fused
    winner buffer charged: narrower bins grow the buffer, wider tiles the
    score tile.  The candidate that fits VMEM with the largest query tile
    wins, the widest on a tie (fewest grid steps).
    """
    if max_block_n is not None:
        capped = min(bin_size, 1 << int(math.log2(max_block_n)))
        return _plan_tiles(
            n_local, d_pad, capped, m, dtype_bytes, hw, block_m=block_m,
            max_block_n=max_block_n, db_bytes=db_bytes, fused_n=n_local,
        )
    best, best_key = None, None
    width = max(bin_size, DEFAULT_BLOCK_N)
    while width >= DEFAULT_BLOCK_N:
        capped = min(bin_size, width)
        bm, bn = _plan_tiles(
            n_local, d_pad, capped, m, dtype_bytes, hw, block_m=block_m,
            db_bytes=db_bytes, fused_n=n_local,
        )
        fits = _vmem_need(bm, bn, d_pad, dtype_bytes, capped, db_bytes,
                          n_local) <= _vmem_budget(hw)
        key = (fits, bm, bn)
        if best_key is None or key > best_key:
            best, best_key = (bm, bn), key
        width //= 2
    return best


def _escalate_block_m(
    block_m: int,
    block_n: int,
    m_eff: int,
    padded_n: int,
    d_pad: int,
    num_bins: int,
    c: float,
    dtype_bytes: int,
    bin_size: int,
    hw: Hardware,
    db_bytes: Optional[float] = None,
    k_scan: int = 0,
) -> int:
    """Grow the query tile until the memory wall clears the other walls.

    The kernel grid streams the full database once per ``block_m`` query
    rows (Eq. 20's ``ib``), so a too-small query tile makes the kernel
    memory-bound regardless of N.  The model doubles ``block_m`` — within
    the VMEM budget (which charges the fused winner buffer at each
    candidate size), the query batch, and a 1024-row cap — until the
    attainable FLOP/s stop being memory-limited.  This is the planner
    reproducing the paper's Fig. 2 reasoning as a *decision* instead of a
    figure.
    Costs come from the fused single-pass model (the kernel this tile
    actually feeds); ``num_bins`` stays in the signature for the legacy
    two-pass callers in older tests.
    """
    ks = max(1, k_scan)
    cap = min(1024, max(block_m, round_up(m_eff, 8)))
    while block_m < cap:
        cost = partial_reduce_fused_cost(
            m_eff, padded_n, d_pad, ks,
            cops_per_dot=c, block_rows=block_m, dtype_bytes=dtype_bytes,
            db_bytes=db_bytes, block_n=block_n,
            bins_per_block=max(1, block_n // bin_size),
        )
        memory_wall = hw.hbm_bandwidth * cost.i_mem
        other_walls = min(hw.peak_flops, hw.peak_cops * cost.i_cop)
        if memory_wall >= other_walls:
            break
        bigger = min(cap, block_m * 2)
        if _vmem_need(bigger, block_n, d_pad, dtype_bytes, bin_size,
                      db_bytes, padded_n) > _vmem_budget(hw):
            break
        block_m = bigger
    return block_m


def _dense_cost(m: int, n: int, d: int, l: int, dtype_bytes: int,
                db_bytes: Optional[int] = None) -> KernelCost:
    """Cost of the *unfused* dense path (Remark 1 / Level-3 BLAS shape).

    ``dense_search`` materializes the full (M, N) f32 score matrix in HBM
    before ApproxTopK consumes it, over the unpadded (N, D) operands — so
    its model is operand reads + score write/read + bin winners, not the
    fused kernel's Eq. 20.  This is what makes the dense baseline
    memory-bound at paper scale, i.e. why the fused kernel exists.
    ``db_bytes`` prices the (N, D) operand read at the storage tier's
    bytes/element; the f32 score matrix dominates here regardless, which
    is why quantized tiers pay off most on the fused kernel.
    """
    if db_bytes is None:
        db_bytes = dtype_bytes
    flops = 2.0 * m * n * d
    hbm = (
        dtype_bytes * m * d + db_bytes * n * d
        + 4.0 * (2.0 * m * n + 2.0 * m * l)
    )
    cops = float(m) * n  # the reduction's compare chain
    return KernelCost(flops=flops, hbm_bytes=hbm, cops=cops)


def _rescore_cost(m: int, l: int, k_scan: int, d: int) -> KernelCost:
    """Added cost of the exact second pass (quantized tiers).

    The L bin winners are first cut to the ``k_scan`` best by quantized
    score (a compare chain over L, no HBM gather), then only those
    O(M·K') rows are gathered at full precision and re-scored — so the
    second pass stays O(M), inside Eq. 10's O(min(M, N)) budget, and its
    gather traffic scales with the over-fetch budget, not the bin count.
    """
    flops = 2.0 * m * k_scan * d
    hbm = 4.0 * (m * k_scan * d + 3.0 * m * k_scan)  # rows + bias/vals/idxs
    cops = float(m) * (l + k_scan)  # the cut + the exact compare chain
    return KernelCost(flops=flops, hbm_bytes=hbm, cops=cops)


def plan_clusters(
    *, n: int, k_scan: int, recall_target: float
) -> clusterlib.ClusterPlan:
    """Derive the cluster-pruning parameters — and the enable decision.

    All geometry comes from ``repro.search.cluster``'s closed forms (C =
    2^ceil(log2(sqrt(N))), rho from the geometric-decay miss budget, 25 %
    balance headroom per cluster, an always-scanned spill block); this
    wrapper adds the *cost* decision: per query the pruned path pays C
    centroid dots plus ``CLUSTER_GATHER_PENALTY`` x S gathered-row dots
    against the full scan's N, and pruning is enabled only when that wins
    by ``CLUSTER_SPEEDUP_BAR`` — plus sanity floors (the scanned slot
    count must comfortably hold the over-fetched ``k_scan``, and pruning a
    scan smaller than its own candidate set is never a win).

    >>> plan_clusters(n=8192, k_scan=10, recall_target=0.95).enabled
    True
    >>> plan_clusters(n=2048, k_scan=10, recall_target=0.95).enabled
    False
    """
    num_clusters = clusterlib.num_clusters_for(n)
    rows_per_cluster = clusterlib.rows_per_cluster_for(n, num_clusters)
    probes = clusterlib.probes_for(recall_target, num_clusters)
    spill = clusterlib.spill_capacity_for(n)
    budget = clusterlib.miss_budget_for(recall_target)
    # Inner-scan target so the product (collision x miss) meets the
    # original target: target / (1 - budget) = 2t/(1+t) < 1 always.
    target_scan = recall_target / (1.0 - budget)
    scan_rows = probes * rows_per_cluster + spill
    speedup = n / (num_clusters + CLUSTER_GATHER_PENALTY * scan_rows)
    enabled = (
        speedup >= CLUSTER_SPEEDUP_BAR
        and probes < num_clusters
        and scan_rows < n
        and scan_rows >= 4 * k_scan
    )
    return clusterlib.ClusterPlan(
        n=n, num_clusters=num_clusters, rows_per_cluster=rows_per_cluster,
        probes=probes, spill_capacity=spill, miss_budget=budget,
        target_scan=target_scan, predicted_speedup=speedup, enabled=enabled,
    )


def _cluster_cost(m: int, d: int, l: int, cp: clusterlib.ClusterPlan,
                  dtype_bytes: int, db_bytes: int) -> KernelCost:
    """Cost of the pruned gathered scan (all backends share this program).

    Centroid scoring is a small dense matmul; the candidate rows are then
    *gathered* — every query reads its own S rows with no cross-query
    reuse, so the database term is ``m*S*d`` at the storage tier's width
    (the pruning win is that ``S << N``, not better locality).  The fused
    Eq. 20 kernel is bypassed on this path: a gather-dominated scan has no
    sequential stream to fuse.
    """
    c, s = cp.num_clusters, cp.scan_rows
    flops = 2.0 * m * (c + s) * d
    hbm = (
        dtype_bytes * m * d                    # queries
        + 4.0 * c * d + 4.0 * c               # centroid table + bias
        + 4.0 * m * s                          # gathered candidate ids
        + db_bytes * m * s * d                 # gathered rows, no reuse
        + 4.0 * (2.0 * m * s + 2.0 * m * l)    # score tile + bin winners
    )
    cops = float(m) * (c + s)
    return KernelCost(flops=flops, hbm_bytes=hbm, cops=cops)


def plan_buckets(
    max_batch: int, *, min_bucket: int = MIN_SERVE_BUCKET
) -> Tuple[int, ...]:
    """Micro-batch bucket ladder for the concurrent serving layer.

    A coalesced batch of queries is padded up to the smallest bucket that
    holds it, so the server only ever dispatches one of these shapes — each
    bucket is compiled once and the steady state never retraces.  The
    ladder doubles from ``min_bucket`` (one sublane tile, so a lone tiny
    request is not padded to a full ``query_block``) up to ``max_batch``
    (the planner-sized micro-batch, normally one ``query_block``), which is
    always the last rung.  Padded rows cost FLOPs, so a geometric ladder
    bounds the waste at <2x while keeping the compile count logarithmic.

    >>> plan_buckets(64)
    (8, 16, 32, 64)
    >>> plan_buckets(100)
    (8, 16, 32, 64, 100)
    >>> plan_buckets(4)
    (4,)
    """
    if max_batch <= 0:
        raise ValueError(f"max_batch must be positive, got {max_batch}")
    if min_bucket <= 0:
        raise ValueError(f"min_bucket must be positive, got {min_bucket}")
    out = []
    b = min(min_bucket, max_batch)
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def _plan_query_block(n: int, backend: str) -> int:
    """Host-level query tiling: bound the XLA backend's (qb, N) score tile.

    The fused Pallas kernel never materializes the full score matrix, so it
    keeps the full default.  So does the sharded backend: where its shards
    run that kernel nothing is materialized, and where they run the XLA
    scan each shard cuts the block into sub-blocks against its own
    ``(qb, n_local)`` score tile (``make_sharded_search_fn``) — the shard
    count is unknown at plan time, and shrinking against the global N
    would explode the dispatch count on exactly the large-N meshes
    sharding exists for.  Only the dense single-device XLA path, which
    writes the full ``4*qb*n`` score bytes per dispatch, shrinks ``qb``
    under ``SCORE_TILE_BUDGET``.
    """
    if backend != "xla":
        return DEFAULT_QUERY_BLOCK
    qb = SCORE_TILE_BUDGET // max(1, 4 * n)
    if qb >= DEFAULT_QUERY_BLOCK:
        return DEFAULT_QUERY_BLOCK
    # Largest power of two under budget, floored at one sublane tile.
    qb = 1 << max(3, int(math.floor(math.log2(max(8, qb)))))
    return min(qb, DEFAULT_QUERY_BLOCK)


def plan_segments(
    *,
    n: int,
    d: int,
    db_bytes: int,
    hbm_budget_bytes: float,
    rescore: bool = False,
    segment_rows: Optional[int] = None,
) -> Tuple[int, int]:
    """Host-tier segment schedule: ``(segment_rows, num_segments)``.

    Two segments are HBM-resident at once — the wave being scanned and
    the double-buffered prefetch of the next — so one segment's bytes
    must fit in *half* of ``hbm_budget_bytes``.  A segment row costs its
    stored width plus the per-row bias/scale vectors, plus the f32
    rescore tail when the quantized two-pass runs.  Rows round up to
    ``SEGMENT_ALIGN`` (whole waves survive capacity growth without a
    shape change), and the returned schedule always covers ``n``:
    ``segment_rows * num_segments >= n`` — ``Index.build`` pads capacity
    up to that product so every wave is the same compiled shape.

    An explicit ``segment_rows`` pins the wave shape (the budget check is
    skipped — the caller owns the consequences), mirroring the tile-field
    contract everywhere else in this module.

    >>> plan_segments(n=4096, d=128, db_bytes=4, hbm_budget_bytes=2**20)
    (1024, 4)
    """
    if n <= 0:
        raise ValueError(f"need positive n, got {n}")
    per_row = float(d * db_bytes) + 8.0            # stored row + bias/scale
    if rescore:
        per_row += 4.0 * d + 4.0                   # f32 rescore tail + bias
    if segment_rows is None:
        if hbm_budget_bytes <= 0:
            raise ValueError(
                f"hbm_budget_bytes must be positive, got {hbm_budget_bytes}"
            )
        fit = int(hbm_budget_bytes / 2.0 / per_row)
        # Align DOWN so the two resident segments stay inside the budget;
        # one SEGMENT_ALIGN wave is the floor regardless (a sub-1024-row
        # wave would thrash the dispatch pipeline for no memory win).
        segment_rows = max(
            SEGMENT_ALIGN, (fit // SEGMENT_ALIGN) * SEGMENT_ALIGN
        )
    num_segments = -(-n // segment_rows)
    return segment_rows, num_segments


def plan_search(
    *,
    n: int,
    d: int,
    k: int,
    m: Optional[int] = None,
    metric: str = "mips",
    recall_target: float = 0.95,
    dtype: Optional[str] = None,
    backend: str = "xla",
    device: Optional[str] = None,
    reduction_input_size_override: int = -1,
    block_m: Optional[int] = None,
    max_block_n: Optional[int] = None,
    query_block: Optional[int] = None,
    storage: str = "f32",
    rescore: Optional[bool] = None,
    cluster: str = "off",
    db_shards: int = 1,
    residency: str = "hbm",
    segment_rows: Optional[int] = None,
    hbm_budget_bytes: Optional[float] = None,
) -> Plan:
    """Derive every kernel parameter analytically (Eq. 4–10 + Eq. 13–14).

    The planner never raises on awkward workloads — k = 1 (bins
    degenerate), N smaller than a database tile, D not a multiple of the
    128-lane contract, recall targets at the guarantee's ceiling — it falls
    back to the nearest valid layout instead (degenerate bins become the
    exact top-k layout; tiles clamp to the data).

    Explicit ``block_m`` / ``max_block_n`` / ``query_block`` overrides pin
    the corresponding choice (the prediction is then computed *for the
    pinned layout*, and ``source`` reports ``"user"`` if every knob was
    pinned).

    ``storage`` is the database's ``repro.search.quant`` tier: it sets the
    bytes/row of the Eq. 10/20 database-stream term (so the memory-wall
    escalation and roofline predictions shift with 2- or 1-byte rows), the
    stored-dtype sublane alignment of ``block_n``, and — when ``rescore``
    (default: on for quantized tiers) — the over-fetched scan k
    (``quant.scan_k``) plus the exact second pass's O(M·L·D) cost.

    ``cluster="auto"`` evaluates the cluster-pruned front-end
    (:func:`plan_clusters`): the returned plan carries a ``ClusterPlan``
    and — when it is past the cost crossover — the roofline prediction
    models the gathered pruned program and ``expected_recall`` becomes the
    product bound (collision over the scanned slots x the miss term).
    ``cluster="off"`` (the default) never evaluates it: ``plan.cluster``
    stays ``None`` and nothing else changes.

    >>> plan_search(n=100, d=8, k=1, device="tpu_v4").num_bins >= 1
    True
    >>> plan_search(n=64, d=7, k=4, device="cpu").d_pad
    128
    >>> p8 = plan_search(n=1 << 20, d=128, k=10, m=256, backend="pallas",
    ...                  device="tpu_v4", storage="int8")
    >>> pf = plan_search(n=1 << 20, d=128, k=10, m=256, backend="pallas",
    ...                  device="tpu_v4")
    >>> p8.hbm_bytes < 0.5 * pf.hbm_bytes  # >=2x less traffic (Eq. 10)
    True
    """
    if n <= 0 or d <= 0:
        raise ValueError(f"need positive n, d; got n={n}, d={d}")
    if k > n:
        raise ValueError(f"k={k} exceeds database size n={n}")
    device = detect_device(device)
    hw = HARDWARE[device]
    dtype_name = str(dtype) if dtype is not None else "float32"
    dbytes = _dtype_bytes(dtype)
    # storage="f32" means "store the compute dtype as-is" (pack_state casts
    # to spec.dtype before preparing), so its rows stream at dbytes; the
    # quantized tiers stream their own narrower width.
    sbytes = dbytes if storage == "f32" else quant.storage_bytes(storage)
    # The scan's query operand: bf16 rows are scanned against queries
    # rounded to bf16 (kernels.partial_reduce._scan_queries), so that tier's
    # query tile has bf16 bytes and sublanes, and no tile is cast in VMEM.
    qbytes = 2 if storage == "bf16" else dbytes
    if storage == "int4" and backend != "pallas":
        # Only the Pallas packed layout stores two nibbles per byte; every
        # other backend scores the canonical int8-held codes, so its
        # database streams (and host segments hold) one byte per element.
        sbytes = 1.0
    if rescore and storage == "f32":
        raise ValueError(
            'rescore=True requires a quantized storage tier ("bf16", '
            '"int8" or "int4"); storage="f32" is already exact'
        )
    rescore_on = (storage != "f32") if rescore is None else rescore
    ks = quant.scan_k(storage, k, n=n) if rescore_on else k
    if cluster not in ("auto", "off"):
        raise ValueError(f'cluster must be "auto" or "off", got {cluster!r}')
    if residency not in ("hbm", "host"):
        raise ValueError(
            f'residency must be "hbm" or "host", got {residency!r}'
        )
    if residency == "host" and backend in ("pallas", "sharded"):
        raise ValueError(
            f'residency="host" requires the xla backend, got {backend!r}'
        )
    if db_shards < 1:
        raise ValueError(f"db_shards must be >= 1, got {db_shards}")
    cplan = (
        plan_clusters(n=n, k_scan=ks, recall_target=recall_target)
        # Host residency never evaluates pruning: the pruned program
        # gathers arbitrary rows, which needs the whole database resident.
        if cluster == "auto" and residency != "host" else None
    )
    seg_rows, num_segs, budget = 0, 0, 0.0
    if residency == "host":
        budget = float(hbm_budget_bytes or hw.hbm_bytes)
        seg_rows, num_segs = plan_segments(
            n=n, d=d, db_bytes=sbytes, hbm_budget_bytes=budget,
            rescore=rescore_on, segment_rows=segment_rows,
        )

    d_pad = round_up(d, 128)
    if backend == "sharded":
        # Each shard runs the fused kernel over its own n/shards rows,
        # with bins laid against the global N and capped at the tile.
        n_local = -(-n // db_shards)
        recall_bin = plan_bins(
            n_local, min(ks, n_local), recall_target,
            reduction_input_size_override=(
                reduction_input_size_override
                if reduction_input_size_override > 0
                else n_local * db_shards),
        ).bin_size
        bm, bn = _plan_shard_tiles(
            n_local, d_pad, recall_bin, m, qbytes, hw,
            block_m=block_m, max_block_n=max_block_n, db_bytes=sbytes,
        )
        bins = shard_bins(
            n_local * db_shards, db_shards, ks, recall_target, bn,
            reduction_input_size_override,
        )
    else:
        bins = plan_bins(
            n, ks, recall_target,
            reduction_input_size_override=reduction_input_size_override,
        )
        bm, bn = _plan_tiles(
            n, d_pad, bins.bin_size, m, qbytes, hw,
            block_m=block_m, max_block_n=max_block_n, db_bytes=sbytes,
            fused_n=n if backend == "pallas" else 0,
        )
    # Host residency materializes a (qb, segment_rows) score tile per
    # wave, not (qb, N) — size the query block against the wave shape.
    qb = query_block or _plan_query_block(
        seg_rows if residency == "host" else n, backend
    )

    m_eff = m if m else qb
    flags = dict(
        l2=(metric == "l2"),
        non_pow2_n=(bins.padded_n != n),
        # D is padded with zero lanes at pack time — exact for dot
        # products, so no runtime masking COP; likewise the fused bias row
        # folds the ||x||^2/2 broadcast into the tombstone/tail mask add
        # (Appendix A.5 — this is why the packed layout exists).
        padded_d=False,
        broadcast_norm=False,
    )
    c = cops_per_dot(**flags)
    # Rows and bins one fused kernel scans: the database, or one shard's
    # block of the sharded layout (the shards run concurrently, so the
    # wall is one shard's scan plus the ICI all-gather priced below).
    shards = db_shards if backend == "sharded" else 1
    scan_n, scan_bins = bins.padded_n // shards, bins.num_bins // shards
    if backend in ("pallas", "sharded"):
        # Only the fused kernel consumes block_m; escalate it off the
        # memory wall (Eq. 10/20) and cost the padded kernel layout.
        if block_m is None:
            bm = _escalate_block_m(
                bm, bn, m_eff, scan_n, d_pad, scan_bins, c,
                qbytes, bins.bin_size, hw, db_bytes=sbytes, k_scan=ks,
            )
        # The kernel clamps its query tile to the sublane-rounded batch
        # (kernels.partial_reduce._effective_block_m), so a 1-row search
        # pads to 8 MXU rows, not a full block_m — model the padded shape
        # the kernel actually runs, then price the fused single-pass
        # program: the database streamed once per query block plus the
        # O(M·k_scan) result, with no score-tile HBM round trip.
        sublane_q = _SUBLANE.get(qbytes, 8)
        bm_eff = min(bm, max(sublane_q, round_up(max(m_eff, 1), sublane_q)))
        m_pad = round_up(max(m_eff, 1), bm_eff)
        cost = partial_reduce_fused_cost(
            m_pad, scan_n, d_pad, ks,
            cops_per_dot=c, block_rows=bm_eff, dtype_bytes=qbytes,
            db_bytes=sbytes, block_n=bn,
            bins_per_block=max(1, bn // bins.bin_size),
        )
    else:
        # The dense xla path runs the *unpadded* operands unfused — model
        # the program that actually executes.
        cost = _dense_cost(m_eff, n, d, bins.num_bins, dbytes, sbytes)
    expected = bins.expected_recall
    if cplan is not None and cplan.enabled:
        # The pruned gathered program replaces the scan cost wholesale,
        # and the guarantee becomes the collision x miss product over the
        # S scanned slots (the full-scan bin fields above still describe
        # the packed layout, which clustering leaves untouched).
        cost = _cluster_cost(m_eff, d, bins.num_bins, cplan, dbytes, sbytes)
        expected = cplan.recall_decomposition(ks)["expected_recall"]
    if rescore_on:
        extra = _rescore_cost(m_eff, scan_bins, ks, d)
        cost = KernelCost(
            flops=cost.flops + extra.flops,
            hbm_bytes=cost.hbm_bytes + extra.hbm_bytes,
            cops=cost.cops + extra.cops,
        )
    att = attainable_flops(cost, hw)
    predicted_s = cost.flops / att
    ici_bytes = ici_s = 0.0
    if backend == "sharded" and db_shards > 1:
        # The §7 collective: every shard all-gathers its k (f32 value,
        # int32 global id) winners to every other shard — 8 bytes per
        # candidate, shards x k rows total: the fused select (and the
        # quantized tiers' exact rescore) leaves k a shard.  This is the
        # ONLY cross-device traffic of a search, which is the whole
        # traffic-contract argument.
        ici_bytes = 8.0 * m_eff * k * db_shards
        ici_s = ici_bytes / hw.ici_bandwidth
        predicted_s = predicted_s + ici_s
    pinned = all(v is not None for v in (block_m, max_block_n, query_block))
    return Plan(
        m=m or 0, n=n, d=d, k=k, metric=metric, dtype=dtype_name,
        recall_target=recall_target, backend=backend, device=device,
        num_bins=bins.num_bins, log2_bin_size=bins.log2_bin_size,
        padded_n=bins.padded_n, expected_recall=expected,
        d_pad=d_pad, block_m=bm, block_n=bn, query_block=qb,
        stream=True,
        flops=cost.flops, hbm_bytes=cost.hbm_bytes, cops=cost.cops,
        i_mem=cost.i_mem, i_cop=cost.i_cop,
        attainable_flops=att, bottleneck=bottleneck(cost, hw),
        predicted_s=predicted_s, predicted_qps=m_eff / predicted_s,
        source="user" if pinned else "model",
        pinned_tiles=tuple(
            name for name, v in (("block_m", block_m),
                                 ("block_n", max_block_n))
            if v is not None
        ),
        reduction_input_size_override=reduction_input_size_override,
        storage=storage, rescore=rescore_on, k_scan=ks, cluster=cplan,
        db_shards=db_shards, ici_bytes=ici_bytes, ici_s=ici_s,
        residency=residency, segment_rows=seg_rows, num_segments=num_segs,
        hbm_budget_bytes=budget,
    )


# --- measured refinement (subsumes the old hillclimb loop) -------------------


def time_search(index, queries, *, repeats: int = 3, passes: int = 2
                ) -> float:
    """Wall seconds per ``index.search(queries)``, compile excluded.

    One warmup dispatch (triggers trace + compile), then the best-of-
    ``passes`` mean over ``repeats`` searches — the same protocol as
    ``benchmarks/bench_search.py``, shared here so ``tune_plan`` and
    ``Index.explain(measure=True)`` cannot drift apart.
    """
    index.search(queries).values.block_until_ready()
    best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = index.search(queries)
        out.values.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / repeats)
    # The plan="measure" signal is telemetry too: explain(measure=True) /
    # tune_plan refinements land next to the serve-path drift series.
    telemetry.registry().observe(
        "repro_plan_measured_wall_seconds", best,
        rows=queries.shape[0],
    )
    return best


def _with_measured_tiles(plan: Plan, bm: int, bn: int, qb: int) -> Plan:
    """Re-derive the plan for the measured tile triple.

    A plain ``dataclasses.replace`` of the tiles would leave the roofline
    prediction (flops/bytes/bottleneck/predicted_s) describing the *old*
    tiles; re-running ``plan_search`` with the winners pinned keeps the
    prediction consistent with the configuration it describes.
    """
    refreshed = plan_search(
        n=plan.n, d=plan.d, k=plan.k, m=plan.m or None, metric=plan.metric,
        recall_target=plan.recall_target, dtype=plan.dtype,
        backend=plan.backend, device=plan.device,
        reduction_input_size_override=plan.reduction_input_size_override,
        block_m=bm, max_block_n=bn, query_block=qb,
        storage=plan.storage, rescore=plan.rescore,
        cluster="auto" if plan.cluster is not None else "off",
        db_shards=plan.db_shards, residency=plan.residency,
        segment_rows=plan.segment_rows or None,
        hbm_budget_bytes=plan.hbm_budget_bytes or None,
    )
    return dataclasses.replace(refreshed, source="measure")


class PlanCache:
    """Persistent store of measured plan refinements.

    Keys are the full workload signature (device, backend, metric, dtype,
    shapes, recall target); values are the winning tile triple plus the
    measured wall time.  Backed by a JSON file when ``path`` is given (or
    the ``REPRO_PLAN_CACHE`` environment variable is set); in-memory
    otherwise.  Corrupt or missing files are treated as empty.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get("REPRO_PLAN_CACHE")
        self._entries: Dict[str, dict] = {}
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    self._entries = json.load(f)
            except (OSError, ValueError):
                self._entries = {}

    @staticmethod
    def key(plan: Plan, spec: Optional[SearchSpec] = None) -> str:
        base = (
            f"{plan.device}/{plan.backend}/{plan.metric}/{plan.dtype}"
            f"/m{plan.m}/n{plan.n}/d{plan.d}/k{plan.k}/r{plan.recall_target}"
        )
        if plan.storage != "f32":
            # Tiers tile and cost differently; never serve a measured f32
            # layout to a quantized build (or vice versa).
            base += f"/st-{plan.storage}" + ("" if plan.rescore else "-raw")
        if plan.cluster is not None and plan.cluster.enabled:
            # The pruned gathered program times nothing like the full
            # scan; keep its measurements in their own bucket.
            base += "/cl"
        if plan.db_shards > 1:
            base += f"/sh{plan.db_shards}"
        if plan.residency != "hbm":
            # Segment waves time nothing like a resident scan.
            base += f"/host{plan.segment_rows}"
        if spec is not None and not (
            spec.block_m is None
            and spec.max_block_n is None
            and spec.query_block is None
        ):
            # User-pinned knobs constrain the sweep, so results measured
            # under pins must not be served to unpinned workloads.
            base += f"/pin{spec.block_m}-{spec.max_block_n}-{spec.query_block}"
        return base

    def get(self, plan: Plan, spec: Optional[SearchSpec] = None
            ) -> Optional[dict]:
        return self._entries.get(self.key(plan, spec))

    def put(self, plan: Plan, entry: dict,
            spec: Optional[SearchSpec] = None) -> None:
        self._entries[self.key(plan, spec)] = entry
        if self.path:
            with open(self.path, "w") as f:
                json.dump(self._entries, f, indent=1, sort_keys=True)

    def __len__(self) -> int:
        return len(self._entries)


def _tile_candidates(plan: Plan, spec: Optional[SearchSpec] = None) -> list:
    """Small neighbourhood sweep around the model's pick.

    Halved/doubled tiles, clamped to validity (sublane floor, bin-size
    multiples, never beyond the data) — the refinement is a *local* check
    of the model, not a grid search; anything further from the model's
    optimum than 2x is the model being wrong, which is a bug to fix in the
    model, not something to tune around.  Only knobs the sweep may
    legitimately move are varied: user-pinned ``spec`` fields stay fixed,
    and the dense XLA path ignores the Pallas tiles, so for it only
    ``query_block`` varies.
    """
    sublane = _SUBLANE.get(_dtype_bytes(plan.dtype), 8)
    # the database tile is stored-dtype (int8 tiles at 32 sublanes); the
    # query tile (block_m) follows the compute dtype — same split as
    # _plan_tiles, or the sweep would propose Mosaic-mistiled candidates.
    sbytes = (
        _dtype_bytes(plan.dtype) if plan.storage == "f32"
        else quant.storage_bytes(plan.storage)
    )
    unit = max(plan.bin_size, _SUBLANE.get(sbytes, 8))
    n_aligned = round_up(plan.n, unit)

    def clamp_bm(v):
        return max(sublane, min(1024, round_up(v, sublane)))

    def clamp_bn(v):
        return max(unit, min(n_aligned, round_up(v, unit)))

    def clamp_qb(v):
        return max(8, min(8192, round_up(v, 8)))

    pallas = plan.backend in ("pallas", "sharded")
    m_factors = (1, 0.5, 2) if pallas and (
        spec is None or spec.block_m is None) else (1,)
    n_factors = (1, 0.5, 2) if pallas and (
        spec is None or spec.max_block_n is None) else (1,)
    q_factors = (1, 0.5, 2) if (
        spec is None or spec.query_block is None) else (1,)
    cands = []
    for fm in m_factors:
        for fn in n_factors:
            for fq in q_factors:
                c = (
                    clamp_bm(int(plan.block_m * fm)),
                    clamp_bn(int(plan.block_n * fn)),
                    clamp_qb(int(plan.query_block * fq)),
                )
                if c not in cands:
                    cands.append(c)
    return cands


def tune_plan(
    database,
    plan: Plan,
    *,
    spec: Optional[SearchSpec] = None,
    cache: Optional[PlanCache] = None,
    repeats: int = 3,
    interpret: Optional[bool] = None,
) -> Plan:
    """Refine a model plan with a short on-device sweep (``plan="measure"``).

    Builds a throwaway index per candidate tile triple, times a
    ``query_block``-sized synthetic batch, and returns the plan rewritten
    with the fastest configuration (``source="measure"``).  Results persist
    in ``cache`` so the sweep runs once per workload signature per device.

    ``spec`` is the workload's real ``SearchSpec``: candidates are built by
    replacing only its tile fields, so the sweep times the exact program
    the index will run (same dtype, rescoring mode, recall accounting) and
    user-pinned tile fields are never varied.  Pinned sweeps are cached
    under a distinct key so their result is not served to unpinned builds.

    This subsumes the old per-config hillclimb harness for search kernels:
    the model proposes, one bounded measurement disposes.
    """
    import jax
    from repro.search.index import Index  # deferred: index imports plan

    if cache is None:  # NOT ``or``: an empty PlanCache is len()==0/falsy
        cache = PlanCache()
    base_spec = spec if spec is not None else SearchSpec(
        metric=plan.metric, k=plan.k, recall_target=plan.recall_target,
        backend=plan.backend, dtype=None if plan.dtype == "float32"
        else plan.dtype, storage=plan.storage, rescore=plan.rescore,
    )
    hit = cache.get(plan, spec)
    if hit is not None:
        return _with_measured_tiles(
            plan, hit["block_m"], hit["block_n"], hit["query_block"]
        )

    m_eff = plan.m or plan.query_block
    queries = jax.random.normal(
        jax.random.PRNGKey(0), (min(m_eff, 2 * plan.query_block), plan.d)
    )
    best, best_wall = None, float("inf")
    last_error: Optional[Exception] = None
    for bm, bn, qb in _tile_candidates(plan, spec):
        cand = dataclasses.replace(
            base_spec, block_m=bm, max_block_n=bn, query_block=qb,
        )
        try:
            # The fully-pinned spec makes plan="model" a no-op passthrough,
            # so candidate builds never recurse into another sweep.
            index = Index.build(
                database, spec=cand, plan="model", interpret=interpret
            )
            wall = time_search(index, queries, repeats=repeats, passes=1)
        except Exception as e:  # invalid candidate on this backend — skip
            last_error = e
            continue
        if wall < best_wall:
            best, best_wall = (bm, bn, qb), wall
    if best is None:
        # Every candidate failed: keep the model's answer, but loudly —
        # a systemic build/search error here would bite real searches too.
        import warnings

        warnings.warn(
            "plan measurement failed for every candidate; keeping the "
            f"unmeasured model plan (last error: {last_error!r})",
            RuntimeWarning,
            stacklevel=2,
        )
        return plan
    cache.put(plan, {
        "block_m": best[0], "block_n": best[1], "query_block": best[2],
        "wall_s": best_wall, "source": "measure",
    }, spec)
    return _with_measured_tiles(plan, *best)


# --- HLO cross-check (absorbing analysis.hlo_cost into the planner) ----------


def hlo_check(plan: Plan, lowered_text: str) -> dict:
    """Compare the plan's analytic cost against compiler-reported HLO cost.

    ``lowered_text`` is optimized HLO (``jax.jit(f).lower(...).compile()
    .as_text()``).  Returns the analytic and HLO FLOP counts plus their
    ratio — the planner's self-audit that Eq. 4–10 describe the program XLA
    actually built (the matmul FLOPs must agree; byte models are
    fusion-granularity estimates on both sides, so only reported).
    """
    from repro.analysis.hlo_cost import analyze_hlo

    hlo = analyze_hlo(lowered_text)
    return {
        "model_flops": plan.flops,
        "hlo_dot_flops": hlo.dot_flops,
        "flops_ratio": hlo.dot_flops / max(plan.flops, 1e-30),
        "model_hbm_bytes": plan.hbm_bytes,
        "hlo_hbm_bytes": hlo.hbm_bytes,
        "hlo_hbm_bytes_bounds": (hlo.hbm_bytes_lo, hlo.hbm_bytes_hi),
        "model_cops": plan.cops,
        "hlo_cop_count": hlo.cop_count,
    }

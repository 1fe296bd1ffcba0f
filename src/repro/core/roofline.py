"""Instruction-throughput-aware roofline model (paper §4, Eq. 6).

P  <=  min( pi,  beta * I_MEM,  gamma * I_COP )

with pi = peak matmul FLOP/s, beta = HBM bytes/s, gamma = peak
coefficient-wise op (COP) throughput.  Includes the paper's Table 1 hardware
plus TPU v5e (this repo's deployment target) and the kernel cost accounting
of Appendix A.3/A.5 (I_MEM Eq. 20, COPs-per-dot C).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

__all__ = [
    "Hardware",
    "HARDWARE",
    "KernelCost",
    "attainable_flops",
    "bottleneck",
    "partial_reduce_cost",
    "RooflineTerms",
    "roofline_terms",
]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float          # pi  [FLOP/s]
    hbm_bandwidth: float       # beta [bytes/s]
    peak_cops: float           # gamma [COP/s]
    hbm_bytes: float = 16e9    # per-chip HBM capacity
    ici_bandwidth: float = 50e9  # per-link interconnect [bytes/s]
    # Fast on-chip memory available to one kernel instance (TPU: VMEM per
    # core; GPU: shared memory + L2 slice).  The kernel planner
    # (repro.search.plan) sizes its tiles against a fraction of this
    # (operand tiles are double-buffered; see plan._vmem_budget).
    vmem_bytes: float = 16 * 2**20


HARDWARE: Dict[str, Hardware] = {
    # Paper Table 1.
    "v100": Hardware("GPU V100", 125e12, 900e9, 15.7e12),
    "a100": Hardware("GPU A100", 312e12, 1555e9, 19.5e12),
    "tpu_v3": Hardware("TPU V3", 126e12, 858e9, 4.0e12),
    "tpu_v4": Hardware("TPU V4", 274e12, 1144e9, 4.3e12),
    # Deployment target for this repo (brief): 197 bf16 TFLOP/s, 819 GB/s HBM,
    # ~50 GB/s/link ICI.  gamma estimated from VPU geometry (8x128 lanes x 2
    # unit x ~940MHz x 2 cores) ~= 3.9 TCOP/s, same methodology as Table 1.
    "tpu_v5e": Hardware("TPU v5e", 197e12, 819e9, 3.9e12, hbm_bytes=16e9),
    # Development host (the CI/interpret-mode environment).  Rough orders of
    # magnitude for a server-class CPU socket; the planner only uses the
    # *ratios* (roofline walls) and the vmem tile budget, which is set to the
    # TPU value so host-planned tiles match what the TPU would get.
    "cpu": Hardware("CPU host", 0.5e12, 100e9, 0.1e12, hbm_bytes=64e9),
}


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Workload description of one kernel: FLOPs, HBM bytes, COPs."""

    flops: float
    hbm_bytes: float
    cops: float

    @property
    def i_mem(self) -> float:
        return self.flops / max(self.hbm_bytes, 1e-30)

    @property
    def i_cop(self) -> float:
        return self.flops / max(self.cops, 1e-30)


def attainable_flops(cost: KernelCost, hw: Hardware) -> float:
    """Eq. 6: attainable performance of a kernel on given hardware."""
    return min(hw.peak_flops, hw.hbm_bandwidth * cost.i_mem, hw.peak_cops * cost.i_cop)


def bottleneck(cost: KernelCost, hw: Hardware) -> str:
    terms = {
        "compute": hw.peak_flops,
        "memory": hw.hbm_bandwidth * cost.i_mem,
        "instruction": hw.peak_cops * cost.i_cop,
    }
    return min(terms, key=terms.get)


def partial_reduce_cost(
    m: int,
    n: int,
    d: int,
    l: int,
    *,
    cops_per_dot: float = 3.0,
    block_rows: int = 512,
    dtype_bytes: int = 4,
    db_bytes: int = None,
) -> KernelCost:
    """Cost model of the PartialReduce kernel (Appendix A.3).

    FLOPs  = 2MND (the einsum)
    bytes  = 4(MD + MND/ib + 2ML)  -- Eq. 20, ib = query block rows
    COPs   = C * M * N             -- C per dot product (A.5 accounting)

    ``db_bytes`` prices the database-stream term (the MND/ib bytes)
    separately from the query/winner traffic — reduced-precision storage
    tiers (``repro.search.quant``) stream 2- or 1-byte rows while queries
    and bin winners stay at ``dtype_bytes``.  ``None`` keeps the classic
    single-dtype Eq. 20 form.
    """
    if db_bytes is None:
        db_bytes = dtype_bytes
    flops = 2.0 * m * n * d
    hbm = (
        dtype_bytes * (m * d + 2 * m * l)
        + db_bytes * (m / block_rows) * n * d
    )
    cops = cops_per_dot * m * n
    return KernelCost(flops=flops, hbm_bytes=hbm, cops=cops)


def partial_reduce_fused_cost(
    m: int,
    n: int,
    d: int,
    k_scan: int,
    *,
    cops_per_dot: float = 3.0,
    block_rows: int = 512,
    dtype_bytes: int = 4,
    db_bytes: float = None,
    block_n: int = 1024,
    bins_per_block: int = 64,
) -> KernelCost:
    """Cost model of the single-pass fused scan→select kernel (Eq. 20).

    FLOPs  = 2MND (the einsum, unchanged)
    bytes  = dtype(MD) + db_bytes * ceil(M/ib) * ND + 8 M k_scan
    COPs   = C*M*N + M * (T * ceil128(bins_per_block) + k_scan * ceil128(L))

    with T = ceil(N/block_n) database tiles, L = T * bins_per_block bins
    and ceil128 rounding up to whole 128-lane rows.

    Versus :func:`partial_reduce_cost`, the ``2ML`` bin-winner HBM term
    (the (M, N/bin_size) score-tile round trip the two-pass select pays)
    collapses to the O(M·k_scan) final result — the winner buffer lives
    in VMEM across the database stream.  The database term uses the *integer*
    pass count ``ceil(M/ib)``: each query-block grid row streams the whole
    database once, so a fractional M/ib would under-price small batches.
    The extra COP term prices the in-VMEM select: each tile stores its
    bin winners into a winner buffer (one select per 128-lane row it
    touches), and once per query block k_scan max extractions run over
    the buffer's ceil128(L) lanes.  Amortized over the database rows it
    is a lower-order term, priced so tile escalation cannot pretend the
    select is free.
    """
    if db_bytes is None:
        db_bytes = dtype_bytes
    passes = max(1, -(-m // block_rows))  # ceil, floored at one stream
    flops = 2.0 * m * n * d
    hbm = (
        dtype_bytes * m * d
        + db_bytes * passes * n * d
        + 8.0 * m * k_scan
    )
    tiles = max(1, -(-n // max(1, block_n)))
    lanes = 128
    placed = -(-bins_per_block // lanes) * lanes
    bins = -(-tiles * bins_per_block // lanes) * lanes
    cops = (
        cops_per_dot * m * n
        + m * (tiles * placed + k_scan * bins)
    )
    return KernelCost(flops=flops, hbm_bytes=hbm, cops=cops)


def cops_per_dot(
    *,
    base: int = 3,
    l2: bool = False,
    non_pow2_n: bool = False,
    padded_d: bool = False,
    broadcast_norm: bool = False,
) -> int:
    """Appendix A.5 COP accounting: 3 base + 1 per listed condition."""
    c = base
    c += int(l2)              # relaxed distance subtract
    c += int(non_pow2_n)      # database masking
    c += int(padded_d)        # D not a multiple of 128
    c += int(broadcast_norm)  # broadcasting ||x||^2/2
    return c


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Three-term time decomposition for a compiled step on a mesh."""

    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        # Lower bound: perfectly-overlapped execution is max(); serialized is
        # sum().  We report the max-model (roofline convention).
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    chips: int,
    hw: Hardware,
    ici_links: int = 1,
) -> RooflineTerms:
    """Brief-specified three-term roofline for a whole compiled step.

    compute    = FLOPs / (chips * pi)
    memory     = bytes / (chips * HBM bw)
    collective = collective bytes / (chips * ici_links * link bw)

    ici_links defaults to 1 (the brief's convention: ~50 GB/s/link and one
    link's worth of bandwidth counted per chip).
    """
    return RooflineTerms(
        compute_s=hlo_flops / (chips * hw.peak_flops),
        memory_s=hlo_bytes / (chips * hw.hbm_bandwidth),
        collective_s=collective_bytes / (chips * ici_links * hw.ici_bandwidth),
    )

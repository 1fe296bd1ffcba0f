"""The work and the bytes one search needs, from a configuration's shapes.

Counted from the published shapes (N rows of D, M queries, top k), never
from the padded layout or the tiles a planner picked, so the count stays
the same whatever implements the search:

- operations: one multiply-add per query, row and dimension, ``2·M·N·D``,
  priced at the peak of the stored tier's MXU type (bf16 for f32 and bf16
  storage, int8 for int8 and int4);
- bytes: the stored database read once, the queries read, the (M, k)
  values and ids written, and for a quantized tier with an exact rescore
  the least gather that rescore needs, k full-precision rows per query.

Over several chips the same work is spread over all of them: the least
time divides it by the chips times each one's peaks.
"""
from __future__ import annotations

__all__ = ["STORED_BYTES", "least_time", "search_cost"]

# Bytes per stored database element, by storage tier.
STORED_BYTES = {"f32": 4.0, "bf16": 2.0, "int8": 1.0, "int4": 0.5}
# The peak a tier's scan is priced at.
MXU_TYPE = {"f32": "bf16", "bf16": "bf16", "int8": "int8", "int4": "int8"}


def search_cost(cfg: dict, m: int) -> dict:
    """Operations and HBM bytes of one search of ``m`` queries."""
    n, d, k, storage = cfg["n"], cfg["d"], cfg["k"], cfg["storage"]
    nbytes = n * d * STORED_BYTES[storage] + m * d * 4.0 + m * k * 8.0
    if storage != "f32":
        nbytes += m * k * d * 4.0
    return {"ops": 2.0 * m * n * d, "bytes": nbytes,
            "mxu_type": MXU_TYPE[storage]}


def least_time(cost: dict, peaks: dict, chips: int = 1) -> tuple:
    """(seconds, "compute" | "memory"): the larger of the two bounds, with
    the work spread over ``chips`` chips of these peaks."""
    compute = cost["ops"] / (chips * peaks["flops"][cost["mxu_type"]])
    memory = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return (compute, "compute") if compute >= memory else (memory, "memory")

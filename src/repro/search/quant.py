"""Quantized storage tiers: bf16/int8 packed databases with exact rescoring.

The paper's Eq. 10 memory-wall analysis makes per-search cost proportional
to the bytes streamed for the (N, D) database — which means bytes-per-row
directly sets where the roofline knee lands.  This module owns the
``storage`` tier of the search stack:

  * ``"f32"``  — 4 bytes/element, today's exact path (the default; packed
    state, kernels and planner behave bit-identically to before this
    subsystem existed).
  * ``"bf16"`` — 2 bytes/element.  The scan matmul consumes the bf16 rows
    as stored against the queries rounded to bf16 once per query block:
    one bf16 x bf16 MXU pass with f32 accumulation, no per-tile cast, and
    half the database HBM traffic.
  * ``"int8"`` — 1 byte/element with a per-row symmetric scale
    (``row ≈ scale * int8``), quartering database HBM traffic.
  * ``"int4"`` — 0.5 bytes/element with a per-row symmetric scale
    (``row ≈ scale * int4``, codes in [-7, 7]).  The *canonical* stored
    form everywhere above the kernel is one int8 code per element (so the
    XLA reference paths, cluster gathers and snapshots stay byte-wise and
    backend-agnostic); the Pallas layout packs two codes per byte
    (:func:`pack_int4_rows`) and the scan kernel unpacks the nibbles in
    VMEM, so the 8x HBM-traffic drop is realized where the memory wall
    actually is.

Quantized tiers run a **two-pass search** mirroring the paper's
score/rescore split: PartialReduce scans the quantized database over all N
rows to produce an *over-fetched* candidate set (see :func:`scan_k`), then
``core.rescoring`` re-scores only those candidates against a full-precision
rescore tail — O(M·L·D) exact work, within Eq. 10's O(min(M, N)) budget.

Over-fetch derivation (why the Eq. 13–14 guarantee survives quantization)
-------------------------------------------------------------------------

With exact scores, a true top-K entry is lost only when a *better* top-K
entry shares its bin — the ball-in-bins argument behind
``E[recall] = ((L-1)/L)^(K-1)`` (Eq. 13).  With quantized scan scores, a
top-K entry can additionally lose its bin to a truly-worse row that
quantization *promotes* past it; that requires the rival's true score to
lie within the quantization band ``2·eps`` of the entry's.  Budget at most
``T`` such in-band rivals per top-K entry and treat each, conservatively,
exactly like a truly-better entry in the bin argument: the scan's candidate
set then contains the true top-K with

    E[recall_scan] >= ((L-1)/L)^(K+T-1)

so planning the bins for an **effective K' = K + T at the original recall
target** (and rescoring the L winners exactly) preserves the guarantee in
expectation.  The per-tier confusion budgets

    T(bf16) = ceil(K/2)        T(int8) = K        T(int4) = 2K

follow from the tiers' relative score-error bounds (bf16 keeps an 8-bit
mantissa, relative error ~2^-8; per-row symmetric int8 bounds the per-entry
error at ``scale/2`` with ``scale = max|row|/127``, a ~0.4 % relative score
error for well-conditioned rows; int4's ``scale = max|row|/7`` widens the
band 16x to a ~7 % relative error, so its in-band rival budget doubles
again) under a bounded near-tie density — they are deliberately
conservative, and ``tests/test_recall_guarantee.py`` validates the
end-to-end recall empirically with a Hoeffding margin.

The bf16 scan rounds the queries as well as the rows, each to the same
unit roundoff ``u = 2^-8``.  The budget still holds: the bias is computed
from the stored rows, so a row's rounding ``dx`` enters the l2 scan score
as ``<q - x, dx>`` and the query's ``dq`` as ``<dq, x>``, two independent
terms, and the score's rms error grows by about a tenth over rounding the
rows alone (0.0377 -> 0.0419 score units for Gaussian rows at D = 128,
N = 2^16 and 2^18).  Against the budget's ``ceil(K/2) = 5`` in-band
rivals at K = 10, the count that matters — truly-worse rows whose scan
score passes a true top-K entry's — is 0.006 per entry there, never more
than 1 (``tests/test_quant.py`` counts it).

Nothing here imports the rest of ``repro.search`` — the metric registry,
packed state, planner and backends all build *on* these primitives.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = [
    "STORAGE_TIERS",
    "QuantizedRows",
    "check_metric_storage",
    "dequantize_rows",
    "is_quantized",
    "pack_int4_rows",
    "quantize_rows",
    "scan_k",
    "storage_bytes",
    "storage_dtype",
    "unpack_int4_rows",
    "validate_restored",
]

# The legal ``SearchSpec.storage`` values, in decreasing bytes/element.
STORAGE_TIERS: Tuple[str, ...] = ("f32", "bf16", "int8", "int4")

_BYTES = {"f32": 4, "bf16": 2, "int8": 1, "int4": 0.5}
# Stored container dtype per tier.  int4 codes live in an int8 container:
# unpacked (one code per byte, values in [-7, 7]) in the canonical form,
# two codes per byte in the Pallas layout (pack_int4_rows).
_DTYPES = {
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "int4": jnp.int8,
}

# Smallest representable per-row scale: keeps all-zero rows quantizing to
# zeros instead of dividing by zero.
_SCALE_FLOOR = 1e-30

_INT8_MAX = 127.0
_INT4_MAX = 7.0

# Tiers that carry a per-row scale table alongside the stored rows.
_SCALED_TIERS = ("int8", "int4")


def is_quantized(storage: str) -> bool:
    """True for tiers that store fewer than 4 bytes per element."""
    return storage_bytes(storage) < 4


def storage_bytes(storage: str) -> float:
    """Bytes per stored database element for a tier.

    Integral for the byte-wise tiers; ``0.5`` for int4, where the Pallas
    layout packs two codes per byte (the XLA reference paths keep one code
    per byte — see :func:`pack_int4_rows`).

    >>> [storage_bytes(s) for s in STORAGE_TIERS]
    [4, 2, 1, 0.5]
    """
    try:
        return _BYTES[storage]
    except KeyError:
        raise ValueError(
            f"unknown storage tier {storage!r}; expected one of "
            f"{STORAGE_TIERS}"
        ) from None


def storage_dtype(storage: str):
    """The jnp dtype rows of a tier are stored in."""
    storage_bytes(storage)  # validate
    return _DTYPES[storage]


def quantize_rows(
    rows: jnp.ndarray, storage: str
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Quantize metric-prepared f32 rows into a tier's stored form.

    Returns ``(stored, scale)`` where ``scale`` is the per-row symmetric
    scale for the scaled tiers (``rows ≈ stored * scale[:, None]``) and
    ``None`` for the others.  Pure per-row math — the property
    ``Index.add`` exploits to quantize only the appended slice.  int4
    returns *unpacked* codes (one int8 per element, values in [-7, 7]) —
    the canonical form; nibble-packing is a Pallas layout concern
    (:func:`pack_int4_rows`).

    >>> import jax.numpy as jnp
    >>> q, s = quantize_rows(jnp.ones((2, 3)), "int8")
    >>> (q.dtype.name, s.shape)
    ('int8', (2,))
    >>> q4, s4 = quantize_rows(jnp.ones((2, 3)), "int4")
    >>> (q4.dtype.name, int(q4.max()), s4.shape)
    ('int8', 7, (2,))
    """
    rows = rows.astype(jnp.float32)
    if storage == "f32":
        return rows, None
    if storage == "bf16":
        return rows.astype(jnp.bfloat16), None
    if storage in _SCALED_TIERS:
        qmax = _INT8_MAX if storage == "int8" else _INT4_MAX
        amax = jnp.max(jnp.abs(rows), axis=-1)
        scale = jnp.maximum(amax / qmax, _SCALE_FLOOR)
        q = jnp.clip(
            jnp.round(rows / scale[:, None]), -qmax, qmax
        ).astype(jnp.int8)
        return q, scale.astype(jnp.float32)
    raise ValueError(
        f"unknown storage tier {storage!r}; expected one of {STORAGE_TIERS}"
    )


def dequantize_rows(
    stored: jnp.ndarray, scale: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """f32 view of stored rows — the values the quantized scan *actually*
    ranks by, used to fold the metric-bias correction into the bias row."""
    rows = stored.astype(jnp.float32)
    if scale is not None:
        rows = rows * scale[:, None]
    return rows


def pack_int4_rows(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack canonical int4 codes (one int8 per element) two-per-byte.

    Column ``2j`` lands in byte ``j``'s low nibble, column ``2j+1`` in its
    high nibble; an odd trailing column is padded with a zero code.  This
    is the on-device layout the Pallas scan kernel streams — half the HBM
    bytes of the canonical form — and :func:`unpack_int4_rows` inverts it
    exactly.

    >>> import jax.numpy as jnp
    >>> codes = jnp.asarray([[-7, 3, 5, -1]], dtype=jnp.int8)
    >>> packed = pack_int4_rows(codes)
    >>> packed.shape
    (1, 2)
    >>> bool((unpack_int4_rows(packed) == codes).all())
    True
    """
    if codes.shape[-1] % 2:
        pad = [(0, 0)] * (codes.ndim - 1) + [(0, 1)]
        codes = jnp.pad(codes, pad)
    lo = codes[..., 0::2].astype(jnp.int32)
    hi = codes[..., 1::2].astype(jnp.int32)
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def unpack_int4_rows(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4_rows`: bytes back to int8 codes.

    The output's trailing dimension is ``2 *`` the packed one; callers
    slice off the odd-``d`` pad column if they need the logical width.
    """
    b = packed.astype(jnp.int32)
    lo = (b << 28) >> 28  # arithmetic shifts sign-extend the low nibble
    hi = b >> 4
    interleaved = jnp.stack([lo, hi], axis=-1)
    return interleaved.reshape(*packed.shape[:-1], -1).astype(jnp.int8)


def scan_k(storage: str, k: int, *, n: Optional[int] = None) -> int:
    """Effective neighbour count the quantized scan plans its bins for.

    Implements the over-fetch derivation in the module docstring:
    ``K' = K + T`` with the tier's confusion budget ``T``.  ``n`` clamps
    the result to the database size (``plan_bins`` requires ``k <= n``).

    >>> scan_k("f32", 10), scan_k("bf16", 10), scan_k("int8", 10)
    (10, 15, 20)
    >>> scan_k("int4", 10)
    30
    """
    if k <= 0:
        raise ValueError(f"k must be positive, got {k}")
    if storage == "bf16":
        k = k + math.ceil(k / 2)
    elif storage == "int8":
        k = 2 * k
    elif storage == "int4":
        k = 3 * k
    else:
        storage_bytes(storage)  # validate the tier name
    if n is not None:
        k = min(k, n)
    return k


def check_metric_storage(metric, storage: str) -> None:
    """Reject unsupported metric × storage combinations, actionably.

    ``metric`` is a ``repro.search.metrics.Metric`` (duck-typed here to
    keep this module import-free).  Metrics declare the tiers their
    prepared rows survive in ``Metric.storage_tiers``; e.g. a raw cosine
    variant whose ``prepare_database`` does *not* normalize rows should
    exclude ``"int8"`` — per-row scales cannot bound its score error, and
    the failure would otherwise surface as a cryptic kernel-level error.
    """
    storage_bytes(storage)  # validate the tier name first
    tiers = getattr(metric, "storage_tiers", STORAGE_TIERS)
    if storage not in tiers:
        raise ValueError(
            f"metric {metric.name!r} does not support storage="
            f"{storage!r} (supported tiers: {tuple(tiers)}).  Either pick "
            "a supported tier, or register the metric with a "
            "quantization-compatible preparation (normalized/bounded rows) "
            "and declare it via Metric(storage_tiers=...)."
        )


def validate_restored(storage: str, db_dtype, has_scale: bool) -> None:
    """Consistency check for a snapshot-restored packed database.

    A snapshot's META names the storage tier and its arrays carry the
    stored rows — if they disagree (truncated write that dodged the
    commit protocol, hand-edited META, version skew) the search kernels
    would fail deep inside a dispatch with a dtype error, or worse,
    silently misinterpret int8 codes.  Fail here instead, actionably.

    >>> import jax.numpy as jnp
    >>> validate_restored("int8", jnp.int8, has_scale=True)
    >>> validate_restored("f32", jnp.float32, has_scale=False)
    """
    expected = storage_dtype(storage)
    if is_quantized(storage) and jnp.dtype(db_dtype) != jnp.dtype(expected):
        raise ValueError(
            f"snapshot claims storage={storage!r} but the stored rows are "
            f"{jnp.dtype(db_dtype).name} (expected "
            f"{jnp.dtype(expected).name}) — corrupt or version-skewed "
            "snapshot; rebuild the index"
        )
    if (storage in _SCALED_TIERS) != has_scale:
        raise ValueError(
            f"snapshot storage={storage!r} "
            + ("is missing its per-row scale table"
               if storage in _SCALED_TIERS
               else "carries an unexpected scale table")
            + " — corrupt or version-skewed snapshot; rebuild the index"
        )


@dataclasses.dataclass
class QuantizedRows:
    """One metric-prepared, tier-quantized row slice (build or ``add``).

    Attributes:
      rows: stored-dtype rows (what the scan matmul consumes; canonical
        unpacked codes for int4).
      scale: per-row f32 scale (int8/int4 tiers) or None.
      bias: metric bias *of the stored values* (the metric-bias correction
        folded into the fused bias row, so quantized scan scores are
        internally consistent), or None.
      exact_rows: full-precision metric-prepared rows — the rescore tail.
      exact_bias: metric bias of ``exact_rows`` (what the rescore pass and
        the f32 path use), or None.
    """

    rows: jnp.ndarray
    scale: Optional[jnp.ndarray]
    bias: Optional[jnp.ndarray]
    exact_rows: jnp.ndarray
    exact_bias: Optional[jnp.ndarray]

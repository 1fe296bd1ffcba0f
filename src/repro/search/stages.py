"""Composable search stages: scan -> rescore -> gather/merge.

Every layout this package executes — the replicated xla/pallas programs,
the 1-D and 2-D sharded programs (paper §7), and the host-RAM cold tier's
segment waves — is an assembly of the same four stage primitives over
metric-prepared operands in the *internal max convention* (maximize
``<q', x'> + bias``, negate once at the API boundary):

  * :func:`score_rows`       — the streamed score matmul + fused bias
    (one additive COP carrying metric bias, tombstones and tail mask).
  * :func:`scan_candidates`  — the PartialReduce / ApproxTopK bin scan
    (Eq. 13–14 recall accounting, optionally against a *global* N when
    the operand is one shard or one segment of a larger database).
  * :func:`rescore_candidates` — the exact second pass of the quantized
    two-pass search: cut the bin winners to the ``k_scan`` over-fetch
    budget, gather the full-precision tail, re-score exactly.  Shards and
    host segments run it on *local* candidate ids before any merge, so
    the gather never crosses the interconnect (rescore-before-gather).
  * :func:`merge_topk`       — exact top-k merge of candidate streams
    (the all-gather reduction of the sharded path; the per-wave carry
    merge of the host tier).

:func:`prune_candidates` is the optional cluster-pruning front-end that
replaces the streamed scan's candidate set with gathered slots, and
:func:`finalize_values` applies the metric's single sign flip.

On the Pallas backend the fused kernel
(``repro.kernels.partial_reduce.partial_reduce_fused``) subsumes the
scan → ``merge_topk`` pair: the top-``k_scan`` is selected in VMEM
during the scan, so the composed pipeline degenerates to
score+scan+select (one dispatch, Eq. 20 traffic) followed by the same
rescore/finalize stages.  The two-pass composition remains the parity
oracle (``SearchSpec(fused_select=False)``).

These functions are deliberately *pure shape-in/shape-out jax* — no jit,
no counters, no layout knowledge.  ``repro.search.backends`` composes
them into the entry points ``Index`` dispatches (where tracing/dispatch
accounting lives), and the property tests in
``tests/test_packed_invariants.py`` assert that stage composition equals
the monolithic dense reference under arbitrary add/delete interleavings.

History note: these bodies were extracted verbatim from the accreted
dense/packed/quant/cluster × one-pass/two-pass variants in
``backends.py`` — op order is unchanged on purpose, so the refactor is
bit-identical to the pre-stage programs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rescoring import exact_rescoring
from repro.core.topk import approx_max_k

__all__ = [
    "MASK_VALUE",
    "score_rows",
    "scan_candidates",
    "rescore_candidates",
    "prune_candidates",
    "merge_topk",
    "finalize_values",
    "pad_queries_to",
    "sentinelize_masked",
]

# Finite -inf surrogate (float32 min): keeps the MXU/VPU paths free of NaN
# propagation while still losing every comparison against real scores.
MASK_VALUE = float(np.finfo(np.float32).min)

# Dots whose scores are returned (the f32 tier's scan, every exact rescore)
# run at full f32 precision: the TPU's default rounds f32 operands to bf16.
# A narrower tier's scan only ranks candidates and keeps the default.
EXACT = jax.lax.Precision.HIGHEST

Array = jnp.ndarray


def sentinelize_masked(vals: Array, idxs: Array, n: int) -> Array:
    """Pair masked candidates with the sentinel index ``-1``.

    A masked winner (fully tombstoned bin, padded tail) carries a
    meaningless index; clamping it into ``[0, n)`` — the historical
    behaviour — let it alias row ``n-1`` and surface as a phantom
    duplicate neighbour once ``merge_topk`` tied at ``-inf``.  Keeping the
    ``-inf`` value paired with ``-1`` through the merge makes masked
    entries collision-free; live winners are clamped into range here (the
    only clamp the pipeline applies, at finalize order).  The fused Pallas
    kernel applies the identical rule in VMEM, so fused and two-pass
    outputs agree bitwise.
    """
    return jnp.where(vals > MASK_VALUE * 0.5, jnp.minimum(idxs, n - 1), -1)


def pad_queries_to(q: Array, width: int) -> Array:
    """Zero-pad query lanes up to a packed layout's d_pad (exact for dot
    products — the database's padded lanes are zero too)."""
    if q.shape[1] == width:
        return q
    return jnp.pad(q, ((0, 0), (0, width - q.shape[1])))


# --- stage 1: score -----------------------------------------------------------


def score_rows(
    q: Array,
    database: Array,
    row_bias: Optional[Array] = None,
    scale: Optional[Array] = None,
) -> Array:
    """Streamed biased-MIPS score tile: ``q @ db.T (* scale) + bias``.

    ``q`` must already be metric-prepared; ``database`` holds the stored
    rows of any tier (int8 rows score through ``scale``, the per-row
    dequantization scale).  bf16 rows are scored against the queries
    rounded to bf16, with f32 accumulation, as the Pallas kernel scores
    them (``kernels.partial_reduce._scan_queries``), so both backends rank
    by the same scores.  ``row_bias`` is the fused bias row — adding it
    *after* the scale keeps quantized scan scores internally consistent
    (the bias is computed from the stored values).
    """
    if database.dtype == jnp.bfloat16 and q.dtype == jnp.float32:
        scores = jnp.einsum("ik,jk->ij", q.astype(jnp.bfloat16), database,
                            preferred_element_type=jnp.float32)
    else:
        exact = database.dtype == jnp.float32
        scores = jnp.einsum("ik,jk->ij", q, database,
                            precision=EXACT if exact else None)
    if scale is not None:
        scores = scores * scale[None, :]
    if row_bias is not None:
        scores = scores + row_bias[None, :]
    return scores


def score_gathered(
    q: Array,
    rows: Array,
    row_bias: Array,
    ids: Array,
    valid: Array,
    scale: Optional[Array] = None,
) -> Array:
    """Gathered biased-MIPS scores over per-query candidate rows.

    ``rows`` is the (m, S, d) gather ``database[ids]`` (cast to f32 by
    the caller when the tier stores narrower rows); invalid slots (empty
    cluster tails, slots another shard owns) score ``MASK_VALUE`` so they
    can never win a bin.
    """
    scores = jnp.einsum("md,msd->ms", q, rows)
    if scale is not None:
        scores = scores * scale.reshape(-1)[ids]
    scores = scores + row_bias.reshape(-1)[ids]
    return jnp.where(valid, scores, MASK_VALUE)


# --- stage 2: scan (the Eq. 13-14 bin reduction) ------------------------------


def scan_candidates(
    scores: Array,
    k: int,
    *,
    recall_target: float,
    reduction_input_size_override: int = -1,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
) -> Tuple[Array, Array]:
    """PartialReduce the score tile into L bin winners (or the top-k).

    ``reduction_input_size_override`` carries the recall accounting when
    ``scores`` covers only a shard or a host-tier segment of a larger
    database (paper §7): bins are then laid out as if the scan saw the
    global N, which is what makes the per-partition collision terms
    compose into the global Eq. 13 bound.
    """
    return approx_max_k(
        scores,
        k,
        recall_target=recall_target,
        reduction_input_size_override=reduction_input_size_override,
        aggregate_to_topk=aggregate_to_topk,
        use_bitonic=use_bitonic,
    )


# --- stage 3: rescore (exact second pass of the quantized tiers) --------------


def rescore_candidates(q, scan_vals, idxs, rescore_db, rescore_bias, k,
                       k_scan, use_bitonic=False):
    """Exact second pass of the quantized search (internal max convention).

    Two stages, mirroring the paper's score/rescore split with the *scan*
    at reduced precision: first the L bin winners are cut to the
    ``k_scan`` best by quantized score (``k_scan = k + T``, the
    over-fetch budget of ``repro.search.quant.scan_k`` — a true top-k
    entry drops out only past T quantization-promoted rivals, the same
    event the bin over-fetch already insures), then only those O(M·K')
    rows are gathered from the full-precision rescore tail and re-scored
    exactly.  Candidates the scan masked (tombstoned rows, padded bins —
    their clamped indices would otherwise rescore to a live row's true
    score and duplicate it into top-k) stay masked.

    ``idxs`` index ``rescore_db`` directly, so on sharded/host-tiered
    layouts they are *local* (shard- or segment-relative) ids — rescoring
    happens before any offset translation or gather across partitions.
    """
    if k_scan < scan_vals.shape[-1]:
        scan_vals, sel = jax.lax.top_k(scan_vals, k_scan)
        idxs = jnp.take_along_axis(idxs, sel, axis=-1)
    rows = rescore_db[idxs]                           # (m, k_scan, d) gather
    exact = jnp.einsum("md,mld->ml", q, rows, precision=EXACT)
    exact = exact + rescore_bias[idxs]
    exact = jnp.where(scan_vals > MASK_VALUE * 0.5, exact, MASK_VALUE)
    return exact_rescoring(exact, idxs, k, mode="max", use_bitonic=use_bitonic)


# --- optional front-end: cluster pruning --------------------------------------


def prune_candidates(q, centroids, centroid_bias, cluster_rows,
                     spill_rows, probes):
    """Per-query candidate row ids from the pruning side tables.

    Scores the prepared queries against the (C, d) centroids with the same
    biased-MIPS convention as the row scan, keeps the top-``probes``
    clusters, and concatenates their slot tables with the always-scanned
    spill block.  Returns ``(ids, valid)`` where ``ids`` (m, S) are
    *user-space* row ids clamped to >= 0 and ``valid`` marks real slots —
    empty slots (padded tails of partially-filled clusters, unused spill
    capacity) must be masked by the caller so they can never win a bin.

    The slot order INTERLEAVES the probed clusters (slot j of every
    cluster, then slot j+1, ...) instead of concatenating them whole.
    Eq. 13's collision bound assumes the true top-k land in random bins;
    cluster-contiguous order breaks that badly — a query's winners
    concentrate in its best cluster's slots, adjacent slots share a bin,
    and measured recall falls below the planned collision term.
    Interleaving spreads each cluster across the bin space, restoring the
    random-placement regime the plan prices.
    """
    caff = jnp.einsum("md,cd->mc", q, centroids) + centroid_bias[None, :]
    _, top_c = jax.lax.top_k(caff, probes)
    m = q.shape[0]
    slots = cluster_rows[top_c]                       # (m, probes, R)
    slots = slots.swapaxes(1, 2).reshape(m, -1)       # (m, R * probes)
    spill = jnp.broadcast_to(
        spill_rows[None, :], (m, spill_rows.shape[0])
    )
    ids = jnp.concatenate([slots, spill], axis=1)     # (m, S)
    return jnp.maximum(ids, 0), ids >= 0


# --- stage 4: gather/merge ----------------------------------------------------


def merge_topk(
    vals: Array,
    idxs: Array,
    k: int,
    *,
    extra_vals: Optional[Array] = None,
    extra_idxs: Optional[Array] = None,
    use_bitonic: bool = False,
) -> Tuple[Array, Array]:
    """Exact top-k reduction of one or two candidate streams.

    The merge node every distributed layout ends in: the sharded path
    all-gathers per-shard winners and merges them here; the host tier
    merges each segment wave's winners into the running (m, k) carry.
    Values are compared as-is (internal max convention) — since every
    partition computes a given row's score from identical bits, the merge
    is order-insensitive up to exact-tie placement.
    """
    if extra_vals is not None:
        vals = jnp.concatenate([vals, extra_vals], axis=-1)
        idxs = jnp.concatenate([idxs, extra_idxs], axis=-1)
    return exact_rescoring(vals, idxs, k, mode="max", use_bitonic=use_bitonic)


def finalize_values(vals: Array, negate_output: bool) -> Array:
    """The single internal-max -> public-value sign flip (metric contract
    in ``repro.search.metrics``); every composed pipeline applies it
    exactly once, at the very end."""
    return -vals if negate_output else vals

"""Backend implementations behind the unified search API.

Three interchangeable executions of the same algorithm (score matmul ->
PartialReduce -> ExactRescoring), all consuming metric-prepared operands and
an additive per-row bias (metric bias + tombstone mask), all returning the
*internal* max-convention first and negating once for distance metrics:

  * ``dense_search``  — pure-XLA reference path (einsum + approx_max_k).
  * ``pallas_search`` — fused Pallas PartialReduce kernel (interpret mode on
    CPU, compiled on TPU); cosine works here too since it is biased MIPS.
  * ``make_sharded_search_fn`` — shard_map over a database axis with
    ``reduction_input_size_override`` recall accounting (paper §7); each
    shard runs the fused Pallas kernel over its block of rows on a TPU
    mesh (``shard_scan``), the XLA scan elsewhere.

``TRACE_COUNTS`` increments once per *trace* of each backend (the body of a
jitted function only runs while tracing), which is how the compile-cache
tests assert "no retrace on same-shape repeat searches".  ``DISPATCH_COUNTS``
increments once per compiled-callable *invocation* from ``Index`` — the
streaming executor's "one dispatch for an 8-block batch" contract is
asserted against it.  Both have ``reset_*`` helpers; tests should reset
rather than do cross-test counter arithmetic.

The steady-state entry points (``dense_search``, ``pallas_search_packed``)
consume pre-packed operands from ``repro.search.packed`` and perform no
database-sized padding or preparation; ``pallas_search`` keeps the one-shot
pack-inside-jit behavior for the functional API and the legacy shims.
"""
from __future__ import annotations

import collections
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.binning import plan_bins, round_up
from repro.core.partial_reduce import partial_reduce_with_plan
from repro.kernels.partial_reduce import (
    SELECT_MODE,
    partial_reduce_fused,
    partial_reduce_packed,
    partial_reduce_pallas,
    scan_operands,
)
from repro.search.metrics import get_metric
from repro.search.plan import _plan_query_block, shard_bin_plan
from repro.search import telemetry
from repro.search.stages import (
    EXACT,
    MASK_VALUE,
    finalize_values,
    merge_topk,
    pad_queries_to,
    prune_candidates,
    rescore_candidates,
    scan_candidates,
    score_gathered,
    score_rows,
    sentinelize_masked,
)

__all__ = [
    "MASK_VALUE",
    "TRACE_COUNTS",
    "DISPATCH_COUNTS",
    "FUSED_SELECT_COUNTS",
    "SCAN_OPERAND_COUNTS",
    "SHARD_SCAN_COUNTS",
    "CompileCache",
    "ShardKernel",
    "count_dispatch",
    "cluster_search",
    "cluster_search_quant",
    "dense_search",
    "dense_search_quant",
    "pallas_search",
    "pallas_search_packed",
    "pallas_search_packed_quant",
    "prepare_pallas_inputs",
    "make_sharded_search_fn",
    "normalize_db_axes",
    "db_shard_count",
    "default_backend",
    "shard_scan",
    "reset_trace_counts",
    "reset_dispatch_counts",
]

# MASK_VALUE is defined in (and re-exported from) ``repro.search.stages``.

# backend name -> number of jit traces (test observability hook).
# AtomicCounter (repro.search.telemetry): increments are lock-protected
# read-modify-writes, and the global registry adopts the dict so one
# telemetry export / reset_all() covers it.
TRACE_COUNTS = telemetry.AtomicCounter()

# backend name -> number of compiled-callable invocations issued by Index
# (one per device dispatch; the streaming executor issues exactly one for
# an arbitrarily large query batch).
DISPATCH_COUNTS = telemetry.AtomicCounter()

# select mode -> dispatches whose program runs the fused scan→select
# kernel (``count_dispatch``).
FUSED_SELECT_COUNTS = telemetry.AtomicCounter()

# scan operand form (``kernels.partial_reduce.scan_operands``: "f32_highest",
# "bf16", "int8_upcast", "int4_upcast") -> dispatches whose program runs the
# Pallas scan with its dot given that form (``count_dispatch``).
SCAN_OPERAND_COUNTS = telemetry.AtomicCounter()

# per-shard scan ("pallas": the fused kernel over the shard's block of the
# kernel layout; "xla": the XLA scan, or the cluster-pruned gather) ->
# dispatches on the sharded backend (``count_dispatch``).
SHARD_SCAN_COUNTS = telemetry.AtomicCounter()

telemetry.registry().register_counter_dict(
    "repro_traces_total", TRACE_COUNTS, "backend",
    "jit traces per backend (steady state: zero growth)",
)
telemetry.registry().register_counter_dict(
    "repro_dispatches_total", DISPATCH_COUNTS, "backend",
    "device dispatches per backend (one per coalesced batch)",
)
telemetry.registry().register_counter_dict(
    "repro_fused_select_dispatches_total", FUSED_SELECT_COUNTS, "mode",
    "dispatches that run the fused scan→select kernel, by select mode",
)
telemetry.registry().register_counter_dict(
    "repro_scan_operand_dispatches_total", SCAN_OPERAND_COUNTS, "operand",
    "dispatches that run the Pallas scan, by the operand form of its dot",
)
telemetry.registry().register_counter_dict(
    "repro_shard_scan_dispatches_total", SHARD_SCAN_COUNTS, "scan",
    "sharded dispatches, by the scan each shard runs",
)


def count_dispatch(backend: str, spec, pk, q_dtype) -> None:
    """Count one device dispatch of ``spec`` on ``backend`` over the
    packed state ``pk`` with queries of ``q_dtype``.  When the dispatched
    program runs the Pallas scan (the dense Pallas path), also count the
    operand form its dot is given, and the fused kernel's select mode when
    ``fused_select`` is on (a rescore implies ``aggregate_to_topk``).  A
    sharded dispatch also counts the scan its shards run."""
    DISPATCH_COUNTS.inc(backend)
    if backend == "sharded":
        SHARD_SCAN_COUNTS.inc(
            "pallas" if pk.block_n and pk.cluster is None else "xla")
    if backend != "pallas" or pk.cluster is not None:
        return
    SCAN_OPERAND_COUNTS.inc(
        scan_operands(q_dtype, pk.db.dtype, pk.storage == "int4"))
    if spec.fused_select_enabled and spec.aggregate_to_topk:
        FUSED_SELECT_COUNTS.inc(SELECT_MODE)


def reset_trace_counts() -> None:
    """Zero ``TRACE_COUNTS`` (tests: reset, act, assert — no arithmetic).

    Deprecated thin alias: ``repro.search.telemetry.reset_all()`` zeroes
    this and every other global series in one call."""
    TRACE_COUNTS.clear()


def reset_dispatch_counts() -> None:
    """Zero ``DISPATCH_COUNTS`` (deprecated alias — prefer
    ``repro.search.telemetry.reset_all()``)."""
    DISPATCH_COUNTS.clear()


def default_backend(mesh: Optional[Mesh] = None) -> str:
    """Resolve backend="auto": sharded with a mesh, pallas on TPU, else xla."""
    if mesh is not None:
        return "sharded"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def shard_scan(mesh: Mesh, interpret: Optional[bool] = None) -> str:
    """The scan each shard of the sharded backend runs: ``"pallas"`` (the
    fused kernel) when the mesh's devices are TPUs or interpret mode was
    asked for, else ``"xla"`` — ``default_backend``'s rule, per shard."""
    if interpret or mesh.devices.flat[0].platform == "tpu":
        return "pallas"
    return "xla"


class CompileCache:
    """Shape/spec-keyed cache of built search callables.

    jax.jit already memoizes traces per callable; this cache additionally
    memoizes the *callables* (closures over static config) so repeat
    searches at the same shape hit the same jitted function — and exposes
    hit/miss counters so tests and users can verify no retracing happens.
    """

    def __init__(self):
        self._fns = {}
        self.hits = 0
        self.misses = 0

    def get(self, key, builder: Callable[[], Callable]) -> Callable:
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            fn = self._fns[key] = builder()
        else:
            self.hits += 1
        return fn

    def info(self) -> dict:
        return {"hits": self.hits, "misses": self.misses, "entries": len(self._fns)}

    def reset_counters(self) -> None:
        """Zero hit/miss counters, keeping the compiled entries."""
        self.hits = 0
        self.misses = 0

    def clear(self) -> None:
        """Drop every entry and zero the counters (forces rebuilds)."""
        self._fns.clear()
        self.reset_counters()


# --- XLA backend ------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "recall_target", "reduction_input_size_override",
        "aggregate_to_topk", "use_bitonic",
    ),
)
def dense_search(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: Optional[jnp.ndarray] = None,
    *,
    metric: str = "mips",
    k: int = 10,
    recall_target: float = 0.95,
    reduction_input_size_override: int = -1,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-XLA search: full score matrix + approx_max_k (paper Listings 1/2).

    ``database`` must already be metric-prepared (e.g. normalized for
    cosine); ``row_bias`` carries the metric bias and/or tombstone mask.
    """
    m = get_metric(metric)
    TRACE_COUNTS.inc("xla")
    q = m.prepare_queries(queries)
    scores = score_rows(q, database, row_bias)
    vals, idxs = scan_candidates(
        scores,
        k,
        recall_target=recall_target,
        reduction_input_size_override=reduction_input_size_override,
        aggregate_to_topk=aggregate_to_topk,
        use_bitonic=use_bitonic,
    )
    return finalize_values(vals, m.negate_output), idxs


# --- Quantized two-pass (scan -> exact rescore), repro.search.quant ---------


# Stage alias: the exact second pass lives in ``repro.search.stages``;
# the underscored name predates the stage split and stays for callers
# (and tests) that reached into this module.
_rescore_candidates = rescore_candidates


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "k_scan", "recall_target",
        "reduction_input_size_override", "aggregate_to_topk", "use_bitonic",
    ),
)
def dense_search_quant(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: Optional[jnp.ndarray],
    scale: Optional[jnp.ndarray],
    rescore_db: Optional[jnp.ndarray],
    rescore_bias: Optional[jnp.ndarray],
    *,
    metric: str,
    k: int,
    k_scan: int,
    recall_target: float = 0.95,
    reduction_input_size_override: int = -1,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """XLA two-pass search over a quantized storage tier.

    ``database`` holds the stored (bf16/int8) metric-prepared rows,
    ``scale`` the int8 per-row dequantization scale (None otherwise), and
    ``row_bias`` the fused bias *of the stored values* (metric-bias
    correction + tombstones).  When ``rescore_db`` is given the scan keeps
    the over-fetched candidate set (bins planned for ``k_scan``,
    ``repro.search.quant.scan_k``) and the exact top-k comes from
    re-scoring those candidates against the full-precision tail; without
    it the quantized scan's own scores are returned (approximate values).
    """
    m = get_metric(metric)
    TRACE_COUNTS.inc("xla")
    q = m.prepare_queries(queries)
    scores = score_rows(q, database, row_bias, scale)
    if rescore_db is not None:
        vals, idxs = scan_candidates(
            scores,
            k_scan,
            recall_target=recall_target,
            reduction_input_size_override=reduction_input_size_override,
            aggregate_to_topk=False,
        )
        vals, idxs = rescore_candidates(
            q, vals, idxs, rescore_db, rescore_bias, k, k_scan, use_bitonic
        )
    else:
        vals, idxs = scan_candidates(
            scores,
            k,
            recall_target=recall_target,
            reduction_input_size_override=reduction_input_size_override,
            aggregate_to_topk=aggregate_to_topk,
            use_bitonic=use_bitonic,
        )
    return finalize_values(vals, m.negate_output), idxs


# --- Cluster-pruned scan (repro.search.cluster) ------------------------------


# Stage aliases (see ``repro.search.stages``): the pruning front-end and
# the lane-padding helper moved to the stage layer; the underscored names
# stay for in-repo callers that predate the split.
_cluster_candidates = prune_candidates
_pad_queries_to = pad_queries_to


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "probes", "target_scan", "aggregate_to_topk",
        "use_bitonic", "trace_as",
    ),
)
def cluster_search(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: jnp.ndarray,
    centroids: jnp.ndarray,
    centroid_bias: jnp.ndarray,
    cluster_rows: jnp.ndarray,
    spill_rows: jnp.ndarray,
    *,
    metric: str,
    k: int,
    probes: int,
    target_scan: float,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
    trace_as: str = "xla",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cluster-pruned search over a packed f32-tier layout (one dispatch).

    Score the C centroids, gather only the top-``probes`` clusters' rows
    (plus the spill block) from the packed database, and reduce those S
    candidates at the planner's inflated ``target_scan`` — the product
    with the cluster-miss budget meets the user's original target
    (``repro.search.cluster``).  Consumes either packed layout: the xla
    (n, d)/(n,) operands or the pallas (n_pad, d_pad)/(1, n_pad) ones —
    gathers are layout-indifferent, which is also why the fused Eq. 20
    kernel is bypassed here: a pruned scan has no sequential database
    stream left to fuse, so both single-device backends share this
    gathered program (``trace_as`` keeps trace accounting under the
    resolved backend's name).  Returned ids are user-space directly — the
    slot tables *are* the permutation map.  Gathered candidates carry the
    fused bias row, so tombstones and masked slots can never surface.
    """
    m_obj = get_metric(metric)
    TRACE_COUNTS.inc(trace_as)
    q = m_obj.prepare_queries(queries)
    idc, valid = prune_candidates(
        q, centroids, centroid_bias, cluster_rows, spill_rows, probes
    )
    qp = pad_queries_to(q, database.shape[1])
    rows = database[idc]                              # (m, S, d) gather
    scores = score_gathered(qp, rows.astype(jnp.float32), row_bias, idc, valid)
    vals, pos = scan_candidates(
        scores, k, recall_target=target_scan,
        aggregate_to_topk=aggregate_to_topk, use_bitonic=use_bitonic,
    )
    idxs = jnp.take_along_axis(idc, pos, axis=-1)
    return finalize_values(vals, m_obj.negate_output), idxs


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "k_scan", "probes", "target_scan",
        "aggregate_to_topk", "use_bitonic", "trace_as",
    ),
)
def cluster_search_quant(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: jnp.ndarray,
    scale: Optional[jnp.ndarray],
    rescore_db: Optional[jnp.ndarray],
    rescore_bias: Optional[jnp.ndarray],
    centroids: jnp.ndarray,
    centroid_bias: jnp.ndarray,
    cluster_rows: jnp.ndarray,
    spill_rows: jnp.ndarray,
    *,
    metric: str,
    k: int,
    k_scan: int,
    probes: int,
    target_scan: float,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
    trace_as: str = "xla",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cluster-pruned two-pass search over a quantized packed tier.

    The over-fetches stack: the pruned scan ranks the S gathered
    candidates by quantized score with bins planned for ``k_scan``
    (``quant.scan_k``'s confusion budget) at the cluster planner's
    ``target_scan``, then the usual exact second pass re-scores the
    over-fetched winners from the full-precision tail — so the combined
    guarantee is collision(K', S) x miss, both terms budgeted.  Candidate
    ids are user-space, so the rescore gather is identical to the
    unclustered one.
    """
    m_obj = get_metric(metric)
    TRACE_COUNTS.inc(trace_as)
    q = m_obj.prepare_queries(queries)
    idc, valid = prune_candidates(
        q, centroids, centroid_bias, cluster_rows, spill_rows, probes
    )
    qp = pad_queries_to(q, database.shape[1])
    rows = database[idc]
    scores = score_gathered(
        qp, rows.astype(jnp.float32), row_bias, idc, valid, scale
    )
    if rescore_db is not None:
        vals, pos = scan_candidates(
            scores, k_scan, recall_target=target_scan,
            aggregate_to_topk=False,
        )
        idxs = jnp.take_along_axis(idc, pos, axis=-1)
        vals, idxs = rescore_candidates(
            q, vals, idxs, rescore_db, rescore_bias, k, k_scan, use_bitonic
        )
    else:
        vals, pos = scan_candidates(
            scores, k, recall_target=target_scan,
            aggregate_to_topk=aggregate_to_topk, use_bitonic=use_bitonic,
        )
        idxs = jnp.take_along_axis(idc, pos, axis=-1)
    return finalize_values(vals, m_obj.negate_output), idxs


# --- Pallas backend ---------------------------------------------------------


def prepare_pallas_inputs(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    k: int,
    recall_target: float,
    *,
    block_m: int,
    max_block_n: int = 1024,
    row_bias: Optional[jnp.ndarray] = None,
    reduction_input_size_override: int = -1,
):
    """Pad operands to the kernel tiling contract and build the fused bias row.

    The bias row fuses (Appendix A.5) the non-power-of-2 tail mask, the
    metric's additive per-row bias (e.g. -||x||^2/2 for L2), and any
    tombstone mask into a single COP.
    """
    m, d = queries.shape
    n = database.shape[0]
    plan = plan_bins(
        n, k, recall_target,
        reduction_input_size_override=reduction_input_size_override,
    )
    bin_size = plan.bin_size
    block_n = bin_size * max(1, max_block_n // bin_size)
    n_pad = round_up(max(n, block_n), block_n)
    m_pad = round_up(max(m, block_m), block_m)
    d_pad = round_up(d, 128)

    q = jnp.pad(queries, ((0, m_pad - m), (0, d_pad - d)))
    db = jnp.pad(database, ((0, n_pad - n), (0, d_pad - d)))
    bias = jnp.full((n_pad,), MASK_VALUE, jnp.float32)
    body = (
        jnp.zeros((n,), jnp.float32)
        if row_bias is None
        else jnp.maximum(row_bias.astype(jnp.float32), MASK_VALUE)
    )
    bias = bias.at[:n].set(body)
    return q, db, bias[None, :], plan, bin_size, block_n, (m, n)


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "recall_target", "block_m", "max_block_n", "interpret",
        "aggregate_to_topk", "use_bitonic", "reduction_input_size_override",
    ),
)
def _pallas_search_jit(
    queries,
    database,
    row_bias,
    *,
    metric,
    k,
    recall_target,
    block_m,
    max_block_n,
    interpret,
    aggregate_to_topk,
    use_bitonic,
    reduction_input_size_override,
):
    m_obj = get_metric(metric)
    TRACE_COUNTS.inc("pallas")
    q = m_obj.prepare_queries(queries)
    q, db, bias, plan, bin_size, block_n, (m, n) = prepare_pallas_inputs(
        q, database, k, recall_target,
        block_m=block_m, max_block_n=max_block_n, row_bias=row_bias,
        reduction_input_size_override=reduction_input_size_override,
    )
    vals, idxs = partial_reduce_pallas(
        q, db, bias, bin_size=bin_size,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    # Masked winners (padded tail) pair -inf with the sentinel index -1:
    # clamping them into [0, n) would let them alias row n-1 and surface
    # as phantom duplicates after merge_topk ties at -inf.
    vals, idxs = vals[:m], sentinelize_masked(vals[:m], idxs[:m], n)
    if aggregate_to_topk:
        vals, idxs = merge_topk(vals, idxs, k, use_bitonic=use_bitonic)
    return finalize_values(vals, m_obj.negate_output), idxs


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "n", "bin_size", "block_m", "block_n", "interpret",
        "aggregate_to_topk", "use_bitonic", "fused_select",
    ),
)
def pallas_search_packed(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: jnp.ndarray,
    *,
    metric: str,
    k: int,
    n: int,
    bin_size: int,
    block_m: int,
    block_n: int,
    interpret: bool,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
    fused_select: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused-kernel search over pre-packed operands (steady-state path).

    ``database`` (n_pad, d_pad) and ``row_bias`` (1, n_pad) must already
    satisfy the kernel tiling contract — ``repro.search.packed`` builds
    them once at index build/mutation time.  Only the (M, D) query block
    is prepared and padded here, so the per-dispatch memory traffic
    matches the paper's model (I_MEM ~ O(min(M, N)), Eq. 10).  ``n`` is
    the logical row space (packed padding excluded).

    ``fused_select=True`` runs the single-pass scan→select kernel (the
    top-k merge happens in VMEM during the scan; Eq. 20 traffic — only
    the (M, k) result touches HBM).  Requires ``aggregate_to_topk``;
    ``False`` keeps the two-pass bin-winner path, the parity oracle.
    Masked result entries pair -inf values with the sentinel index -1 on
    both paths.
    """
    m_obj = get_metric(metric)
    TRACE_COUNTS.inc("pallas")
    q = m_obj.prepare_queries(queries)
    if fused_select and aggregate_to_topk:
        vals, idxs = partial_reduce_fused(
            q, database, row_bias,
            k_scan=k, bin_size=bin_size, block_m=block_m, block_n=block_n,
            interpret=interpret,
        )
        return finalize_values(vals, m_obj.negate_output), idxs
    vals, idxs = partial_reduce_packed(
        q, database, row_bias,
        bin_size=bin_size, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )
    # Masked winners keep -inf paired with sentinel index -1 through the
    # merge (clamping to n-1 here minted phantom duplicate neighbours).
    idxs = sentinelize_masked(vals, idxs, n)
    if aggregate_to_topk:
        vals, idxs = merge_topk(vals, idxs, k, use_bitonic=use_bitonic)
    return finalize_values(vals, m_obj.negate_output), idxs


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "k", "k_scan", "n", "bin_size", "block_m", "block_n",
        "interpret", "aggregate_to_topk", "use_bitonic", "fused_select",
        "int4_packed",
    ),
)
def pallas_search_packed_quant(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: jnp.ndarray,
    scale: Optional[jnp.ndarray],
    rescore_db: Optional[jnp.ndarray],
    rescore_bias: Optional[jnp.ndarray],
    *,
    metric: str,
    k: int,
    k_scan: int,
    n: int,
    bin_size: int,
    block_m: int,
    block_n: int,
    interpret: bool,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
    fused_select: bool = False,
    int4_packed: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused-kernel two-pass search over a quantized packed tier.

    Same packed-operand contract as ``pallas_search_packed`` — the kernel
    streams the (n_pad, d_pad) *stored* rows (bf16/int8/int4 HBM bytes,
    dequantized tile-locally in VMEM; ``scale`` is the per-row scale in
    the bias row's (1, n_pad) layout, and ``int4_packed`` marks a
    two-nibbles-per-byte database of stored width d_pad/2).  The
    over-fetched bin winners (the packed layout's bins are planned for
    ``quant.scan_k``) are then exactly re-scored against the
    full-precision gather tail ``rescore_db``/``rescore_bias`` — O(M·L·D)
    second-pass work, inside Eq. 10's O(min(M, N)) budget.

    ``fused_select=True`` replaces the dispatch-level scan→cut with the
    single-pass kernel: the top-``k_scan`` is selected in VMEM, so
    the rescore consumes the kernel output directly and the (M, L)
    bin-winner tile never exists in HBM.
    """
    m_obj = get_metric(metric)
    TRACE_COUNTS.inc("pallas")
    q = m_obj.prepare_queries(queries)
    if fused_select and (rescore_db is not None or aggregate_to_topk):
        vals, idxs = partial_reduce_fused(
            q, database, row_bias, scale,
            k_scan=k_scan if rescore_db is not None else k,
            bin_size=bin_size, block_m=block_m, block_n=block_n,
            interpret=interpret, int4_packed=int4_packed,
        )
        if rescore_db is not None:
            vals, idxs = rescore_candidates(
                q, vals, idxs, rescore_db, rescore_bias, k, k_scan,
                use_bitonic,
            )
        return finalize_values(vals, m_obj.negate_output), idxs
    vals, idxs = partial_reduce_packed(
        q, database, row_bias, scale,
        bin_size=bin_size, block_m=block_m, block_n=block_n,
        interpret=interpret, int4_packed=int4_packed,
    )
    # Masked winners keep -inf paired with sentinel index -1 through the
    # merge (clamping to n-1 here minted phantom duplicate neighbours).
    idxs = sentinelize_masked(vals, idxs, n)
    if rescore_db is not None:
        vals, idxs = rescore_candidates(
            q, vals, idxs, rescore_db, rescore_bias, k, k_scan, use_bitonic
        )
    elif aggregate_to_topk:
        vals, idxs = merge_topk(vals, idxs, k, use_bitonic=use_bitonic)
    return finalize_values(vals, m_obj.negate_output), idxs


def pallas_search(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    row_bias: Optional[jnp.ndarray] = None,
    *,
    metric: str = "mips",
    k: int = 10,
    recall_target: float = 0.95,
    block_m: Optional[int] = None,
    max_block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
    aggregate_to_topk: bool = True,
    use_bitonic: bool = False,
    reduction_input_size_override: int = -1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-shot fused-kernel search (paper Alg. 2); packs inside jit.

    Same operand contract as ``dense_search`` (metric-prepared database,
    additive ``row_bias``); all three built-in metrics work here — cosine is
    plain MIPS after preparation, closing the old cosine-only-on-XLA gap.
    Tile sizes left ``None`` come from the kernel planner
    (``repro.search.plan``), sized for this workload and device.

    Every call re-pads the (N, D) database inside the jitted program —
    fine for one-shot functional use and the legacy ``kernels.ops`` shims,
    wrong for a steady-state serving loop.  ``Index`` uses
    ``pallas_search_packed`` over a ``repro.search.packed.PackedState``
    instead.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_m is None or max_block_n is None:
        from repro.search import plan as planlib

        p = planlib.plan_search(
            n=database.shape[0], d=queries.shape[1], k=k,
            m=queries.shape[0], metric=metric, recall_target=recall_target,
            # the operand dtype decides the sublane contract the tiles
            # must honour (e.g. bf16 needs block_m % 16 == 0)
            dtype=str(queries.dtype),
            backend="pallas",
            reduction_input_size_override=reduction_input_size_override,
        )
        block_m = block_m or p.block_m
        max_block_n = max_block_n or p.block_n
    return _pallas_search_jit(
        queries, database, row_bias,
        metric=metric, k=k, recall_target=recall_target,
        block_m=block_m, max_block_n=max_block_n, interpret=interpret,
        aggregate_to_topk=aggregate_to_topk, use_bitonic=use_bitonic,
        reduction_input_size_override=reduction_input_size_override,
    )


# --- Sharded backend (paper §7) ---------------------------------------------


def normalize_db_axes(db_axis) -> Tuple[str, ...]:
    """Canonicalize a database-axis spec (``"model"`` or a tuple of mesh
    axis names) into a tuple; the tuple form is a 2-D/N-D database split
    whose shards linearize row-major over the named axes."""
    return (db_axis,) if isinstance(db_axis, str) else tuple(db_axis)


def db_shard_count(mesh: Mesh, db_axis) -> int:
    """Number of database shards: the product of the mesh extents of every
    axis the database rows are split over."""
    count = 1
    for a in normalize_db_axes(db_axis):
        count *= mesh.shape[a]
    return count


def _map_query_blocks(fn, q, rows: int):
    """``fn`` over ``q`` in ``rows``-row blocks, as one ``lax.map``.

    Queries are independent, so the result equals ``fn(q)``; the padded
    tail rows are computed and dropped.
    """
    m = q.shape[0]
    if m <= rows:
        return fn(q)
    blocks = -(-m // rows)
    padded = jnp.pad(q, ((0, blocks * rows - m), (0, 0)))
    outs = jax.lax.map(fn, padded.reshape(blocks, rows, q.shape[1]))
    return tuple(o.reshape(blocks * rows, *o.shape[2:])[:m] for o in outs)


class ShardKernel(NamedTuple):
    """Statics of the fused kernel each shard runs over its block of the
    sharded kernel layout (``repro.search.packed``): the shard's logical
    rows and the layout's tiles.  The kernel always runs its fused
    scan→select (``SearchSpec.fused_select`` is not consulted here)."""

    n_local: int
    block_m: int
    block_n: int
    interpret: bool
    int4_packed: bool = False


def make_sharded_search_fn(
    mesh: Mesh,
    *,
    metric: str = "mips",
    k: int = 10,
    recall_target: float = 0.95,
    db_axis="model",
    batch_axis: Optional[str] = None,
    use_bitonic: bool = False,
    k_scan: Optional[int] = None,
    cluster_probes: Optional[int] = None,
    cluster_target_scan: Optional[float] = None,
    kernel: Optional[ShardKernel] = None,
    bin_size: Optional[int] = None,
):
    """Build (queries, database, row_bias) -> (values, indices) over a mesh.

    database sharded P(db_axis, None); queries replicated over db_axis and
    optionally sharded over ``batch_axis``; ``row_bias`` sharded P(db_axis)
    (or P(None, db_axis), a (1, n) row).  Each shard PartialReduces its
    rows with recall accounted against the *global* N, its winners are
    all-gathered, and ExactRescoring runs replicated.

    With ``kernel`` the operands are the sharded kernel layout (a block of
    ``block_n``-aligned rows a shard, its tail masked) and each shard runs
    the fused Pallas scan→select over its block: the top ``k`` (f32) or
    ``k_scan`` (quantized tiers, then rescored on the shard) of its bins.
    Without it each shard runs the XLA scan over its (n/shards, d) rows,
    in query sub-blocks that bound its (queries, n/shards) score tile,
    and cuts its bin winners to the top ``k``.  Either way only k winners
    a shard are gathered.  ``bin_size`` is the widest bin a shard lays
    (the packed layout's, ``repro.search.plan.shard_bins``), so both scans
    lay the same bins; ``None`` (no kernel) lays the recall plan's.

    ``db_axis`` may be a single mesh axis name or a *tuple* of names: the
    tuple form splits the database rows over the product of those axes
    (a pod-shaped 2-D mesh folds into one logical row partition), with
    shard ids — and hence the global-id offset arithmetic — linearized
    row-major over the named axes, matching both ``P((a, b), None)``
    placement and the tiled all-gather's concatenation order.  Combining
    a tuple ``db_axis`` with ``batch_axis`` gives full 2-D+ (query x
    database) sharding: per-device work is O(M/batch_shards x
    N/db_shards) and only the O(k_scan) per-shard winners cross the ICI
    (paper §7's traffic contract, priced by ``repro.search.plan`` as the
    ici term in ``Index.explain()``).

    Quantized storage tiers pass the extra sharded operands ``scale``
    (int8 per-row scale, P(db_axis)) and ``rescore_db``/``rescore_bias``
    (the full-precision rescore tail, P(db_axis, None)/P(db_axis)): each
    shard then re-scores its own over-fetched bin winners exactly —
    candidate indices are shard-local, so the gather never crosses shards
    — and the all-gather carries *exact* scores into the final rescoring.
    ``k_scan`` is the over-fetched scan k the bins are planned for
    (default: ``k``).

    Cluster pruning (``cluster_probes``/``cluster_target_scan`` set, plus
    the four side-table operands): the tables are *replicated* — every
    shard ranks the same centroids and derives the same global candidate
    ids — and each shard scores only the candidates its row range owns
    (out-of-range slots mask like empty ones), so the union of shard
    scans covers the candidate set exactly once.  Candidate ids are
    already global user ids, so the offset translation of the dense path
    is skipped; per-shard bins are laid over the S candidate slots at the
    cluster planner's ``target_scan``.
    """
    m_obj = get_metric(metric)
    scan_k = k if k_scan is None else k_scan
    db_axes = normalize_db_axes(db_axis)
    if batch_axis is not None and batch_axis in db_axes:
        raise ValueError(
            f"batch_axis {batch_axis!r} cannot also shard the database "
            f"(db_axis={db_axes!r})"
        )
    n_shards = db_shard_count(mesh, db_axes)

    def searcher(queries, database, row_bias=None, scale=None,
                 rescore_db=None, rescore_bias=None, centroids=None,
                 centroid_bias=None, cluster_rows=None, spill_rows=None):
        global_n = database.shape[0]
        if global_n % n_shards:
            raise ValueError(
                f"database rows {global_n} not divisible by {n_shards} shards"
            )
        TRACE_COUNTS.inc("sharded")
        q = m_obj.prepare_queries(queries)
        bias = (
            row_bias
            if row_bias is not None
            else jnp.zeros((global_n,), jnp.float32)
        )
        qspec = P(batch_axis, None) if batch_axis else P(None, None)

        # Per-row operands of the kernel layout are (1, n) rows.
        row_spec = P(None, db_axes) if bias.ndim == 2 else P(db_axes)
        args = [q, database, bias]
        in_specs = [qspec, P(db_axes, None), row_spec]
        with_scale = scale is not None
        with_rescore = rescore_db is not None
        with_cluster = centroids is not None
        if with_cluster and (
            cluster_probes is None or cluster_target_scan is None
        ):
            raise ValueError(
                "cluster operands passed but make_sharded_search_fn was "
                "built without cluster_probes/cluster_target_scan"
            )
        if with_scale:
            args.append(scale)
            in_specs.append(row_spec)
        if with_rescore:
            args.extend([rescore_db, rescore_bias])
            in_specs.extend([P(db_axes, None), P(db_axes)])
        if with_cluster:
            # Side tables replicated: centroid ranking must be identical
            # on every shard for the ownership partition to cover the
            # candidate set exactly once.
            args.extend([centroids, centroid_bias, cluster_rows, spill_rows])
            in_specs.extend([P(None, None), P(None), P(None, None), P(None)])

        def local_fn(q, db, b, *rest):
            # Linearized shard id over the (possibly multi-axis) database
            # split — row-major over db_axes, matching tiled all-gather.
            axis_idx = jax.lax.axis_index(db_axes)
            n_local = kernel.n_local if kernel else db.shape[0]
            offset = axis_idx.astype(jnp.int32) * n_local
            rest = list(rest)
            sc = rest.pop(0) if with_scale else None
            rs_db, rs_bias = (
                (rest.pop(0), rest.pop(0)) if with_rescore else (None, None)
            )
            if with_cluster:
                cents, cbias, crows, srows = rest
                gidc, valid = prune_candidates(
                    q, cents, cbias, crows, srows, cluster_probes
                )
                # Global candidate ids -> this shard's row range; slots
                # another shard owns mask exactly like empty ones.
                local = gidc - offset
                owned = valid & (local >= 0) & (local < n_local)
                lidc = jnp.clip(local, 0, n_local - 1)
                scores = score_gathered(
                    pad_queries_to(q, db.shape[1]),
                    db[lidc].astype(jnp.float32), b, lidc, owned, sc,
                )
                s_slots = scores.shape[-1]
                plan = plan_bins(
                    s_slots, min(scan_k, s_slots), cluster_target_scan
                )
                vals, pos = partial_reduce_with_plan(scores, plan, mode="max")
                idxs = jnp.take_along_axis(gidc, pos, axis=-1)
                if with_rescore:
                    k_cut = min(scan_k, vals.shape[-1])
                    if k_cut < vals.shape[-1]:
                        vals, sel = jax.lax.top_k(vals, k_cut)
                        pos = jnp.take_along_axis(pos, sel, axis=-1)
                        idxs = jnp.take_along_axis(idxs, sel, axis=-1)
                    lsel = jnp.take_along_axis(lidc, pos, axis=-1)
                    exact = (
                        jnp.einsum("md,mld->ml", q, rs_db[lsel],
                                   precision=EXACT)
                        + rs_bias[lsel]
                    )
                    vals = jnp.where(
                        vals > MASK_VALUE * 0.5, exact, MASK_VALUE
                    )
                # idxs are global user ids already — no offset to add.
            elif kernel is not None:
                # Shard-local ids in [0, n_local); masked winners keep -1.
                vals, idxs = partial_reduce_fused(
                    q, db, b, sc, k_scan=scan_k, bin_size=bin_size,
                    block_m=kernel.block_m, block_n=kernel.block_n,
                    interpret=kernel.interpret,
                    int4_packed=kernel.int4_packed,
                )
                if with_rescore:
                    vals, idxs = rescore_candidates(
                        q, vals, idxs, rs_db, rs_bias, k, scan_k,
                        use_bitonic,
                    )
                idxs = jnp.where(idxs >= 0, idxs + offset, -1)
            else:
                plan = shard_bin_plan(
                    n_local, scan_k, recall_target, bin_size or n_local,
                    global_n,
                )

                def dense_scan(qs):
                    scores = score_rows(qs, db, b, sc)
                    vals, idxs = partial_reduce_with_plan(
                        scores, plan, mode="max"
                    )
                    if with_rescore:
                        # Cut the shard's bin winners to its k_scan best by
                        # quantized score, then exact-rescore only those.
                        k_cut = min(scan_k, vals.shape[-1])
                        if k_cut < vals.shape[-1]:
                            vals, sel = jax.lax.top_k(vals, k_cut)
                            idxs = jnp.take_along_axis(idxs, sel, axis=-1)
                        rows = rs_db[idxs]
                        exact = (
                            jnp.einsum("md,mld->ml", qs, rows,
                                       precision=EXACT)
                            + rs_bias[idxs]
                        )
                        vals = jnp.where(
                            vals > MASK_VALUE * 0.5, exact, MASK_VALUE
                        )
                    if k < vals.shape[-1]:
                        # The shard's k best, as the fused select leaves:
                        # the merge's top-k of the union is unchanged.
                        vals, sel = jax.lax.top_k(vals, k)
                        idxs = jnp.take_along_axis(idxs, sel, axis=-1)
                    return vals, idxs + offset

                # The shard's dense scan materializes a (queries, n_local)
                # score tile: bound it exactly as the single-device xla
                # path bounds its (query_block, N) tile.
                vals, idxs = _map_query_blocks(
                    dense_scan, q, _plan_query_block(n_local, "xla")
                )
            # The only cross-device traffic of the whole search: the
            # (value, global id) winners of each shard (k on the dense
            # scans), merged replicated.
            vals = jax.lax.all_gather(vals, db_axes, axis=-1, tiled=True)
            idxs = jax.lax.all_gather(idxs, db_axes, axis=-1, tiled=True)
            top_v, top_i = merge_topk(vals, idxs, k, use_bitonic=use_bitonic)
            return finalize_values(top_v, m_obj.negate_output), top_i

        # Replication checking is off: the all-gather over the db axes
        # replicates the merged winners in a way the checker cannot infer.
        fn = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(batch_axis, None), P(batch_axis, None)),
            check_vma=False,
        )
        return fn(*args)

    return searcher

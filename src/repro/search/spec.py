"""SearchSpec: the frozen, hashable description of a search problem.

One spec owns everything that was previously scattered across keyword
arguments of five entry points: the metric, k, the recall target, the
backend choice, the compute dtype, and the kernel block sizes.  Because the
spec is frozen and hashable it doubles as (part of) the compile-cache key —
two searches with the same spec and the same operand shapes share one traced
program.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.search import quant

__all__ = ["BACKENDS", "SearchSpec"]

BACKENDS = ("auto", "xla", "pallas", "sharded")


@dataclasses.dataclass(frozen=True)
class SearchSpec:
    """Frozen description of an approximate-KNN search problem.

    Attributes:
      metric: registered metric name ("mips", "l2", "cosine", ...).
      k: number of neighbours returned per query.
      recall_target: analytic E[recall] target used to plan bins (Eq. 14).
      backend: "auto" (sharded if a mesh is attached, else pallas on TPU,
        else xla), or an explicit "xla" | "pallas" | "sharded".
      dtype: optional compute dtype name (e.g. "bfloat16") the operands are
        cast to before the distance matmul; None inherits the input dtype.
      storage: database storage tier — "f32" (exact, the default),
        "bf16", "int8" or "int4" (``repro.search.quant``).  Quantized
        tiers store the metric-prepared database at 2, 1 or 0.5
        bytes/element (per-row scale for int8/int4; int4 packs two codes
        per byte in the Pallas layout), scan it over all N rows, and
        exactly rescore an over-fetched candidate set against a
        full-precision tail, so the Eq. 13–14 recall guarantee holds in
        expectation while database HBM traffic drops 2–8x (Eq. 10/20).
        ``"f32"`` is bit-identical to the pre-quantization path.
      cluster: cluster-pruned scan front-end (``repro.search.cluster``).
        ``"auto"`` (the default) lets the planner decide: above the cost
        crossover the index builds a k-means coarse quantizer and each
        query scans only its top-rho clusters (plus the spill block);
        below the crossover nothing is built and the search is
        bit-identical to ``"off"``.  ``"off"`` never evaluates pruning.
        There are no other values — every cluster parameter (C, rho,
        capacities) is derived by the planner, never supplied by the user.
      rescore: run the exact second pass on quantized tiers.  ``None``
        (default) resolves to True whenever ``storage != "f32"`` and
        ``aggregate_to_topk`` holds; False skips the f32 rescore tail
        (lower footprint, approximate values, no over-fetch).  True is
        invalid for f32 storage (nothing to rescore) and with
        ``aggregate_to_topk=False`` (the raw bin winners are the output).
      block_m / max_block_n: Pallas tile sizes (queries resident per grid
        step / upper bound on the database tile, rounded to the bin size).
        ``None`` (the default) defers the choice to the kernel planner
        (``repro.search.plan``): ``Index.build`` resolves them analytically
        from the workload and device profile.  Explicit values pin the
        tile and are never overridden.
      query_block: `.search` auto-tiles query batches larger than this so
        the (query_block, N) score tile bounds VMEM/host memory.  ``None``
        defers to the planner, same contract as the tile sizes.
      stream: execute multi-block query batches as ONE compiled streaming
        program (``lax.map`` over (num_blocks, query_block, D)) instead of
        a Python loop of per-block dispatches.  False keeps the per-block
        loop — bit-identical results, one dispatch per block — which is
        the benchmark baseline and parity oracle, not a production path.
      aggregate_to_topk: run ExactRescoring (True) or return the raw L bin
        winners (False).
      use_bitonic: rescore with the paper-faithful bitonic network instead
        of ``lax.top_k``.  Off by default: compiling the bitonic network
        inside jit is pathologically slow on CPU XLA (minutes at L=256),
        and ``lax.top_k`` over the L candidates is exact either way.
      fused_select: run the Pallas backend's single-pass scan→select
        kernel (the top-k is selected in VMEM during the scan — Eq. 20
        traffic: database bytes + O(k), no (M, N/bin_size) score-tile
        round trip).  ``None`` (default) resolves to True on the pallas
        backend whenever selection happens (``aggregate_to_topk`` or an
        enabled rescore); False pins the two-pass scan→merge path, the
        bit-identical parity oracle.  Ignored off the pallas backend —
        where the sharded backend's shards run the kernel, they always
        run its fused select — and by the cluster-pruned front-end (its
        gathered scan has no streaming j-loop to carry state across).
      reduction_input_size_override: recall-accounting N for sharded inputs
        (paper §7); -1 means "use the operand's own N".
      serve_buckets: ascending micro-batch row counts the concurrent
        ``repro.search.serve.SearchServer`` pads coalesced batches to (each
        bucket is one pre-compiled program shape, so serving traffic never
        retraces).  ``None`` defers to the planner, which derives a
        power-of-two ladder up to ``query_block``
        (``repro.search.plan.plan_buckets``) — same contract as the tile
        fields.  Lists are coerced to tuples so the spec stays hashable.
      residency: where the packed database lives between searches —
        ``"hbm"`` (default: device-resident) or ``"host"`` (the cold
        tier: packed operands stay in host RAM and ``search`` streams
        fixed-shape row segments through device HBM, double-buffered one
        wave ahead, so N is bounded by host memory instead of one
        device's HBM).  Host residency runs on the xla backend only
        (``backend="pallas"``/``"sharded"`` are rejected) and disables
        cluster pruning — the pruned gather needs the whole database
        resident.
      segment_rows: rows per host-tier segment wave.  ``None`` defers to
        the planner, which sizes segments against the device HBM budget
        (``repro.search.plan.plan_segments``) — same contract as the
        tile fields.  Unused for ``residency="hbm"``.

    A freshly-constructed spec defers tiling to the planner; the spec held
    by a built ``Index`` is always fully resolved:

    >>> SearchSpec(metric="l2", k=4).resolved
    False
    >>> SearchSpec(k=4, block_m=256, max_block_n=1024,
    ...            query_block=4096).resolved
    True
    """

    metric: str = "mips"
    k: int = 10
    recall_target: float = 0.95
    backend: str = "auto"
    dtype: Optional[str] = None
    storage: str = "f32"
    cluster: str = "auto"
    rescore: Optional[bool] = None
    block_m: Optional[int] = None
    max_block_n: Optional[int] = None
    query_block: Optional[int] = None
    stream: bool = True
    aggregate_to_topk: bool = True
    use_bitonic: bool = False
    fused_select: Optional[bool] = None
    reduction_input_size_override: int = -1
    serve_buckets: Optional[Tuple[int, ...]] = None
    residency: str = "hbm"
    segment_rows: Optional[int] = None

    def __post_init__(self):
        if self.residency not in ("hbm", "host"):
            raise ValueError(
                f'residency must be "hbm" or "host", got {self.residency!r}'
            )
        if self.residency == "host" and self.backend in ("pallas", "sharded"):
            raise ValueError(
                f'residency="host" streams database segments through a '
                f"single device and requires the xla backend; got "
                f"backend={self.backend!r}"
            )
        if self.segment_rows is not None and self.segment_rows <= 0:
            raise ValueError(
                f"segment_rows must be positive, got {self.segment_rows}"
            )
        if self.residency == "host" and not self.aggregate_to_topk:
            raise ValueError(
                'residency="host" merges per-segment top-k carries and '
                "needs aggregate_to_topk=True: the raw bin winners of one "
                "segment wave are not comparable across waves"
            )
        if self.k <= 0:
            raise ValueError(f"k must be positive, got {self.k}")
        if not 0.0 < self.recall_target < 1.0:
            raise ValueError(
                f"recall_target must be in (0, 1), got {self.recall_target}"
            )
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}"
            )
        quant.storage_bytes(self.storage)  # validate the tier name
        if self.cluster not in ("auto", "off"):
            raise ValueError(
                f'cluster must be "auto" or "off", got {self.cluster!r} — '
                "cluster parameters are planner-derived, not user knobs"
            )
        if self.rescore and self.storage == "f32":
            raise ValueError(
                "rescore=True requires a quantized storage tier "
                '("bf16", "int8" or "int4"); storage="f32" is already '
                "exact"
            )
        if self.fused_select and not self.aggregate_to_topk:
            raise ValueError(
                "fused_select=True needs aggregate_to_topk=True: the "
                "fused kernel's VMEM select *is* the top-k selection, so "
                "there are no raw bin winners to return.  Use "
                "fused_select=False (or None) for the two-pass scan."
            )
        if self.rescore and not self.aggregate_to_topk:
            raise ValueError(
                "rescore=True needs aggregate_to_topk=True: with "
                "aggregate_to_topk=False the raw bin winners are the "
                "output, so there is no top-k to rescore into.  Use "
                "rescore=False for a raw quantized scan."
            )
        if self.storage != "f32":
            # Metric x storage compatibility, checked here when the metric
            # is already registered (Index.build re-checks eagerly so
            # late-registered metrics are covered too).
            from repro.search.metrics import _REGISTRY

            m = _REGISTRY.get(self.metric)
            if m is not None:
                quant.check_metric_storage(m, self.storage)
        for field in ("block_m", "max_block_n", "query_block"):
            v = getattr(self, field)
            if v is not None and v <= 0:
                raise ValueError(f"{field} must be positive, got {v}")
        if self.serve_buckets is not None:
            buckets = tuple(int(b) for b in self.serve_buckets)
            if not buckets or any(b <= 0 for b in buckets):
                raise ValueError(
                    f"serve_buckets must be positive, got {self.serve_buckets}"
                )
            if list(buckets) != sorted(set(buckets)):
                raise ValueError(
                    "serve_buckets must be strictly ascending, got "
                    f"{self.serve_buckets}"
                )
            object.__setattr__(self, "serve_buckets", buckets)
        # Metric existence is validated lazily by the registry (metrics.py)
        # so user-registered metrics can be referenced before import order
        # would otherwise allow.

    @property
    def rescore_enabled(self) -> bool:
        """Whether the two-pass quantized search runs its exact rescore.

        >>> SearchSpec(storage="int8").rescore_enabled
        True
        >>> SearchSpec(storage="f32").rescore_enabled
        False
        """
        if self.storage == "f32" or not self.aggregate_to_topk:
            return False
        return True if self.rescore is None else self.rescore

    @property
    def fused_select_enabled(self) -> bool:
        """Resolved ``fused_select`` (the pallas backend consults this).

        >>> SearchSpec().fused_select_enabled
        True
        >>> SearchSpec(aggregate_to_topk=False).fused_select_enabled
        False
        >>> SearchSpec(fused_select=False).fused_select_enabled
        False
        """
        if self.fused_select is not None:
            return self.fused_select
        # The fused kernel produces the selected top-k directly, so it
        # needs a selection stage to subsume; raw bin winners
        # (aggregate_to_topk=False) keep the two-pass scan.
        return self.aggregate_to_topk

    @property
    def resolved(self) -> bool:
        """True once every planner-deferred block field holds a value."""
        return not (
            self.block_m is None
            or self.max_block_n is None
            or self.query_block is None
        )

    def with_backend(self, backend: str) -> "SearchSpec":
        return dataclasses.replace(self, backend=backend)

    # -- snapshot (de)serialization ------------------------------------------

    def to_json_dict(self) -> dict:
        """JSON-safe field dict (``Index.save`` stamps it into snapshots).

        >>> SearchSpec.from_json_dict(SearchSpec(k=4).to_json_dict()).k
        4
        """
        d = dataclasses.asdict(self)
        if d["serve_buckets"] is not None:
            d["serve_buckets"] = list(d["serve_buckets"])
        return d

    @classmethod
    def from_json_dict(cls, d: dict) -> "SearchSpec":
        """Inverse of :meth:`to_json_dict`, with loud version-skew errors:
        a snapshot written by a newer code version may carry fields this
        version does not know."""
        d = dict(d)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"snapshot spec carries unknown fields {unknown} — written "
                "by a newer version? Rebuild the index or upgrade."
            )
        if d.get("serve_buckets") is not None:
            d["serve_buckets"] = tuple(d["serve_buckets"])
        return cls(**d)

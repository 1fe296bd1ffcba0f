"""Index: the index-free front door of the unified search API.

"Index" in the Faiss sense of the word only — true to the paper there is no
graph/IVF data structure to build or maintain.  ``Index.build`` does the
only precompute the algorithm needs (metric preparation + packing into the
backend's native layout, O(N) element-wise), held device-resident in a
``repro.search.packed.PackedState`` so updates are cheap:

  * ``add(rows)``    appends into spare capacity and metric-prepares ONLY
    the appended slice (amortized growth, no O(N) re-derivation),
  * ``delete(ids)``  tombstones rows by patching the packed bias row —
    no host sync, no O(N) work,
  * the bin plan and the padded kernel layout are owned by the packed
    state, rebuilt only on capacity/backend changes —
    no rebuild, the paper's "suitable for frequent updates" claim.

``search`` dispatches pre-packed operands to the xla / pallas / sharded
backend, so the steady-state dispatch never pads or prepares the (N, D)
database (the paper's I_MEM ~ O(min(M, N)) bound, Eq. 10).  Query batches
larger than ``spec.query_block`` run as ONE compiled streaming program
(``lax.map`` over equal-shaped blocks) instead of a Python loop of
dispatches; compiled callables are memoized per (shape, dtype, spec) in a
``CompileCache`` — repeat same-shape searches never retrace.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.binning import BinPlan, plan_bins, round_up
from repro.launch.mesh import auto_axes
from repro.search import backends, packed as packedlib, plan as planlib
from repro.search import cluster as clusterlib
from repro.search import faults as faultslib
from repro.search import hosttier as hosttierlib
from repro.search import telemetry as telemetrylib
from repro.search import quant
from repro.search.metrics import Metric, get_metric
from repro.search.spec import SearchSpec

__all__ = ["Index", "SNAPSHOT_FORMAT", "SNAPSHOT_VERSION", "SearchResult"]

# Snapshot stamping (Index.save / Index.restore).  The format string guards
# against loading some other repro.checkpoint artifact as an index; the
# version gates forward compatibility — restore refuses snapshots written
# by a NEWER version (older ones are handled field-by-field).
SNAPSHOT_FORMAT = "repro.search.index"
SNAPSHOT_VERSION = 1


class SearchResult(NamedTuple):
    """(values, indices), both (M, k); value conventions per the metric
    contract in ``repro.search.metrics``."""

    values: jnp.ndarray
    indices: jnp.ndarray


class Index:
    """Searchable database under one ``SearchSpec``.

    Build one with ``Index.build(db, metric=..., k=..., ...)``; never call
    the constructor directly.  All mutating methods (``add``, ``delete``)
    update in place and return ``self`` for chaining.
    """

    def __init__(
        self,
        spec: SearchSpec,
        db: jnp.ndarray,
        live: jnp.ndarray,
        size: int,
        num_live: Union[int, jnp.ndarray],
        *,
        capacity_block: int = 1024,
        mesh: Optional[Mesh] = None,
        db_axis: str = "model",
        batch_axis: Optional[str] = None,
        interpret: Optional[bool] = None,
        kernel_plan: Optional[planlib.Plan] = None,
    ):
        self.spec = spec
        self._db = db
        self._live = live
        self._size = size          # append high-water mark (<= capacity)
        self._num_live = num_live  # live rows; int, or a lazy device scalar
        self._capacity_block = capacity_block
        self._mesh = mesh
        self._db_axis = db_axis
        self._batch_axis = batch_axis
        self._interpret = interpret
        self._kernel_plan = kernel_plan
        self._packed: Optional[packedlib.PackedState] = None
        self._cache = backends.CompileCache()

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        database: jnp.ndarray,
        *,
        metric: str = "mips",
        k: int = 10,
        recall_target: float = 0.95,
        backend: str = "auto",
        spec: Optional[SearchSpec] = None,
        capacity: Optional[int] = None,
        capacity_block: int = 1024,
        interpret: Optional[bool] = None,
        plan: Union[str, planlib.Plan] = "model",
        device: Optional[str] = None,
        plan_cache: Optional[planlib.PlanCache] = None,
        hbm_budget_bytes: Optional[float] = None,
        **spec_kwargs,
    ) -> "Index":
        """Create an index over ``database`` rows (N, D).

        ``spec`` overrides the individual (metric, k, ...) arguments when
        given.  ``capacity`` pre-allocates room for ``add`` beyond N;
        ``interpret`` forces Pallas interpret mode (auto: on except on TPU).
        The packed search state (metric precompute, fused bias row, kernel
        layout) is materialized here, at build time — not on first search.

        ``plan`` selects how kernel parameters (tile sizes, query block)
        are chosen for spec fields left ``None``:

          * ``"model"`` (default): analytically, from the paper's
            performance model (``repro.search.plan.plan_search``).
          * ``"measure"``: the model's choice refined by a short on-device
            sweep (``repro.search.plan.tune_plan``), persisted in
            ``plan_cache`` (or the ``REPRO_PLAN_CACHE`` file).
          * a :class:`repro.search.plan.Plan` instance: used as-is.

        Explicit block fields in ``spec``/``spec_kwargs`` always pin the
        corresponding choice.  ``device`` names a hardware profile from
        ``repro.core.roofline.HARDWARE`` (default: auto-detect).

        ``residency="host"`` (a spec field, accepted here as a keyword)
        builds the cold tier: packed operands stay in host RAM and the
        planner sizes the segment waves against ``hbm_budget_bytes``
        (default: the device profile's HBM) — capacity is padded to whole
        segments so every wave shares one compiled program shape.

        >>> import jax.numpy as jnp
        >>> idx = Index.build(jnp.eye(32), metric="mips", k=2)
        >>> idx.spec.resolved and idx.kernel_plan.source == "model"
        True
        """
        if spec is None:
            spec = SearchSpec(
                metric=metric, k=k, recall_target=recall_target,
                backend=backend, **spec_kwargs,
            )
        # Validate eagerly: metric existence AND metric x storage-tier
        # compatibility (covers metrics registered after the spec was
        # built, which SearchSpec's own validation cannot see).
        quant.check_metric_storage(get_metric(spec.metric), spec.storage)
        database = jnp.asarray(database)
        if database.ndim != 2:
            raise ValueError(f"database must be (N, D), got {database.shape}")
        n = database.shape[0]
        cap = max(n, capacity or n)
        if cap > n:
            cap = round_up(cap, capacity_block)
            database = jnp.pad(database, ((0, cap - n), (0, 0)))

        # Resolve the kernel plan over the *capacity* row space — that is
        # what the packed layout (and its bin plan) covers.
        plan_backend = spec.backend
        if plan_backend == "auto":
            plan_backend = backends.default_backend(None)
        if isinstance(plan, planlib.Plan):
            plan_obj = plan
        elif plan in ("model", "measure"):
            plan_obj = planlib.plan_search(
                n=cap, d=database.shape[1], k=spec.k, metric=spec.metric,
                recall_target=spec.recall_target,
                # the planner sizes tiles for the dtype that actually runs:
                # the spec override, else the database's own
                dtype=spec.dtype or str(database.dtype),
                backend=plan_backend, device=device,
                reduction_input_size_override=
                    spec.reduction_input_size_override,
                block_m=spec.block_m, max_block_n=spec.max_block_n,
                query_block=spec.query_block,
                storage=spec.storage, rescore=spec.rescore_enabled,
                cluster=spec.cluster,
                residency=spec.residency, segment_rows=spec.segment_rows,
                hbm_budget_bytes=hbm_budget_bytes,
            )
            if plan == "measure" and plan_obj.source != "user":
                plan_obj = planlib.tune_plan(
                    database, plan_obj, spec=spec, cache=plan_cache,
                    interpret=interpret,
                )
        else:
            raise ValueError(
                f"plan must be 'model', 'measure' or a Plan, got {plan!r}"
            )
        spec = plan_obj.to_spec(spec)

        if spec.residency == "host" and spec.segment_rows:
            # The wave program has one fixed shape; pad capacity (with
            # tombstoned rows) to a whole number of segment waves.
            seg_cap = round_up(cap, spec.segment_rows)
            if seg_cap > cap:
                database = jnp.pad(database, ((0, seg_cap - cap), (0, 0)))
                cap = seg_cap

        live = jnp.zeros((cap,), bool).at[:n].set(True)
        index = cls(
            spec, database, live, size=n, num_live=n,
            capacity_block=capacity_block, interpret=interpret,
            kernel_plan=plan_obj,
        )
        if spec.backend != "sharded":
            # backend="sharded" has no mesh yet; ``shard`` packs instead.
            index.pack()
        return index

    # -- introspection -------------------------------------------------------

    @property
    def metric(self) -> Metric:
        return get_metric(self.spec.metric)

    @property
    def capacity(self) -> int:
        return self._db.shape[0]

    @property
    def dim(self) -> int:
        return self._db.shape[1]

    @property
    def size(self) -> int:
        """Number of live (searchable) rows.

        ``delete`` keeps the live count as a lazy device scalar so the
        dispatch pipeline is never blocked; reading ``size`` (or ``len``)
        is what materializes it.
        """
        if not isinstance(self._num_live, int):
            self._num_live = int(self._num_live)
        return self._num_live

    @property
    def num_appended(self) -> int:
        """Rows ever appended (live + tombstoned) — the append high-water
        mark.  Row-aligned side tables (e.g. value tokens) must cover at
        least this many rows."""
        return self._size

    def __len__(self) -> int:
        return self.size

    @property
    def plan(self) -> BinPlan:
        """Bin plan (and analytic E[recall], Eq. 13) for the current shape.

        Quantized tiers plan for the over-fetched scan k
        (``repro.search.quant.scan_k``), so ``expected_recall`` is the
        conservative ``((L-1)/L)^(K'-1)`` bound the two-pass guarantee
        rests on.
        """
        if self._packed is not None:
            return self._packed.plan
        return plan_bins(
            self.capacity,
            packedlib.scan_k_for(self.spec, self.capacity),
            self.spec.recall_target,
            reduction_input_size_override=self.spec.reduction_input_size_override,
        )

    @property
    def expected_recall(self) -> float:
        cp = self._cluster_plan_in_effect()
        if cp is not None:
            k_scan = packedlib.scan_k_for(self.spec, cp.scan_rows)
            return cp.recall_decomposition(k_scan)["expected_recall"]
        return self.plan.expected_recall

    @property
    def expected_recall_live(self) -> float:
        """Live served-recall proxy: the analytic bin-collision term
        (Eq. 13, over-fetch margin of the quantized tiers already folded
        into ``scan_k``) times the *measured* served-query cluster-miss
        survival rate when the ``SearchServer`` sampler has data —
        falling back to the analytic miss term before any sample, and to
        plain ``expected_recall`` on unclustered indexes.  This is the
        one gauge that moves when real traffic drifts out of the
        distribution the cluster tables were certified on."""
        cp = self._cluster_plan_in_effect()
        if cp is None:
            return float(self.plan.expected_recall)
        k_scan = packedlib.scan_k_for(self.spec, cp.scan_rows)
        decomp = cp.recall_decomposition(k_scan)
        cs = self._packed.cluster if self._packed is not None else None
        rate = cs.served_miss_rate if cs is not None else None
        if rate is None:
            return float(decomp["expected_recall"])
        return float(decomp["collision_term"] * (1.0 - rate))

    @property
    def interpret(self) -> bool:
        """Whether Pallas kernels run in interpret mode: as passed at
        build, else exactly when the default backend is not a TPU."""
        if self._interpret is None:
            return jax.default_backend() != "tpu"
        return self._interpret

    def _cluster_plan_in_effect(self):
        """The ClusterPlan the live search path actually prunes with.

        Prefers the plan the packed side-tables were built with (the one
        whose probes/target_scan are baked into the compiled program);
        falls back to the kernel plan's derivation pre-pack.  None when
        pruning is off or rejected by the planner crossover.
        """
        if self._packed is not None:
            cs = self._packed.cluster
            return cs.plan if cs is not None else None
        kp = self._kernel_plan
        if kp is not None and kp.cluster is not None and kp.cluster.enabled:
            return kp.cluster
        return None

    def _replan(
        self,
        *,
        n: Optional[int] = None,
        m: Optional[int] = None,
        backend: Optional[str] = None,
        device: Optional[str] = None,
        pin_from: Optional[planlib.Plan] = None,
    ) -> planlib.Plan:
        """One re-planning entry point for growth/shard/explain.

        Always carries the spec's recall accounting
        (``reduction_input_size_override``) and the *actual* operand dtype
        (spec override or the database's own), so a derived plan can never
        diverge from the packed layout's bin math.  ``pin_from`` pins the
        tile triple of an existing plan (layout-preserving re-plans);
        otherwise the spec's own (possibly ``None``) fields apply.
        """
        spec = self.spec
        tiles = (
            dict(block_m=pin_from.block_m, max_block_n=pin_from.block_n,
                 query_block=pin_from.query_block)
            if pin_from is not None
            else dict(block_m=spec.block_m, max_block_n=spec.max_block_n,
                      query_block=spec.query_block)
        )
        return planlib.plan_search(
            n=self.capacity if n is None else n, d=self.dim, k=spec.k,
            m=m, metric=spec.metric, recall_target=spec.recall_target,
            dtype=spec.dtype or str(self._db.dtype),
            backend=backend or self._resolve_backend(),
            device=device or (pin_from.device if pin_from else None),
            reduction_input_size_override=spec.reduction_input_size_override,
            storage=spec.storage, rescore=spec.rescore_enabled,
            cluster=spec.cluster,
            db_shards=self._num_db_shards(),
            residency=spec.residency,
            segment_rows=spec.segment_rows,
            **tiles,
        )

    @property
    def kernel_plan(self) -> planlib.Plan:
        """The resolved kernel plan (``repro.search.plan.Plan``) — tile
        sizes, bin layout and the roofline prediction behind them."""
        if self._kernel_plan is None:
            self._kernel_plan = self._replan()
        return self._kernel_plan

    def explain(
        self,
        *,
        m: Optional[int] = None,
        measure: bool = False,
        validate_hlo: bool = False,
    ) -> dict:
        """The plan behind this index, with its predicted roofline position.

        Returns a dict with the resolved ``plan`` (tiles, bin layout,
        provenance), the ``predicted`` roofline placement (attainable
        FLOP/s, binding wall, per-batch wall time — Eq. 4–10), and the
        analytic ``expected_recall`` (Eq. 13).  ``m`` re-evaluates the
        prediction for a specific query-batch size (default: one
        ``query_block``).

        ``measure=True`` additionally times a synthetic batch on the live
        index and reports achieved FLOP/s and the fraction of the model's
        attainable roof actually reached.  ``validate_hlo=True`` (xla
        backend) lowers the search program and cross-checks the model's
        FLOP count against the compiled HLO (``repro.search.plan.hlo_check``).
        """
        plan = self.kernel_plan
        if m is not None and m != plan.m:
            plan = dataclasses.replace(
                self._replan(n=plan.n, m=m, backend=plan.backend,
                             pin_from=plan),
                source=plan.source,
            )
        report = {
            "plan": plan.summary(),
            "backend": self._resolve_backend(),
            "expected_recall": plan.expected_recall,
            "predicted": {
                "device": plan.device,
                "flops": plan.flops,
                "hbm_bytes": plan.hbm_bytes,
                "cops": plan.cops,
                "i_mem": plan.i_mem,
                "i_cop": plan.i_cop,
                "attainable_flops": plan.attainable_flops,
                "bottleneck": plan.bottleneck,
                "wall_s": plan.predicted_s,
                "qps": plan.predicted_qps,
            },
            # Traffic is priced from the dtype actually *stored*, not an
            # assumed 4 bytes/element: quantized tiers stream 2- or 1-byte
            # rows (Eq. 10/20) plus an O(M·L·D) exact rescore pass.
            "storage": {
                "tier": self.spec.storage,
                "db_bytes_per_element": quant.storage_bytes(
                    self.spec.storage
                ),
                "db_resident_bytes": self.capacity * self.dim
                * quant.storage_bytes(self.spec.storage),
                "rescore": self.spec.rescore_enabled,
                "k_scan": plan.k_scan or plan.k,
                # Eq. 20 traffic for one dispatch at this tier: on the
                # fused Pallas path this is db-bytes + O(M·k_scan) with no
                # score-tile round trip — what the bench smoke asserts.
                "predicted_hbm_bytes": plan.hbm_bytes,
                "fused_select": self.spec.fused_select_enabled,
            },
        }
        if self.spec.residency == "host":
            seg = self.spec.segment_rows or plan.segment_rows
            waves = self.capacity // seg if seg else 0
            sbytes = quant.storage_bytes(self.spec.storage)
            # The segment schedule a search will actually run: fixed-shape
            # waves streamed through device HBM, double-buffered one ahead.
            report["residency"] = {
                "tier": "host",
                "segment_rows": seg,
                "num_segments": waves,
                "segment_hbm_bytes": seg * self.dim * sbytes,
                "hbm_budget_bytes": plan.hbm_budget_bytes,
                "schedule": [
                    {"wave": i, "rows": [i * seg, (i + 1) * seg]}
                    for i in range(waves)
                ],
            }
        if self._mesh is not None:
            # The §7 distributed-traffic picture: per-shard scan sizing
            # plus the one collective — the O(k_scan)-per-shard (value,
            # global id) all-gather — priced against the ICI bandwidth.
            report["sharding"] = {
                "db_axes": list(self._db_axes()),
                "batch_axis": self._batch_axis,
                "db_shards": plan.db_shards,
                "per_shard_n": plan.n // max(plan.db_shards, 1),
                "ici_gather_bytes": plan.ici_bytes,
                "ici_s": plan.ici_s,
            }
        cp = self._cluster_plan_in_effect()
        report["cluster"] = {"mode": self.spec.cluster,
                             "enabled": cp is not None}
        if cp is None and plan.cluster is not None:
            # auto mode, rejected by the crossover: record why.
            report["cluster"]["predicted_speedup"] = \
                plan.cluster.predicted_speedup
        rejected_miss = (
            self._packed.cluster_rejected_miss
            if self._packed is not None else None
        )
        if cp is None and rejected_miss is not None:
            # auto mode, planner crossover passed but the build-time
            # empirical check measured a miss rate the decay model can't
            # budget (structureless data): record the measurement.
            report["cluster"].update({
                "rejected_by": "sampled_miss_check",
                "sampled_miss": rejected_miss,
                "miss_budget": plan.cluster.miss_budget
                if plan.cluster is not None else None,
            })
        if cp is not None:
            k_scan = packedlib.scan_k_for(self.spec, cp.scan_rows)
            decomp = cp.recall_decomposition(k_scan)
            report["cluster"].update({
                "num_clusters": cp.num_clusters,
                "probes": cp.probes,
                "rows_per_cluster": cp.rows_per_cluster,
                "spill_capacity": cp.spill_capacity,
                "scan_rows": cp.scan_rows,
                "scanned_fraction": cp.scanned_fraction,
                "predicted_speedup": cp.predicted_speedup,
                # E[recall] = P(no bin collision) * P(no cluster miss):
                # the product the planner certified against the target.
                "collision_term": decomp["collision_term"],
                "miss_term": decomp["miss_term"],
                "expected_recall": decomp["expected_recall"],
            })
            report["expected_recall"] = decomp["expected_recall"]
            cs = self._packed.cluster if self._packed is not None else None
            if cs is not None:
                # Served-query miss monitor (fed by SearchServer sampling):
                # the build-time check used db rows as query proxies; this
                # is the live estimate over *real* traffic, the only signal
                # for out-of-distribution query streams.
                report["cluster"]["served_miss"] = cs.served_miss_report()
        report["expected_recall_live"] = self.expected_recall_live
        if self._packed is not None:
            report["packed"] = {
                "n": self._packed.n,
                "db_shape": tuple(self._packed.db.shape),
                "bin_size": self._packed.bin_size,
                "block_n": self._packed.block_n,
            }
        m_eff = m or plan.m or plan.query_block
        if measure:
            queries = jax.random.normal(
                jax.random.PRNGKey(0), (m_eff, self.dim), self._db.dtype
            )
            wall = planlib.time_search(self, queries, repeats=3)
            # plan.flops is already the backend-correct count for m_eff
            # (padded kernel layout on pallas, raw operands on xla/sharded)
            achieved = plan.flops / wall
            report["measured"] = {
                "wall_s": wall,
                "qps": m_eff / wall,
                "achieved_flops": achieved,
                "roofline_fraction": achieved / plan.attainable_flops,
            }
        if validate_hlo:
            backend = self._resolve_backend()
            if backend != "xla":
                report["hlo"] = {"skipped": f"hlo check is xla-only "
                                 f"(resolved backend {backend!r})"}
            elif cp is not None:
                report["hlo"] = {"skipped": "hlo check models the dense "
                                 "scan; the clustered program gathers a "
                                 "pruned row set instead"}
            else:
                pk = self.pack()
                q = jax.ShapeDtypeStruct(
                    (min(m_eff, self.spec.query_block), self.dim),
                    self._db.dtype,
                )
                if self.spec.storage == "f32":
                    lowered = backends.dense_search.lower(
                        q, pk.db, pk.bias,
                        metric=self.spec.metric, k=self.spec.k,
                        recall_target=self.spec.recall_target,
                        reduction_input_size_override=
                            self.spec.reduction_input_size_override,
                        aggregate_to_topk=self.spec.aggregate_to_topk,
                        use_bitonic=self.spec.use_bitonic,
                    ).compile()
                else:
                    lowered = backends.dense_search_quant.lower(
                        q, pk.db, pk.bias, pk.scale,
                        pk.rescore_db, pk.rescore_bias,
                        metric=self.spec.metric, k=self.spec.k,
                        k_scan=packedlib.scan_k_for(
                            self.spec, pk.n, live=self.size
                        ),
                        recall_target=self.spec.recall_target,
                        reduction_input_size_override=
                            self.spec.reduction_input_size_override,
                        aggregate_to_topk=self.spec.aggregate_to_topk,
                        use_bitonic=self.spec.use_bitonic,
                    ).compile()
                block_plan = plan
                if q.shape[0] != plan.m:
                    block_plan = self._replan(
                        n=plan.n, m=q.shape[0], backend=plan.backend,
                        pin_from=plan,
                    )
                report["hlo"] = planlib.hlo_check(
                    block_plan, lowered.as_text()
                )
        return report

    def cache_info(self) -> dict:
        return self._cache.info()

    def telemetry(self) -> dict:
        """One JSON-serializable telemetry snapshot, index gauges included.

        Refreshes this index's gauges in the process-global registry —
        size/capacity and the recall pair (analytic ``expected_recall``
        and live ``expected_recall_live``), labeled by
        backend/storage/cluster — then returns
        ``repro.search.telemetry.export_json()`` (so the dispatch/trace/
        pack/serve counters and every histogram ride along).  For the
        Prometheus text form, call ``telemetry.export_prometheus()``
        after this.
        """
        reg = telemetrylib.registry()
        labels = {
            "backend": self._resolve_backend(),
            "storage": self.spec.storage,
            "cluster": (
                "on" if self._cluster_plan_in_effect() is not None else "off"
            ),
        }
        reg.set_gauge("repro_index_size", self.size, **labels)
        reg.set_gauge("repro_index_capacity", self.capacity, **labels)
        reg.set_gauge(
            "repro_index_expected_recall", self.expected_recall, **labels
        )
        reg.set_gauge(
            "repro_index_expected_recall_live", self.expected_recall_live,
            **labels,
        )
        return telemetrylib.export_json()

    def __repr__(self) -> str:
        mesh = f", mesh={dict(self._mesh.shape)}" if self._mesh else ""
        return (
            f"Index(metric={self.spec.metric!r}, k={self.spec.k}, "
            f"backend={self._resolve_backend()!r}, size={self.size}, "
            f"capacity={self.capacity}, dim={self.dim}{mesh})"
        )

    # -- packed state --------------------------------------------------------

    def _resolve_backend(self) -> str:
        b = self.spec.backend
        if self.spec.residency == "host":
            # The cold tier scans xla-layout segment waves; "auto" never
            # resolves to pallas/sharded here (spec validation already
            # rejects them explicitly).
            return "xla"
        if b == "auto":
            return backends.default_backend(self._mesh)
        if b == "sharded" and self._mesh is None:
            raise ValueError(
                "backend='sharded' requires a mesh — call "
                ".shard(mesh, db_axis=...) first"
            )
        return b

    def _db_axes(self) -> tuple:
        """Database mesh axes as a tuple (1-D: one name; 2-D: several)."""
        return backends.normalize_db_axes(self._db_axis)

    def _num_db_shards(self) -> int:
        """Database shard count — the product of the db-axis extents."""
        if self._mesh is None:
            return 1
        return backends.db_shard_count(self._mesh, self._db_axis)

    def _layout_args(self) -> dict:
        """``pack_state``'s ``shards`` and ``tiled`` for the resolved
        backend: the sharded backend's shard count, and the kernel layout
        where the fused kernel scans it — on the Pallas backend, and in
        each shard where ``backends.shard_scan`` picks it."""
        backend = self._resolve_backend()
        if backend != "sharded":
            return dict(shards=1, tiled=backend == "pallas")
        return dict(
            shards=self._num_db_shards(),
            tiled=backends.shard_scan(self._mesh, self._interpret)
            == "pallas",
        )

    def pack(self) -> packedlib.PackedState:
        """The device-resident packed operands for the resolved backend.

        Built at ``build``/``shard`` time and patched incrementally by
        ``add``/``delete``; a full repack happens only if the resolved
        backend changed under an ``auto`` spec or a non-row-wise metric
        invalidated the state.
        """
        backend = self._resolve_backend()
        if self._packed is None or self._packed.backend != backend:
            self._packed = packedlib.pack_state(
                self._db, self._live, self.metric, self.spec, backend,
                cluster_plan=self.kernel_plan.cluster,
                **self._layout_args(),
            )
            self._place_packed()
        return self._packed

    def _place_packed(self):
        """Pin packed operands to their residency: host RAM for the cold
        tier, the mesh layout when sharded (no-op for plain hbm)."""
        if self._packed is None:
            return
        if self.spec.residency == "host":
            # The packed arrays live on the host CPU between searches;
            # HostTierSearcher streams segment slices to the hot device.
            cpu = jax.local_devices(backend="cpu")[0]
            pk = self._packed
            pk.db = jax.device_put(pk.db, cpu)
            pk.bias = jax.device_put(pk.bias, cpu)
            if pk.scale is not None:
                pk.scale = jax.device_put(pk.scale, cpu)
            if pk.rescore_db is not None:
                pk.rescore_db = jax.device_put(pk.rescore_db, cpu)
                pk.rescore_bias = jax.device_put(pk.rescore_bias, cpu)
            return
        if self._mesh is None:
            return
        rows = NamedSharding(self._mesh, P(self._db_axis, None))
        per_row = NamedSharding(self._mesh, P(self._db_axis))
        pk = self._packed
        # The kernel layout's bias and scale are (1, n) rows.
        kernel_row = NamedSharding(
            self._mesh, P(None, self._db_axis) if pk.bias.ndim == 2
            else P(self._db_axis))
        pk.db = jax.device_put(pk.db, rows)
        pk.bias = jax.device_put(pk.bias, kernel_row)
        if pk.scale is not None:
            pk.scale = jax.device_put(pk.scale, kernel_row)
        if pk.rescore_db is not None:
            pk.rescore_db = jax.device_put(pk.rescore_db, rows)
            pk.rescore_bias = jax.device_put(pk.rescore_bias, per_row)
        if pk.cluster is not None:
            # Cluster side-tables are small (O(C*d + C*R)) and hold GLOBAL
            # row ids, so they are replicated — every shard probes the same
            # clusters and masks down to the rows it owns.
            repl2 = NamedSharding(self._mesh, P(None, None))
            repl1 = NamedSharding(self._mesh, P(None))
            cs = pk.cluster
            cs.centroids = jax.device_put(cs.centroids, repl2)
            cs.centroid_bias = jax.device_put(cs.centroid_bias, repl1)
            cs.cluster_rows = jax.device_put(cs.cluster_rows, repl2)
            cs.spill_rows = jax.device_put(cs.spill_rows, repl1)

    # -- search --------------------------------------------------------------

    def search(self, queries: jnp.ndarray) -> SearchResult:
        """Top-k neighbours of each query row: (M, D) -> SearchResult (M, k).

        Query batches larger than ``spec.query_block`` are processed in
        equal-shaped tiles to bound the score tile — by default as a
        single compiled streaming program (one device dispatch for the
        whole batch); ``spec.stream=False`` falls back to the per-block
        Python loop (bit-identical, one dispatch per block).  If fewer
        than k live rows exist (mass deletes), the tail of each result row
        is filled with sentinel values (float32 min) and arbitrary indices
        of masked rows.

        >>> import jax.numpy as jnp
        >>> index = Index.build(jnp.eye(16), metric="mips", k=3)
        >>> values, indices = index.search(jnp.eye(16)[:4])
        >>> indices.shape
        (4, 3)
        >>> int(indices[0, 0])  # e_0's best match is row 0
        0
        """
        # A profiler span (recorded only while a trace runs) over the host
        # work of a search: validation, cast, pads, cache lookup, dispatch.
        with TraceAnnotation("index.search"):
            queries = jnp.asarray(queries)
            if queries.ndim != 2:
                raise ValueError(
                    f"queries must be (M, D), got {queries.shape}"
                )
            if queries.shape[1] != self.dim:
                raise ValueError(
                    f"query dim {queries.shape[1]} != index dim {self.dim}"
                )
            if self.spec.dtype is not None:
                queries = queries.astype(jnp.dtype(self.spec.dtype))
            if queries.shape[0] <= self.spec.query_block:
                return SearchResult(*self._search_block(queries))
            if self.spec.stream and self.spec.residency != "host":
                # The host tier stages its segment waves from Python, so
                # multi-block batches run the (bit-identical) per-block
                # loop — each block still re-streams the database once.
                return self._search_stream(queries)
            return self._search_loop(queries)

    def _batch_axis_for(self, rows: int) -> Optional[str]:
        """Query batch axis, dropped when it does not divide the block."""
        batch_axis = self._batch_axis
        if batch_axis is not None and rows % self._mesh.shape[batch_axis]:
            return None  # replicate queries that do not divide
        return batch_axis

    def _search_block(self, q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        backend = self._resolve_backend()
        pk = self.pack()
        if self.spec.residency == "host":
            key = ("host", q.shape, str(q.dtype), self.capacity, self.spec)
            fn = self._cache.get(key, self._build_host_searcher)
            # Dispatch accounting (one per segment wave) lives inside the
            # wave driver.
            return fn(q, pk)
        key = ("block", backend, q.shape, str(q.dtype), self.capacity, self.spec)
        batch_axis = None
        if backend == "sharded":
            batch_axis = self._batch_axis_for(q.shape[0])
            key = key + (id(self._mesh), self._db_axis, batch_axis)
        fn = self._cache.get(
            key, lambda: self._build_block_fn(backend, pk, batch_axis)
        )
        backends.count_dispatch(backend, self.spec, pk, q.dtype)
        return fn(q, *pk.operands())

    def _build_host_searcher(self) -> hosttierlib.HostTierSearcher:
        pk = self._packed
        return hosttierlib.HostTierSearcher(
            self.spec,
            k_scan=packedlib.scan_k_for(self.spec, pk.n, live=self.size),
            segment_rows=self.spec.segment_rows
            or self.kernel_plan.segment_rows,
        )

    def _search_loop(self, queries: jnp.ndarray) -> SearchResult:
        """Per-block Python loop: one dispatch per tile.

        Kept as the parity oracle for the streaming executor and as the
        benchmark's dispatch-overhead baseline (``spec.stream=False``).
        """
        m = queries.shape[0]
        qb = self.spec.query_block
        m_pad = round_up(m, qb)
        padded = jnp.pad(queries, ((0, m_pad - m), (0, 0)))
        vals, idxs = [], []
        for start in range(0, m_pad, qb):
            v, i = self._search_block(padded[start : start + qb])
            vals.append(v)
            idxs.append(i)
        # stack, not concatenate: on multi-device meshes, concatenating
        # shard_map outputs (check_rep disabled) makes the partitioner
        # treat them as unreduced over the db axis and psum — silently
        # scaling results by the shard count.  stack keeps the replicas.
        k = vals[0].shape[-1]
        return SearchResult(
            jnp.stack(vals).reshape(m_pad, k)[:m],
            jnp.stack(idxs).reshape(m_pad, k)[:m],
        )

    def _search_stream(self, queries: jnp.ndarray) -> SearchResult:
        """Single-program streaming executor: the whole multi-block batch
        is ONE compiled dispatch (``lax.map`` over (B, query_block, D))."""
        backend = self._resolve_backend()
        pk = self.pack()
        m, d = queries.shape
        qb = self.spec.query_block
        num_blocks = -(-m // qb)
        m_pad = num_blocks * qb
        blocks = jnp.pad(queries, ((0, m_pad - m), (0, 0))).reshape(
            num_blocks, qb, d
        )
        key = (
            "stream", backend, blocks.shape, str(blocks.dtype),
            self.capacity, self.spec,
        )
        batch_axis = None
        if backend == "sharded":
            batch_axis = self._batch_axis_for(qb)
            key = key + (id(self._mesh), self._db_axis, batch_axis)
        fn = self._cache.get(
            key, lambda: self._build_stream_fn(backend, pk, batch_axis)
        )
        backends.count_dispatch(backend, self.spec, pk, blocks.dtype)
        vals, idxs = fn(blocks, *pk.operands())
        k = vals.shape[-1]
        return SearchResult(
            vals.reshape(m_pad, k)[:m], idxs.reshape(m_pad, k)[:m]
        )

    def _build_block_fn(self, backend, pk, batch_axis=None):
        """(q_block, *packed_operands) -> (values, indices) callable.

        Closes only over static config (spec fields, packed layout
        constants); the packed arrays — ``PackedState.operands()``: (db,
        bias) for the f32 tier, plus (scale, rescore_db, rescore_bias) for
        quantized tiers — are passed as operands so bias/row/scale patches
        never invalidate the compiled program.
        """
        spec = self.spec
        quantized = spec.storage != "f32"
        clustered = pk.cluster is not None
        if clustered:
            # Statics come from the plan the tables were BUILT with (the
            # live ``pk.cluster``), never ``kernel_plan.cluster``: after
            # growth the re-derived plan may disagree with the carried
            # tables until the lazy recluster fires.
            cplan = pk.cluster.plan
            probes, target_scan = cplan.probes, cplan.target_scan
        if backend in ("xla", "pallas") and clustered:
            trace_as = backend
            if not quantized:
                def fn(q, db, bias, ce, cb, cr, sr):
                    return backends.cluster_search(
                        q, db, bias, ce, cb, cr, sr,
                        metric=spec.metric, k=spec.k, probes=probes,
                        target_scan=target_scan,
                        aggregate_to_topk=spec.aggregate_to_topk,
                        use_bitonic=spec.use_bitonic, trace_as=trace_as,
                    )
                return fn
            k_scan = packedlib.scan_k_for(spec, pk.n, live=self.size)
            def fn(q, db, bias, scale, rs_db, rs_bias, ce, cb, cr, sr):
                return backends.cluster_search_quant(
                    q, db, bias, scale, rs_db, rs_bias, ce, cb, cr, sr,
                    metric=spec.metric, k=spec.k, k_scan=k_scan,
                    probes=probes, target_scan=target_scan,
                    aggregate_to_topk=spec.aggregate_to_topk,
                    use_bitonic=spec.use_bitonic, trace_as=trace_as,
                )
            return fn
        if backend == "xla":
            if not quantized:
                def fn(q, db, bias):
                    return backends.dense_search(
                        q, db, bias,
                        metric=spec.metric, k=spec.k,
                        recall_target=spec.recall_target,
                        reduction_input_size_override=
                            spec.reduction_input_size_override,
                        aggregate_to_topk=spec.aggregate_to_topk,
                        use_bitonic=spec.use_bitonic,
                    )
                return fn
            k_scan = packedlib.scan_k_for(spec, pk.n, live=self.size)
            def fn(q, db, bias, scale, rs_db, rs_bias):
                return backends.dense_search_quant(
                    q, db, bias, scale, rs_db, rs_bias,
                    metric=spec.metric, k=spec.k, k_scan=k_scan,
                    recall_target=spec.recall_target,
                    reduction_input_size_override=
                        spec.reduction_input_size_override,
                    aggregate_to_topk=spec.aggregate_to_topk,
                    use_bitonic=spec.use_bitonic,
                )
            return fn
        if backend == "pallas":
            interpret = self.interpret
            n, bin_size, block_n = pk.n, pk.bin_size, pk.block_n
            fused = spec.fused_select_enabled
            int4_packed = spec.storage == "int4"
            if not quantized:
                def fn(q, db, bias):
                    return backends.pallas_search_packed(
                        q, db, bias,
                        metric=spec.metric, k=spec.k, n=n,
                        bin_size=bin_size, block_m=spec.block_m,
                        block_n=block_n, interpret=interpret,
                        aggregate_to_topk=spec.aggregate_to_topk,
                        use_bitonic=spec.use_bitonic,
                        fused_select=fused,
                    )
                return fn
            k_scan = packedlib.scan_k_for(spec, pk.n, live=self.size)
            def fn(q, db, bias, scale, rs_db, rs_bias):
                return backends.pallas_search_packed_quant(
                    q, db, bias, scale, rs_db, rs_bias,
                    metric=spec.metric, k=spec.k, k_scan=k_scan, n=n,
                    bin_size=bin_size, block_m=spec.block_m,
                    block_n=block_n, interpret=interpret,
                    aggregate_to_topk=spec.aggregate_to_topk,
                    use_bitonic=spec.use_bitonic,
                    fused_select=fused, int4_packed=int4_packed,
                )
            return fn
        if backend == "sharded":
            mesh, db_axis = self._mesh, self._db_axis
            searcher = backends.make_sharded_search_fn(
                mesh, metric=spec.metric, k=spec.k,
                recall_target=spec.recall_target,
                db_axis=db_axis, batch_axis=batch_axis,
                use_bitonic=spec.use_bitonic,
                k_scan=packedlib.scan_k_for(spec, pk.n, live=self.size)
                if quantized else None,
                cluster_probes=probes if clustered else None,
                cluster_target_scan=target_scan if clustered else None,
                kernel=self._shard_kernel(pk), bin_size=pk.bin_size,
            )
            jitted = jax.jit(searcher)
            qsharding = NamedSharding(mesh, P(batch_axis, None))
            if clustered and not quantized:
                # The searcher signature puts the quant operands before the
                # cluster tables, so the f32 clustered operand tuple
                # (db, bias, cents, cbias, crows, srows) must skip them
                # explicitly or the tables would bind to scale/rescore.
                def fn(q, db, bias, ce, cb, cr, sr):
                    return jitted(jax.device_put(q, qsharding),
                                  db, bias, None, None, None,
                                  ce, cb, cr, sr)
                return fn
            def fn(q, *ops):
                return jitted(jax.device_put(q, qsharding), *ops)
            return fn
        raise ValueError(f"unknown backend {backend!r}")

    def _shard_kernel(self, pk) -> Optional[backends.ShardKernel]:
        """The fused kernel's statics for each shard of a kernel layout;
        None on the gather layout, whose shards run the XLA scan."""
        if not pk.block_n:
            return None
        return backends.ShardKernel(
            n_local=pk.n // pk.shards, block_m=self.spec.block_m,
            block_n=pk.block_n,
            interpret=self.interpret,
            int4_packed=self.spec.storage == "int4",
        )

    def _build_stream_fn(self, backend, pk, batch_axis=None):
        """(blocks (B, qb, D), db, bias) -> ((B, qb, k), (B, qb, k)).

        ``lax.map`` streams the blocks through one compiled program; the
        query buffer is donated on accelerators (it is dead after the
        dispatch), never the shared db/bias operands.
        """
        if backend == "sharded":
            mesh, spec = self._mesh, self.spec
            clustered = pk.cluster is not None
            cplan = pk.cluster.plan if clustered else None
            searcher = backends.make_sharded_search_fn(
                mesh, metric=spec.metric, k=spec.k,
                recall_target=spec.recall_target,
                db_axis=self._db_axis, batch_axis=batch_axis,
                use_bitonic=spec.use_bitonic,
                k_scan=packedlib.scan_k_for(spec, pk.n, live=self.size)
                if spec.storage != "f32" else None,
                cluster_probes=cplan.probes if clustered else None,
                cluster_target_scan=cplan.target_scan
                if clustered else None,
                kernel=self._shard_kernel(pk), bin_size=pk.bin_size,
            )
            if clustered and spec.storage == "f32":
                # Same positional-binding hazard as the block fn: the f32
                # clustered operand tuple must skip the quant slots.
                def call(q, ops):
                    db, bias, ce, cb, cr, sr = ops
                    return searcher(q, db, bias, None, None, None,
                                    ce, cb, cr, sr)
            else:
                def call(q, ops):
                    return searcher(q, *ops)
            stream = jax.jit(
                lambda blocks, *ops: jax.lax.map(
                    lambda q: call(q, ops), blocks
                )
            )
            qsharding = NamedSharding(mesh, P(None, batch_axis, None))
            def fn(blocks, *ops):
                return stream(jax.device_put(blocks, qsharding), *ops)
            return fn
        block_fn = self._build_block_fn(backend, pk)
        donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
        return jax.jit(
            lambda blocks, *ops: jax.lax.map(
                lambda q: block_fn(q, *ops), blocks
            ),
            donate_argnums=donate,
        )

    # -- updates (the paper's frequent-update path) --------------------------

    def add(self, rows: jnp.ndarray) -> "Index":
        """Append rows; grows capacity in ``capacity_block`` steps.

        No index rebuild: only the appended slice is metric-prepared
        (``Metric.prepare_update``) and patched into the packed state;
        growth re-lays-out the packed operands (one device copy) without
        re-deriving the metric precompute of existing rows.
        """
        faultslib.fire("index.add")  # before any state changes: add is
        # all-or-nothing under injection, so a failed extend never leaves
        # a half-patched packed state behind.
        rows = jnp.atleast_2d(jnp.asarray(rows))
        if rows.shape[1] != self.dim:
            raise ValueError(f"row dim {rows.shape[1]} != index dim {self.dim}")
        r = rows.shape[0]
        required = self._size + r
        had_packed = self._packed is not None
        rowwise = self.metric.rowwise
        if not rowwise:
            # Coupled preparation (e.g. a learned rotation refit): the
            # incremental patches below are undefined, so drop the state
            # now — also skips the pointless growth relayout copy.
            self._packed = None
        if required > self.capacity:
            # Linear growth in capacity_block steps, not doubling: spare
            # capacity is tombstone-masked but still *scored* on every
            # search, so over-allocation costs FLOPs, not just memory.
            block = self._capacity_block
            if self._mesh is not None:
                block = math.lcm(block, self._num_db_shards())
            if self.spec.residency == "host" and self.spec.segment_rows:
                # Capacity stays a whole number of segment waves, so the
                # compiled wave program's shape never changes under growth.
                block = math.lcm(block, self.spec.segment_rows)
            new_cap = round_up(required, block)
            grow = new_cap - self.capacity
            self._db = jnp.pad(self._db, ((0, grow), (0, 0)))
            self._live = jnp.pad(self._live, (0, grow))
            if self._packed is not None:
                self._packed = self._packed.relayout(
                    self._packed.backend, new_cap, self.spec
                )
            if self._kernel_plan is not None:
                # Same pinned tiles, re-planned bins/prediction for the
                # grown row space (mirrors the packed relayout).
                p = self._kernel_plan
                self._kernel_plan = dataclasses.replace(
                    self._replan(n=new_cap, m=p.m or None,
                                 backend=p.backend, pin_from=p),
                    source=p.source, pinned_tiles=p.pinned_tiles,
                )
            if self._mesh is not None:
                self._reshard()
        self._db = self._db.at[self._size : required].set(
            rows.astype(self._db.dtype)
        )
        self._live = self._live.at[self._size : required].set(True)
        if self._packed is not None:
            self._packed.update_rows(self._size, rows, self.metric)
        self._size = required
        self._num_live = self._num_live + r
        if had_packed and self._packed is None:
            self.pack()  # full repack — still at add() time, never at search
        pk = self._packed
        if pk is not None and pk.cluster is not None \
                and pk.cluster.needs_recluster:
            # Lazy recluster: incremental assignment spilled past the
            # planner's imbalance threshold, so rebuild the coarse
            # quantizer for the *current* capacity — at add() time, never
            # at search.  Same capacity => same table shapes => the
            # compiled programs stay valid (zero retraces in steady state).
            cplan = planlib.plan_clusters(
                n=self.capacity,
                k_scan=packedlib.scan_k_for(self.spec, self.capacity),
                recall_target=self.spec.recall_target,
            )
            if cplan.enabled:
                packedlib.rebuild_cluster(pk, self._live, self.metric, cplan)
                self._place_packed()
        return self

    def delete(self, ids) -> "Index":
        """Tombstone rows by index: masked out via the packed bias row.

        Deleted slots are not reclaimed (append-only storage); their ids
        never appear in subsequent search results.  Pure device-side
        patches — no host sync, so a serving loop's dispatch pipeline is
        never blocked (the live count materializes lazily via ``size``).
        """
        faultslib.fire("index.delete")  # before any patch: all-or-nothing
        ids = jnp.atleast_1d(jnp.asarray(ids, jnp.int32))
        self._live = self._live.at[ids].set(False)
        # Recount rather than decrement: ids may repeat (within a call or
        # across calls) and a gather-then-sum would count those twice.
        # Kept as a device scalar; ``size`` turns it into an int on read.
        self._num_live = jnp.sum(self._live)
        if self._packed is not None:
            self._packed.delete_rows(ids)
        return self

    # -- crash-safe snapshots ------------------------------------------------

    def save(self, path: str) -> str:
        """Write a crash-safe snapshot directory; returns the committed path.

        Serializes the raw database + live mask AND the packed search
        state — prepared rows, fused bias, quant scale/rescore tails,
        cluster side tables — via ``repro.checkpoint.save_snapshot``
        (tmp-dir + fsync + atomic-rename commit; an existing snapshot at
        ``path`` is replaced atomically, and a crash mid-save always
        leaves a loadable snapshot behind).  :meth:`restore` therefore
        re-runs *nothing*: no metric preparation, no quantization, no
        k-means — and returns bit-identical search results.

        Meshed (sharded) indexes save their full logical arrays; restore
        always lands unmeshed — call ``.shard(mesh)`` on the restored
        index before searching a ``backend="sharded"`` spec.
        """
        from repro.checkpoint.checkpoint import save_snapshot

        faultslib.fire("index.save")
        telemetrylib.registry().inc("repro_snapshot_saves_total")
        pk = self.pack()
        arrays, pk_meta = packedlib.snapshot_state(pk)
        arrays["db"] = self._db
        arrays["live"] = self._live
        meta = {
            "format": SNAPSHOT_FORMAT,
            "version": SNAPSHOT_VERSION,
            "spec": self.spec.to_json_dict(),
            "size": self._size,
            "num_live": self.size,  # materializes the lazy device scalar
            "capacity_block": self._capacity_block,
            "packed": pk_meta,
        }
        return save_snapshot(path, arrays, meta)

    @classmethod
    def restore(cls, path: str) -> "Index":
        """Load a snapshot written by :meth:`save` — no build work re-run.

        >>> import tempfile, os, jax.numpy as jnp
        >>> idx = Index.build(jnp.eye(32), metric="mips", k=2)
        >>> with tempfile.TemporaryDirectory() as d:
        ...     _ = idx.save(os.path.join(d, "snap"))
        ...     r = Index.restore(os.path.join(d, "snap"))
        >>> r.size == idx.size
        True
        """
        from repro.checkpoint.checkpoint import load_snapshot

        meta, arrays = load_snapshot(path)
        if meta.get("format") != SNAPSHOT_FORMAT:
            raise ValueError(
                f"{path} is not an index snapshot "
                f"(format={meta.get('format')!r})"
            )
        if int(meta.get("version", 0)) > SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {meta['version']} is newer than this "
                f"code's {SNAPSHOT_VERSION} — upgrade to restore it"
            )
        spec = SearchSpec.from_json_dict(meta["spec"])
        index = cls(
            spec,
            jnp.asarray(arrays["db"]),
            jnp.asarray(arrays["live"]),
            size=int(meta["size"]),
            num_live=int(meta["num_live"]),
            capacity_block=int(meta["capacity_block"]),
        )
        index._packed = packedlib.restore_state(arrays, meta["packed"], spec)
        index._place_packed()  # host-resident specs re-pin to host RAM
        telemetrylib.registry().inc("repro_snapshot_restores_total")
        return index

    # -- sharding ------------------------------------------------------------

    def shard(
        self,
        mesh: Mesh,
        *,
        db_axis="model",
        batch_axis: Optional[str] = None,
    ) -> "Index":
        """Return a mesh-sharded copy: rows P(db_axis, None), queries
        optionally sharded over ``batch_axis``.  A mesh with Explicit axis
        types is taken as the same devices with Auto axes.

        ``db_axis`` may be one mesh axis name or a *tuple* of names — the
        tuple form folds a pod-shaped (multi-host-shaped) mesh into one
        logical database split over the product of those axes; pairing it
        (or a single db axis) with ``batch_axis`` gives 2-D query x
        database sharding.  Capacity is padded (with tombstoned rows) to
        a multiple of the shard count; recall accounting against the
        global N is handled by the sharded backend internally.  The
        packed layout — including the metric precompute — is carried over
        (``relayout``), not rebuilt.  Tiles the user did not pin are
        re-planned for one shard's rows: where the shards run the fused
        kernel, each scans its own block of that layout.
        """
        if self.spec.residency != "hbm":
            raise ValueError(
                "host-resident indexes cannot be sharded — the cold tier "
                "streams segments through a single device's HBM; rebuild "
                "with residency='hbm' first"
            )
        mesh = auto_axes(mesh)
        n_shards = backends.db_shard_count(mesh, db_axis)
        cap = round_up(self.capacity, n_shards)
        db, live = self._db, self._live
        if cap > self.capacity:
            db = jnp.pad(db, ((0, cap - self.capacity), (0, 0)))
            live = jnp.pad(live, (0, cap - self.capacity))
        spec = self.spec.with_backend("sharded")
        p = self._kernel_plan
        if p is not None:
            # The planner's tiles are re-planned below, the user's kept.
            spec = dataclasses.replace(
                spec,
                block_m=spec.block_m if "block_m" in p.pinned_tiles
                else None,
                max_block_n=spec.max_block_n if "block_n" in p.pinned_tiles
                else None,
            )
        out = Index(
            spec, db, live,
            size=self._size, num_live=self._num_live,
            capacity_block=self._capacity_block,
            mesh=mesh, db_axis=db_axis, batch_axis=batch_axis,
            interpret=self._interpret,
        )
        if p is not None:
            out._kernel_plan = dataclasses.replace(
                out._replan(m=p.m or None, device=p.device),
                source=p.source, pinned_tiles=p.pinned_tiles,
            )
            out.spec = out._kernel_plan.to_spec(spec)
        if self._packed is not None:
            out._packed = self._packed.relayout(
                "sharded", cap, out.spec, **out._layout_args())
        out._reshard()
        out.pack()
        return out

    def _reshard(self):
        assert self._mesh is not None
        self._db = jax.device_put(
            self._db, NamedSharding(self._mesh, P(self._db_axis, None))
        )
        self._live = jax.device_put(
            self._live, NamedSharding(self._mesh, P(self._db_axis))
        )
        self._place_packed()

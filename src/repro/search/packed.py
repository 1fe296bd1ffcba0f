"""PackedState: device-resident, backend-layout search operands.

The paper's performance model (Eq. 10) bounds memory traffic at
``I_MEM ~ O(min(M, N))`` — which only holds if the (N, D) database is
touched *once* per search, not re-padded / re-prepared inside every
dispatch.  ``PackedState`` is the layer that guarantees it: at
``Index.build`` / mutation time (never at search time) it materializes

  * the metric-prepared, dtype-cast database in the resolved backend's
    native layout (Pallas: padded to the kernel tiling contract —
    D to a multiple of 128, N to a multiple of ``block_n``),
  * the fused bias row — metric bias (e.g. ``-||x||^2/2`` for L2),
    tombstone mask, and non-power-of-2 tail mask in one additive COP
    (paper Appendix A.5),
  * the bin plan the layout was derived from,

and hands backends pre-packed operands so the steady-state search
dispatch only ever pads the (M, D) *query* block.

Mutation contract (what patches what — the invalidation rules):

  * ``update_rows``  (``Index.add`` without growth): metric-prepares only
    the appended row slice (``Metric.prepare_update``) and patches the db
    rows + bias entries in place — O(r·D), zero O(N·D) work.
  * ``delete_rows``  (``Index.delete``): patches the bias row entries to
    ``MASK_VALUE`` — O(|ids|), the db rows are untouched.
  * ``relayout``     (capacity growth / resharding / backend switch): one
    O(N·D) device-side copy into the new layout, but *no* metric
    re-preparation of existing rows.
  * ``pack_state``   (build / spec change / non-rowwise metric): the only
    full pack — dtype cast + ``Metric.prepare_database`` over all rows.

``PACK_EVENTS`` counts these by name ("full_pack", "relayout",
"rows_updated", "bias_patched", "restore" — plus, on clustered indexes
only, "cluster_built" / "cluster_assigned" / "recluster") so tests and
benchmarks can assert the steady state performs none of them (and that a
snapshot restore performs *only* "restore" — no pack, no k-means).

Clustered indexes (``repro.search.cluster``) add a :class:`ClusterState`
of *side tables* — centroids, per-cluster row-id slots, a spill block —
while the packed arrays above stay in user row order, byte-identical to
the unclustered layout.  The tables are search operands like the bias
row, so slot patches never invalidate compiled programs; deletes need no
cluster work at all (the pruned scan gathers the fused bias row, which
already carries the tombstones).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.core.binning import BinPlan, plan_bins, round_up
from repro.search import cluster as clusterlib
from repro.search import quant
from repro.search import telemetry
from repro.search.backends import MASK_VALUE
from repro.search.metrics import Metric
from repro.search.plan import DEFAULT_BLOCK_N, shard_bins
from repro.search.spec import SearchSpec

__all__ = [
    "PACK_EVENTS",
    "PackedState",
    "fuse_bias",
    "pack_state",
    "rebuild_cluster",
    "reset_pack_events",
    "restore_state",
    "scan_k_for",
    "snapshot_state",
]

# event name -> count of packing work performed (test observability hook;
# see module docstring for the event taxonomy).  AtomicCounter + registry
# adoption: see ``repro.search.telemetry``.
PACK_EVENTS = telemetry.AtomicCounter()
telemetry.registry().register_counter_dict(
    "repro_pack_events_total", PACK_EVENTS, "event",
    "packing/cluster/restore work performed (repro.search.packed)",
)


def reset_pack_events() -> None:
    """Zero ``PACK_EVENTS`` (use in tests instead of counter arithmetic).

    Deprecated thin alias: ``repro.search.telemetry.reset_all()`` zeroes
    this and every other global series in one call."""
    PACK_EVENTS.clear()


def fuse_bias(
    metric_bias: Optional[jnp.ndarray],
    live: Optional[jnp.ndarray] = None,
    *,
    num_rows: Optional[int] = None,
) -> jnp.ndarray:
    """Fuse metric bias and tombstone mask into one additive (n,) f32 row.

    ``live=None`` means every row is live (the functional one-shot path).
    The ``maximum(..., MASK_VALUE)`` clamp keeps the row finite so the
    MXU/VPU paths stay NaN-free while still losing every comparison.
    """
    if live is None:
        if metric_bias is None:
            return jnp.zeros((num_rows,), jnp.float32)
        return jnp.maximum(metric_bias.astype(jnp.float32), MASK_VALUE)
    tomb = jnp.where(live, 0.0, MASK_VALUE).astype(jnp.float32)
    if metric_bias is None:
        return tomb
    return jnp.maximum(tomb + metric_bias.astype(jnp.float32), MASK_VALUE)


@dataclasses.dataclass
class PackedState:
    """Device-resident operands for one (backend, capacity, spec) layout.

    Attributes:
      backend: "xla" | "pallas" | "sharded" — decides the layout.
      db: metric-prepared database.  (n, d) for xla/sharded; padded
        (n_pad, d_pad) for pallas (tiling contract of the fused kernel).
      bias: fused bias row.  (n,) f32 for xla/sharded; (1, n_pad) for
        pallas with the tail positions pre-masked to ``MASK_VALUE``.
      n: logical row space covered (== Index.capacity when packed).
      d: logical feature dim (before lane padding).
      plan: the BinPlan the pallas layout was derived from.  For quantized
        tiers the plan is laid out for the over-fetched scan k
        (``repro.search.quant.scan_k``), not the user's k.
      bin_size / block_n: kernel tile parameters (block_n == 0 for the
        gather layout: xla, and sharded where the shards scan with XLA).
      shards: database shards (1 off the sharded backend).  The sharded
        backend lays its bins per shard (``repro.search.plan.shard_bins``),
        and its kernel layout holds one block of rows a shard: shard s's
        n/shards rows at ``[s·rows, s·rows + n/shards)`` of its ``rows`` =
        ``db.shape[0] // shards`` rows, its own tail masked, so each
        shard's block meets the tiling contract.
      storage: the ``repro.search.quant`` tier ``db`` is stored in.  For
        ``"int4"`` the pallas layout stores two codes per byte (db shape
        (n_pad, d_pad/2) int8); other backends keep the canonical one
        code per byte.
      scale: per-row dequantization scale (int8/int4 tiers) — (n,) f32,
        or (1, n_pad) for the pallas layout; None for unscaled tiers.
      rescore_db: full-precision metric-prepared rows (n, d) — the exact
        rescore tail the two-pass search gathers candidates from; None
        when rescoring is disabled or storage is "f32".
      rescore_bias: fused f32 bias row (n,) for the rescore pass — the
        *exact* metric bias plus the same tombstone mask as ``bias``, so
        rescoring can never resurrect a deleted (or padded) row.
    """

    backend: str
    db: jnp.ndarray
    bias: jnp.ndarray
    n: int
    d: int
    plan: BinPlan
    bin_size: int
    block_n: int
    storage: str = "f32"
    shards: int = 1
    scale: Optional[jnp.ndarray] = None
    rescore_db: Optional[jnp.ndarray] = None
    rescore_bias: Optional[jnp.ndarray] = None
    # cluster-pruning side tables (repro.search.cluster); None on
    # unclustered layouts — in which case nothing below changes shape,
    # content or operand order (the bit-identical guarantee).
    cluster: Optional[clusterlib.ClusterState] = None
    # set when the planner enabled pruning but the build-time empirical
    # miss check measured this rate and rejected the tables (structureless
    # data the decay model does not fit); the layout then behaves exactly
    # like cluster="off".  Surfaced by Index.explain().
    cluster_rejected_miss: Optional[float] = None
    # dtype the database was cast to before preparation/quantization;
    # incremental updates must repeat the same cast-then-prepare order so
    # slice and full packs agree exactly (db.dtype itself is the *stored*
    # dtype on quantized tiers, which is not the same thing).
    compute_dtype: str = "float32"

    # -- logical views --------------------------------------------------------

    def rows(self) -> jnp.ndarray:
        """The prepared rows without layout padding: (n, d).

        Always the *canonical* stored form — for the pallas int4 layout
        (two codes per byte on device) the nibbles are unpacked back to
        one int8 code per element, so relayout/snapshot consumers never
        see the packed width.
        """
        rows = self._unblock(self.db)
        if self.storage == "int4" and self.block_n:
            return quant.unpack_int4_rows(rows)[:, : self.d]
        return rows[:, : self.d]

    def bias_row(self) -> jnp.ndarray:
        """The fused bias without layout padding: (n,)."""
        return self._unblock(self.bias[0] if self.bias.ndim == 2
                             else self.bias)

    def scale_row(self) -> Optional[jnp.ndarray]:
        """The int8 per-row scale without layout padding: (n,) or None."""
        if self.scale is None:
            return None
        return self._unblock(self.scale[0] if self.scale.ndim == 2
                             else self.scale)

    def _unblock(self, arr: jnp.ndarray) -> jnp.ndarray:
        """The n logical rows of a per-row array, each shard's padded
        tail dropped (one block on every layout but the sharded kernel
        layout)."""
        if self.shards <= 1 or not self.block_n:
            return arr[: self.n]
        blocks = arr.reshape(self.shards, -1, *arr.shape[1:])
        return blocks[:, : self.n // self.shards].reshape(
            self.n, *arr.shape[1:])

    def _positions(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Layout positions of user row ids: shard s's block starts
        ``s · (n/shards + its tail)`` rows in, on the sharded kernel
        layout; everywhere else a row sits at its id."""
        if self.shards <= 1 or not self.block_n:
            return ids
        n_local = self.n // self.shards
        tail = self.db.shape[0] // self.shards - n_local
        return ids + (ids // n_local) * tail

    def exact_rows_bias(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-precision prepared rows + fused bias, (n, d) / (n,).

        The exact scoring source the monitors and the lazy recluster use:
        the f32 tier's own rows, a quantized tier's rescore tail, or —
        rescore disabled — the dequantized stored rows (coarse structure
        only, fine for centroid geometry and miss sampling).
        """
        if self.storage == "f32":
            return self.rows(), self.bias_row()
        if self.rescore_db is not None:
            return self.rescore_db[: self.n], self.rescore_bias[: self.n]
        return (
            quant.dequantize_rows(self.rows(), self.scale_row()),
            self.bias_row(),
        )

    def operands(self) -> Tuple[Optional[jnp.ndarray], ...]:
        """The positional device operands a search dispatch consumes.

        ``(db, bias)`` for the f32 tier (today's exact call shape);
        ``(db, bias, scale, rescore_db, rescore_bias)`` for quantized
        tiers (entries may be None — e.g. bf16 has no scale).  Clustered
        layouts append the four side tables (centroids, centroid_bias,
        cluster_rows, spill_rows) after either shape.  Passing these as
        *operands* rather than closure captures is what lets bias/row/
        scale/slot patches leave compiled programs valid.
        """
        if self.storage == "f32":
            base: Tuple[Optional[jnp.ndarray], ...] = (self.db, self.bias)
        else:
            base = (
                self.db, self.bias, self.scale,
                self.rescore_db, self.rescore_bias,
            )
        if self.cluster is not None:
            return base + self.cluster.operands()
        return base

    # -- in-place patches (the cheap mutations) -------------------------------

    @staticmethod
    def _patch_row(arr: jnp.ndarray, where, values: jnp.ndarray
                   ) -> jnp.ndarray:
        """Write ``values`` at ``where`` (a slice or layout positions) into
        a per-row array in either layout — (n,) for the gather layout or
        (1, n_pad) for the kernel layout (bias and scale alike)."""
        if arr.ndim == 2:
            return arr.at[0, where].set(values)
        return arr.at[where].set(values)

    def update_rows(self, start: int, rows: jnp.ndarray, metric: Metric):
        """Patch an appended row slice: prepare only the slice, O(r·D).

        ``rows`` are raw (unprepared) and are cast to the packed compute
        dtype before preparation — the same cast-then-prepare(-then-
        quantize) order as the full pack, so incremental and full packs
        are numerically identical (quantization is per-row, see
        ``Metric.prepare_update_storage``).
        """
        if self.storage == "f32":
            prepped, metric_bias = metric.prepare_update(
                rows.astype(self.db.dtype)
            )
        else:
            qr = metric.prepare_update_storage(
                rows.astype(jnp.dtype(self.compute_dtype)), self.storage
            )
            prepped, metric_bias = qr.rows, qr.bias
        r = prepped.shape[0]
        slice_bias = fuse_bias(metric_bias, num_rows=r)
        # Exact prepared slice (pre-padding) for cluster assignment below:
        # the same space the centroids were derived in.
        exact_slice = (
            prepped if self.storage == "f32" else qr.exact_rows
        )
        if self.storage == "int4" and self.block_n:
            # Canonical codes -> the on-device nibble-packed width: pad
            # lanes to the logical d_pad (2x the stored byte width), then
            # pack two codes per byte.  Same order as the full pack.
            prepped = quant.pack_int4_rows(
                jnp.pad(
                    prepped,
                    ((0, 0), (0, 2 * self.db.shape[1] - prepped.shape[1])),
                )
            )
        elif prepped.shape[1] < self.db.shape[1]:  # pallas lane padding
            prepped = jnp.pad(
                prepped, ((0, 0), (0, self.db.shape[1] - prepped.shape[1]))
            )
        where = slice(start, start + r)
        if self.shards > 1 and self.block_n:
            where = self._positions(jnp.arange(start, start + r))
        self.db = self.db.at[where].set(prepped)
        self.bias = self._patch_row(self.bias, where, slice_bias)
        if self.storage != "f32":
            if self.scale is not None:
                self.scale = self._patch_row(self.scale, where, qr.scale)
            if self.rescore_db is not None:
                self.rescore_db = self.rescore_db.at[
                    start : start + r
                ].set(qr.exact_rows.astype(self.rescore_db.dtype))
                self.rescore_bias = self.rescore_bias.at[
                    start : start + r
                ].set(fuse_bias(qr.exact_bias, num_rows=r))
        if self.cluster is not None:
            # Incremental nearest-centroid slotting (spill block absorbs
            # overflow); O(r·C) — no repack, no table reshape, so the
            # compiled pruned program stays valid.
            clusterlib.assign_rows(self.cluster, exact_slice, start)
            PACK_EVENTS.inc("cluster_assigned")
        PACK_EVENTS.inc("rows_updated")

    def delete_rows(self, ids: jnp.ndarray):
        """Tombstone rows: patch only the bias entries, O(|ids|).

        Quantized tiers patch the rescore bias row too — the exact second
        pass recomputes true scores, so it must carry its own tombstone
        mask or rescoring would resurrect deleted rows.
        """
        self.bias = self._patch_row(
            self.bias, self._positions(ids), MASK_VALUE)
        if self.rescore_bias is not None:
            self.rescore_bias = self.rescore_bias.at[ids].set(MASK_VALUE)
        PACK_EVENTS.inc("bias_patched")

    # -- layout changes (copy, but never metric re-preparation) ---------------

    def relayout(
        self, backend: str, new_n: int, spec: SearchSpec,
        shards: Optional[int] = None, tiled: Optional[bool] = None,
    ) -> "PackedState":
        """Re-layout for a new capacity and/or backend, reusing prepared rows.

        One O(N·D) device copy; the grown region is dead (bias
        ``MASK_VALUE``) until ``update_rows`` writes it.  This is what
        capacity growth and ``Index.shard`` use so the packed layout — and
        the metric precompute in it — survives the transition.
        ``shards`` and ``tiled`` are as for :func:`pack_state`; by default
        a layout of the same backend keeps its own.
        """
        if backend == self.backend:
            shards = self.shards if shards is None else shards
            tiled = bool(self.block_n) if tiled is None else tiled
        rows = self.rows()
        bias = self.bias_row()
        scale = self.scale_row()
        rescore_db, rescore_bias = self.rescore_db, self.rescore_bias
        if new_n > self.n:
            grow = new_n - self.n
            rows = jnp.pad(rows, ((0, grow), (0, 0)))
            bias = jnp.pad(bias, (0, grow), constant_values=MASK_VALUE)
            if scale is not None:
                scale = jnp.pad(scale, (0, grow))
            if rescore_db is not None:
                rescore_db = jnp.pad(rescore_db, ((0, grow), (0, 0)))
                rescore_bias = jnp.pad(
                    rescore_bias, (0, grow), constant_values=MASK_VALUE
                )
        PACK_EVENTS.inc("relayout")
        out = _layout(
            backend, rows, bias, new_n, self.d, spec, shards=shards or 1,
            tiled=tiled, scale=scale, rescore_db=rescore_db, rescore_bias=rescore_bias,
            compute_dtype=self.compute_dtype,
        )
        # The side tables hold user row ids, which a relayout never
        # renumbers — carry them verbatim (grown rows are slotted by the
        # update_rows that writes them; a stale-geometry table is caught
        # by Index.add's lazy-recluster trigger).
        out.cluster = self.cluster
        return out


def scan_k_for(
    spec: SearchSpec, n: int, live: Optional[int] = None
) -> int:
    """The k the scan's bin layout is planned for.

    Quantized tiers with rescoring over-fetch (``quant.scan_k``) so the
    exact second pass can restore the Eq. 13–14 guarantee; everything else
    plans for the user's k exactly as before.

    ``live`` caps the over-fetch at the current live-row count (floored at
    ``spec.k`` — the rescore still needs k outputs): after heavy deletes
    an uncapped ``k_scan > live_n`` made the rescore gather read rows that
    could only be tombstones.  The cap binds when the search program is
    built; later deletes are handled by the sentinel/mask propagation
    (masked candidates carry index -1 and can never surface), so no
    retrace is ever needed.
    """
    if spec.rescore_enabled:
        ks = quant.scan_k(spec.storage, spec.k, n=n)
        if live is not None:
            ks = max(spec.k, min(ks, max(int(live), 0)))
        return ks
    return spec.k


def _layout_bins(backend: str, n: int, spec: SearchSpec,
                 shards: int) -> BinPlan:
    """The bin plan of a layout: the recall plan over its n rows, or on the
    sharded backend ``plan.shard_bins``'s (bins laid per shard against
    the global N and capped at the planned tile)."""
    k_scan = scan_k_for(spec, n)
    if backend == "sharded":
        return shard_bins(
            n, shards, k_scan, spec.recall_target,
            spec.max_block_n or DEFAULT_BLOCK_N,
            spec.reduction_input_size_override,
        )
    return plan_bins(
        n, k_scan, spec.recall_target,
        reduction_input_size_override=spec.reduction_input_size_override,
    )


# Rows of a database block padded per step of ``_pad_rows``: the padded
# copy of a step is its only temporary (128 MiB at 128 f32 lanes).
PAD_CHUNK_ROWS = 1 << 18


def _pad_rows(x, *, rows: int, width: int, fill):
    """``x`` (n, ...) as ``rows`` rows, padded with ``fill`` (a 2-D
    ``x``'s lanes too, to ``width``).  Copied a chunk of rows at a time,
    the last chunk overlapping the one before, so no whole-size temporary
    exists: a TPU stores a (n, d < 128) f32 array lane-major, and a
    one-shot pad first copies all of it into the row-major layout."""
    n = x.shape[0]
    wide = (width,) if x.ndim == 2 else ()
    lanes = [(0, 0), (0, width - x.shape[1])] if x.ndim == 2 else [(0, 0)]
    out = jnp.full((rows, *wide), fill, x.dtype)
    chunk = min(PAD_CHUNK_ROWS, n)

    def step(i, out):
        start = jnp.minimum(i * chunk, n - chunk)
        part = jax.lax.dynamic_slice_in_dim(x, start, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.pad(part, lanes, constant_values=fill), start, 0)

    return jax.lax.fori_loop(0, -(-n // chunk), step, out)


def _blocks(x, *, shards: int, rows: int, width: int = 0, fill=0.0):
    """``x``'s rows as ``shards`` blocks of ``rows`` rows: block s holds
    rows ``[s·n/shards, (s+1)·n/shards)`` then ``fill``, and a 2-D ``x``
    has its lanes padded to ``width`` (``_pad_rows``): the kernel layout.
    Where ``x`` is already split into those blocks over devices, each
    device pads its own (``shard_map``), so none holds more than its
    share."""
    pad = functools.partial(_pad_rows, rows=rows, width=width, fill=fill)
    sharding = getattr(x, "sharding", None)
    if (shards > 1 and isinstance(sharding, NamedSharding)
            and sharding.shard_shape(x.shape)[0] == x.shape[0] // shards):
        return jax.jit(jax.shard_map(
            pad, mesh=sharding.mesh, in_specs=sharding.spec,
            out_specs=sharding.spec, check_vma=False,
        ))(x)
    return jax.jit(lambda x: jnp.concatenate(
        [pad(part) for part in jnp.split(x, shards)]))(x)


def _layout(
    backend: str,
    rows: jnp.ndarray,
    bias: jnp.ndarray,
    n: int,
    d: int,
    spec: SearchSpec,
    *,
    shards: int = 1,
    tiled: Optional[bool] = None,
    scale: Optional[jnp.ndarray] = None,
    rescore_db: Optional[jnp.ndarray] = None,
    rescore_bias: Optional[jnp.ndarray] = None,
    compute_dtype: str = "float32",
) -> PackedState:
    """Lay prepared (rows, bias) out in the backend's native shape.

    ``shards`` and ``tiled`` are as for :func:`pack_state`.  The rescore
    tail stays in gather layout — (n, d) rows, (n,) bias — on every
    backend: the second pass reads O(M·L) candidates by index, never a
    tiled stream, so it has no kernel layout to satisfy (a shard's
    candidate ids are its own rows' offsets, which its share of the
    (n, d) tail is indexed by).
    """
    plan = _layout_bins(backend, n, spec, shards)
    if not (backend == "pallas" if tiled is None else tiled):
        return PackedState(
            backend=backend, db=rows, bias=bias, n=n, d=d,
            plan=plan, bin_size=plan.bin_size, block_n=0,
            storage=spec.storage, shards=shards, scale=scale,
            rescore_db=rescore_db, rescore_bias=rescore_bias,
            compute_dtype=compute_dtype,
        )
    # Specs built via Index.build are always planner-resolved; direct
    # pack_state callers may pass an unresolved spec, which gets the
    # planner's anchor tile (repro.search.plan owns the real model).
    bin_size = plan.bin_size
    block_n = bin_size * max(1, (spec.max_block_n or DEFAULT_BLOCK_N)
                             // bin_size)
    rows_pad = round_up(max(n // shards, block_n), block_n)
    # int4 stores two codes per byte on device: pad the logical lanes to a
    # 256-multiple so the packed byte width stays a 128-lane multiple, then
    # nibble-pack (zero pad codes dequantize to 0, exact for dot products
    # like zero lanes).  A padded-tail scale is 0: tail scores become
    # 0*dot + MASK.
    d_pad = round_up(d, 256 if spec.storage == "int4" else 128)
    if shards == 1:
        # One block (the Pallas backend, a one-device mesh) is padded in
        # one shot; a shard's block a chunk at a time (``_blocks``), where
        # a one-shot pad of a lane-major shard would not fit beside it.
        rows = jnp.pad(rows, ((0, rows_pad - n), (0, d_pad - d)))
        full = jnp.full((rows_pad,), MASK_VALUE, jnp.float32).at[:n].set(bias)
        if scale is not None:
            scale = jnp.zeros((rows_pad,), jnp.float32).at[:n].set(scale)
    else:
        rows = _blocks(rows, shards=shards, rows=rows_pad, width=d_pad)
        full = _blocks(bias, shards=shards, rows=rows_pad, fill=MASK_VALUE)
        if scale is not None:
            scale = _blocks(scale, shards=shards, rows=rows_pad)
    if spec.storage == "int4":
        rows = quant.pack_int4_rows(rows)
    if scale is not None:
        scale = scale[None, :]
    return PackedState(
        backend=backend, db=rows, bias=full[None, :], n=n, d=d,
        plan=plan, bin_size=bin_size, block_n=block_n,
        storage=spec.storage, shards=shards, scale=scale,
        rescore_db=rescore_db, rescore_bias=rescore_bias,
        compute_dtype=compute_dtype,
    )


def pack_state(
    database: jnp.ndarray,
    live: Optional[jnp.ndarray],
    metric: Metric,
    spec: SearchSpec,
    backend: str,
    cluster_plan: Optional[clusterlib.ClusterPlan] = None,
    shards: int = 1,
    tiled: Optional[bool] = None,
) -> PackedState:
    """Full pack: dtype cast + metric preparation over all rows + layout.

    The only entry point that runs ``Metric.prepare_database`` on the
    whole database — everything after build goes through the incremental
    patches above.

    ``shards``: the database's shard count on the sharded backend, which
    lays its bins per shard (1 elsewhere).  ``tiled``: the kernel layout
    (one block of ``block_n``-aligned rows a shard, lanes padded) rather
    than the gather layout — for the fused kernel, which the Pallas
    backend always runs and the sharded backend's shards run where
    ``backends.shard_scan`` picks it; default: on pallas only.

    ``cluster_plan``: an *enabled* ``repro.search.cluster.ClusterPlan``
    builds the pruning side tables over the live prepared rows (k-means +
    capacity-constrained assignment); ``None`` — or a plan the planner
    left disabled — packs exactly as before.

    >>> import jax.numpy as jnp
    >>> from repro.search.metrics import get_metric
    >>> from repro.search.spec import SearchSpec
    >>> st = pack_state(jnp.ones((10, 4)), None, get_metric("mips"),
    ...                 SearchSpec(k=2), "xla")
    >>> (st.backend, st.n, st.d, st.rows().shape)
    ('xla', 10, 4, (10, 4))
    """
    n, d = database.shape
    db = database
    if spec.dtype is not None:
        db = db.astype(jnp.dtype(spec.dtype))
    if spec.storage == "f32":
        db, metric_bias = metric.prepare_database(db)
        bias = fuse_bias(metric_bias, live, num_rows=n)
        PACK_EVENTS.inc("full_pack")
        state = _layout(backend, db, bias, n, d, spec, shards=shards,
                        tiled=tiled)
        _attach_cluster(state, db, bias, live, metric, cluster_plan, spec.k)
        return state
    # Quantized tier: metric-prepare, quantize, fold the bias correction
    # (metric bias of the *stored* values) into the fused scan bias, and
    # optionally keep the full-precision rescore tail with its own fused
    # (exact-bias + tombstone) row.
    qr = metric.prepare_storage(db, spec.storage)
    bias = fuse_bias(qr.bias, live, num_rows=n)
    rescore_db = rescore_bias = None
    if spec.rescore_enabled:
        rescore_db = qr.exact_rows.astype(jnp.float32)
        rescore_bias = fuse_bias(qr.exact_bias, live, num_rows=n)
    PACK_EVENTS.inc("full_pack")
    state = _layout(
        backend, qr.rows, bias, n, d, spec, shards=shards, tiled=tiled,
        scale=qr.scale, rescore_db=rescore_db, rescore_bias=rescore_bias,
        compute_dtype=str(db.dtype),
    )
    exact_fused = (
        rescore_bias
        if rescore_bias is not None
        else fuse_bias(qr.exact_bias, live, num_rows=n)
    )
    _attach_cluster(
        state, qr.exact_rows, exact_fused, live, metric, cluster_plan, spec.k
    )
    return state


def _attach_cluster(
    state: PackedState,
    exact_rows: jnp.ndarray,
    fused_bias: jnp.ndarray,
    live: Optional[jnp.ndarray],
    metric: Metric,
    cluster_plan: Optional[clusterlib.ClusterPlan],
    k: int,
) -> None:
    """Build, validate and attach the pruning side tables (enabled plans).

    ``exact_rows`` are the metric-prepared full-precision rows — the space
    queries score in, so centroids derived here rank clusters exactly the
    way the pruned scan will; ``fused_bias`` is the matching fused
    (metric + tombstone) bias row.

    The planner's crossover prices FLOPs, not geometry, so the decay
    model's clusterable-data assumption is checked empirically here:
    ``sampled_miss_rate`` measures the actual miss rate of the built
    tables on sampled live rows, and a measurement past
    ``miss_check_threshold`` discards them — the layout falls back to the
    dense scan (bit-identical to ``cluster="off"``) instead of silently
    trading recall for speed on data the model does not fit.
    """
    if cluster_plan is None or not cluster_plan.enabled:
        return
    cs = clusterlib.build_tables(
        exact_rows, live, cluster_plan, metric.prepare_database
    )
    miss = clusterlib.sampled_miss_rate(cs, exact_rows, fused_bias, live, k)
    if miss > clusterlib.miss_check_threshold(cluster_plan.miss_budget):
        state.cluster_rejected_miss = miss
        PACK_EVENTS.inc("cluster_rejected")
        return
    state.cluster = cs
    PACK_EVENTS.inc("cluster_built")


def rebuild_cluster(
    state: PackedState,
    live: Optional[jnp.ndarray],
    metric: Metric,
    cluster_plan: clusterlib.ClusterPlan,
) -> None:
    """Lazy recluster: re-derive centroids + tables from the packed rows.

    Triggered by ``Index.add`` when ``ClusterState.needs_recluster`` says
    spill pressure is past the planner threshold (the cluster analogue of
    the lazy bin replan).  O(N·C·D) device k-means plus O(N) host
    assignment — but *no* repack: the packed rows/bias/scale arrays are
    reused as-is, and at unchanged capacity the new tables keep their
    shapes, so compiled pruned programs stay valid (zero retrace).

    Quantized tiers recluster from the exact rescore tail when present,
    else from the dequantized stored rows — centroid geometry only needs
    coarse structure, so tier rounding is immaterial.

    No miss re-check here: the data passed the build-time check (the
    clustered path only exists because it did), and dropping the tables
    mid-life would change the compiled program's operand shape — a
    retrace the steady-state contract forbids.
    """
    rows, _ = state.exact_rows_bias()
    state.cluster = clusterlib.build_tables(
        rows, live, cluster_plan, metric.prepare_database
    )
    PACK_EVENTS.inc("recluster")


# -- crash-safe snapshots (Index.save / Index.restore) ------------------------

def snapshot_state(state: PackedState) -> Tuple[dict, dict]:
    """Serialize a PackedState into ``(arrays, meta)`` for a snapshot.

    Captures everything a bit-identical restore needs *without* re-running
    any build work: the laid-out device arrays verbatim (including pallas
    padding — so the restored operands are byte-identical to the saved
    ones), the layout constants, and the cluster side tables.  The BinPlan
    is NOT serialized: ``plan_bins`` is deterministic in (n, k_scan,
    recall_target), so :func:`restore_state` recomputes it and *verifies*
    the recomputed bin size against the recorded one — which doubles as a
    version-skew detector for the binning math itself.
    """
    arrays = {"packed/db": state.db, "packed/bias": state.bias}
    if state.scale is not None:
        arrays["packed/scale"] = state.scale
    if state.rescore_db is not None:
        arrays["packed/rescore_db"] = state.rescore_db
        arrays["packed/rescore_bias"] = state.rescore_bias
    meta = {
        "backend": state.backend,
        "n": state.n,
        "d": state.d,
        "bin_size": state.bin_size,
        "block_n": state.block_n,
        "shards": state.shards,
        "storage": state.storage,
        "compute_dtype": state.compute_dtype,
        "cluster_rejected_miss": state.cluster_rejected_miss,
        "cluster": None,
    }
    if state.cluster is not None:
        cl_arrays, cl_meta = clusterlib.snapshot_tables(state.cluster)
        arrays.update(cl_arrays)
        meta["cluster"] = cl_meta
    return arrays, meta


def restore_state(arrays: dict, meta: dict, spec: SearchSpec) -> PackedState:
    """Rebuild a PackedState from :func:`snapshot_state` output.

    No metric preparation, no quantization, no k-means — the arrays land
    on device exactly as saved, which is what makes restored search
    results bit-identical to the original replica's.
    """
    n = int(meta["n"])
    shards = int(meta.get("shards", 1))
    plan = _layout_bins(meta["backend"], n, spec, shards)
    if plan.bin_size != meta["bin_size"]:
        raise ValueError(
            f"snapshot bin_size={meta['bin_size']} but this version plans "
            f"bin_size={plan.bin_size} for the same (n, k, target) — the "
            "binning math changed since the snapshot was written; rebuild "
            "the index"
        )
    scale = arrays.get("packed/scale")
    quant.validate_restored(
        meta["storage"], arrays["packed/db"].dtype, has_scale=scale is not None
    )
    rescore_db = arrays.get("packed/rescore_db")
    state = PackedState(
        backend=meta["backend"],
        db=jnp.asarray(arrays["packed/db"]),
        bias=jnp.asarray(arrays["packed/bias"]),
        n=n,
        d=int(meta["d"]),
        plan=plan,
        bin_size=int(meta["bin_size"]),
        block_n=int(meta["block_n"]),
        storage=meta["storage"],
        shards=shards,
        scale=None if scale is None else jnp.asarray(scale),
        rescore_db=None if rescore_db is None else jnp.asarray(rescore_db),
        rescore_bias=(
            None if rescore_db is None
            else jnp.asarray(arrays["packed/rescore_bias"])
        ),
        cluster_rejected_miss=meta.get("cluster_rejected_miss"),
        compute_dtype=meta.get("compute_dtype", "float32"),
    )
    if meta.get("cluster") is not None:
        state.cluster = clusterlib.restore_tables(arrays, meta["cluster"])
    PACK_EVENTS.inc("restore")
    return state

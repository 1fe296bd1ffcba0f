"""Pallas TPU kernel: fused score + PartialReduce (paper Alg. 2).

One grid step computes a (block_m, block_n) tile of the query-database score
matrix on the MXU and immediately reduces it to the top-1 (value, index) of
each bin of size 2**W — the O(M*N) score tile never leaves VMEM, which is
the whole point of the paper (I_MEM ~ O(min(M, N)), Eq. 10).

Two selection back-ends share that scan:

  * **Two-pass** (``partial_reduce_packed``): every grid step writes its
    (block_m, bins_per_block) bin-winner tile to HBM and the caller merges
    the (M, N/bin_size) winners with ``lax.top_k``.  Simple, and the
    parity oracle for the fused path.
  * **Fused** (``partial_reduce_fused``): a winner buffer in VMEM
    scratch holds every bin winner (value + global index) of the query
    block, laid out as (ceil(L/128), block_m, 128) for L bins.  Each grid
    step of the sequential j-loop stores its tile's winners at their
    global bin positions (a load, select and store of one 128-lane row
    per 128 bins); the last step selects the top-k_scan of the buffer
    once, and only that (M, k_scan) result is ever written to HBM (Eq. 20:
    database bytes + O(k), no score-tile term).  The result is the one
    ``lax.top_k`` gives over the two-pass winner row, bit for bit.
    Masked winners (tombstones, padded tail) carry the sentinel index -1
    alongside their -inf value, so they can never collide with a live row
    after the select.

COP accounting (Appendix A.5): the in-tile reduction uses exactly 3
coefficient-wise ops per score (compare/select for the running max, the
iota compare, and the index min) = the paper's C=3.  The bias row fuses both
the non-power-of-2 masking COP and the L2 halved-norm COP into one add.
The fused select adds one 128-lane select per tile and k_scan extractions
over the ceil(L/128)·128-lane buffer per query block — amortized over the
database rows, a lower-order COP term.

Tiling contract (enforced by ops.py / repro.search.packed):
  * D is padded to a multiple of 128 (MXU lane width; 256 for the packed
    int4 tier so the two-codes-per-byte rows stay lane-aligned),
  * block_n is a multiple of the bin size 2**W,
  * N is padded to a multiple of block_n (bias = -inf on the padding),
  * block_m rows of queries are resident in VMEM across the j-loop
    (temporal locality of Alg. 2 line 1).  block_m is clamped to the
    sublane-rounded M, so an M=1 serving dispatch no longer pays a full
    block of wasted MXU rows.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binning import round_up

__all__ = [
    "partial_reduce_fused",
    "partial_reduce_fused_pallas",
    "partial_reduce_packed",
    "partial_reduce_pallas",
    "scan_operands",
    "SELECT_MODE",
    "winner_buffer_bytes",
]

# Same sentinel the search stages use (stages.MASK_VALUE); redeclared here
# so the kernel layer stays import-free of repro.search.
_MASK = float(jnp.finfo(jnp.float32).min)
_LANES = 128

# How the fused kernel selects: every bin winner of a query block is kept
# in VMEM and the top-k_scan is taken once, at the block's last tile.  The
# label its dispatches are counted under (repro.search.backends).
SELECT_MODE = "deferred"
# Query rows the final select works on at once: four f32 sublane tiles,
# whose independent extraction chains overlap (on a v5e, 1.2-2.0 ms less
# kernel time a 4,096-query block than one tile at a time, at k_scan 10
# and 20).
_SELECT_ROWS = 32


def _winner_chunks(n: int, bin_size: int) -> int:
    """128-lane chunks that hold the bin winners of an n-row database."""
    return -(-(n // bin_size) // _LANES)


def winner_buffer_bytes(block_m: int, n: int, bin_size: int) -> int:
    """VMEM bytes of the fused kernel's winner buffer: an f32 value and an
    int32 global id for every bin of an n-row (block-padded) database, for
    each of the block_m query rows, in whole 128-lane chunks."""
    return 2 * 4 * block_m * _winner_chunks(n, bin_size) * _LANES


def _effective_block_m(m: int, block_m: int, dtype) -> int:
    """Clamp the query tile to the sublane-rounded batch size.

    The planner's block_m targets throughput batches; a small serving
    batch (M=1) padded all the way to it would compute block_m rows of
    wasted MXU work per tile.  The sublane floor (8 f32 / 16 bf16 rows)
    is the hardware minimum.
    """
    sublane = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    return min(block_m, round_up(max(m, 1), sublane))


def partial_reduce_packed(
    queries: jnp.ndarray,   # (m, d) — any m, d <= database's lane-padded d
    database: jnp.ndarray,  # (n_pad, d_pad) pre-packed to the tiling contract
    bias: jnp.ndarray,      # (1, n_pad) f32, tail already masked
    scale: jnp.ndarray = None,  # (1, n_pad) f32 per-row scale (int8/int4)
    *,
    bin_size: int,
    block_m: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
    int4_packed: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Query-side front half of the tiling contract over packed operands.

    The database and bias must already be packed (D padded to a lane
    multiple, N padded to ``block_n`` with masked tail) — see
    ``repro.search.packed``.  Only the (m, d) query block is padded here,
    so repeated searches against the same database perform zero
    database-sized copies.  ``database`` may be stored in a reduced-
    precision tier, so HBM streams the reduced bytes: bf16 rows meet
    queries rounded to bf16 (:func:`_scan_queries`), int8/int4 codes are
    cast tile-locally in VMEM; ``scale`` carries the per-row scale,
    and ``int4_packed`` marks a two-codes-per-byte database whose logical
    width is twice its stored width.
    Returns (values, indices) with the query padding already stripped:
    both (m, n_pad // bin_size).
    """
    m, d = queries.shape
    d_pad = database.shape[1] * (2 if int4_packed else 1)
    if d > d_pad:
        raise ValueError(f"query dim {d} exceeds packed dim {d_pad}")
    queries = _scan_queries(queries, database)
    bm = _effective_block_m(m, block_m, queries.dtype)
    m_pad = round_up(m, bm)
    q = jnp.pad(queries, ((0, m_pad - m), (0, d_pad - d)))
    vals, idxs = partial_reduce_pallas(
        q, database, bias, scale,
        bin_size=bin_size, block_m=bm, block_n=block_n,
        interpret=interpret, int4_packed=int4_packed,
    )
    return vals[:m], idxs[:m]


def partial_reduce_fused(
    queries: jnp.ndarray,
    database: jnp.ndarray,
    bias: jnp.ndarray,
    scale: jnp.ndarray = None,
    *,
    k_scan: int,
    bin_size: int,
    block_m: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
    int4_packed: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-pass scan→select over packed operands (Eq. 20 fused path).

    Same operand contract as :func:`partial_reduce_packed`, but selection
    is fused into the scan: every bin winner is kept in a VMEM buffer
    across the database stream, the top-``k_scan`` is selected from it
    once, and only that (m, k_scan) result touches HBM.  Returns (values,
    indices), values sorted descending per row; masked entries (fewer
    than k_scan live candidates) hold ``-inf`` values and the sentinel
    index ``-1``.
    """
    m, d = queries.shape
    d_pad = database.shape[1] * (2 if int4_packed else 1)
    if d > d_pad:
        raise ValueError(f"query dim {d} exceeds packed dim {d_pad}")
    queries = _scan_queries(queries, database)
    bm = _effective_block_m(m, block_m, queries.dtype)
    m_pad = round_up(m, bm)
    q = jnp.pad(queries, ((0, m_pad - m), (0, d_pad - d)))
    vals, idxs = partial_reduce_fused_pallas(
        q, database, bias, scale,
        k_scan=k_scan, bin_size=bin_size, block_m=bm, block_n=block_n,
        interpret=interpret, int4_packed=int4_packed,
    )
    return vals[:m], idxs[:m]


def _scan_queries(queries: jnp.ndarray, database: jnp.ndarray) -> jnp.ndarray:
    """The queries as the scan dot takes them.

    bf16 rows meet bf16 queries: the (M, D) block is rounded once, outside
    the grid loop, so each tile goes to the MXU as stored (one bf16 pass,
    f32 accumulation) with no per-tile cast.  Every other tier's queries
    are returned as given.
    """
    if database.dtype == jnp.bfloat16:
        return queries.astype(jnp.bfloat16)
    return queries


def scan_operands(q_dtype, db_dtype, int4_packed: bool = False) -> str:
    """The form the scan dot's operands take, for queries of ``q_dtype``
    against stored rows of ``db_dtype``: ``"f32_highest"`` (f32 x f32 at
    full precision), ``"bf16"`` (bf16 x bf16), or ``"int8_upcast"`` /
    ``"int4_upcast"`` (integer codes cast to the query dtype in VMEM).

    >>> import jax.numpy as jnp
    >>> scan_operands(jnp.float32, jnp.float32), scan_operands(
    ...     jnp.float32, jnp.bfloat16)
    ('f32_highest', 'bf16')
    >>> scan_operands(jnp.float32, jnp.int8, int4_packed=True)
    'int4_upcast'
    """
    if int4_packed:
        return "int4_upcast"
    if jnp.dtype(db_dtype) == jnp.int8:
        return "int8_upcast"
    if jnp.bfloat16 in (jnp.dtype(q_dtype), jnp.dtype(db_dtype)):
        return "bf16"
    return "f32_highest"


def _load_db_tile(x_ref, q_dtype, int4_packed: bool):
    """VMEM view of one database tile in the query dtype.

    f32 and bf16 tiles go to the MXU as stored (bf16 rows meet queries
    rounded by :func:`_scan_queries`).  int8 codes are cast to the query
    dtype here; their per-row scales apply to the scores.  For the packed
    int4 tier the HBM stream carried two two's-complement nibbles per
    byte; unpack them here (arithmetic shifts sign-extend) so only the
    halved byte count ever crossed the memory wall.  Byte j holds logical
    column 2j in its low nibble and 2j+1 in its high nibble — matching
    ``quant.pack_int4_rows``.  The tile is unpacked as ``[all low nibbles |
    all high nibbles]`` (two lane-aligned halves, no interleave) and the
    query columns are permuted to match outside the kernel
    (:func:`_int4_query_order`), so the dot product is unchanged.
    """
    x = x_ref[...]
    if int4_packed:
        xb = x.astype(jnp.int32)
        lo = (xb << 28) >> 28
        hi = xb >> 4
        x = jnp.concatenate([lo, hi], axis=1)
    if x.dtype != q_dtype:
        x = x.astype(q_dtype)
    return x


def _int4_query_order(queries: jnp.ndarray) -> jnp.ndarray:
    """Query columns reordered ``[even | odd]`` to match the unpacked
    int4 tile of :func:`_load_db_tile` (O(M·D), outside the kernel)."""
    return jnp.concatenate([queries[:, 0::2], queries[:, 1::2]], axis=1)


def _tile_winners(q_ref, x_ref, scale_ref, bias_ref,
                  *, block_n: int, bin_size: int, int4_packed: bool):
    """One grid step's bin-wise top-1: (values, global indices)."""
    block_m = q_ref.shape[0]
    bins_per_block = block_n // bin_size
    j = pl.program_id(1)

    q = q_ref[...]
    x = _load_db_tile(x_ref, q.dtype, int4_packed)
    # MXU: one (block_m, d) x (d, block_n) matmul, f32 accumulation.  The
    # f32 tier's scores are its results, so its dot runs at full f32
    # precision.  A quantized tier's scan only ranks candidates for the
    # exact rescore: bf16 x bf16 is one MXU pass as given, and the int
    # tiers' upcast tiles keep the default precision.
    exact = x_ref.dtype == jnp.float32 and q.dtype == jnp.float32
    scores = jax.lax.dot_general(
        q,
        x,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32,
    )
    if scale_ref is not None:
        scores = scores * scale_ref[...]  # per-row dequant scale
    scores = scores + bias_ref[...]  # fused mask / halved-norm (1 COP)

    # Bin-wise top-1: reshape puts each bin in the minor (lane) dimension.
    binned = scores.reshape(block_m, bins_per_block, bin_size)
    vmax = jnp.max(binned, axis=-1)                        # COP 1: running max
    lane = jax.lax.broadcasted_iota(jnp.int32, binned.shape, 2)
    hit = jnp.where(binned == vmax[..., None], lane, bin_size)  # COP 2: cmp+sel
    amax = jnp.min(hit, axis=-1)                           # COP 3: index min

    base = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, bins_per_block), 1
    ) * bin_size
    return vmax, base + amax


def _reduce_tile(q_ref, x_ref, scale_ref, bias_ref, v_ref, a_ref,
                 *, block_n: int, bin_size: int, int4_packed: bool = False):
    vmax, idx = _tile_winners(
        q_ref, x_ref, scale_ref, bias_ref,
        block_n=block_n, bin_size=bin_size, int4_packed=int4_packed,
    )
    v_ref[...] = vmax
    a_ref[...] = idx


def _spread(x, offset):
    """A (rows, w <= 128) slice of bin winners as a full 128-lane row.

    Column b lands on lane ``(offset + b) % 128``; the lanes no column
    lands on hold junk that the caller's lane mask never selects.  One
    column is a lane broadcast; wider slices are zero-filled to 128 lanes
    and rotated by the (traced) offset.
    """
    rows, w = x.shape
    if w == 1:
        return jnp.broadcast_to(x, (rows, _LANES))
    if w < _LANES:
        x = jnp.concatenate([x, jnp.zeros((rows, _LANES - w), x.dtype)], 1)
    return pltpu.roll(x, offset, 1)


def _record_winners(wv_ref, wi_ref, vmax, idx, first_bin):
    """Store one tile's bin winners at their global bin positions.

    The winner buffer is (chunks, block_m, 128): global bin p lives in
    chunk ``p // 128``, lane ``p % 128``.  Each 128-lane piece of the
    tile's winners costs one load, select and store of a chunk row —
    no cross-lane reduction, no serial chain.  A piece whose lanes run
    past lane 127 (bins_per_block neither divides nor is a multiple of
    128) wraps its tail onto the head of the next chunk.
    """
    bpb = vmax.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    # A piece can straddle two chunks unless every piece starts at a
    # multiple of its width.
    wraps = _LANES % bpb != 0 and bpb % _LANES != 0
    for t in range(0, bpb, _LANES):
        w = min(_LANES, bpb - t)
        pos = first_bin + t
        chunk, off = pos // _LANES, pos % _LANES
        v = _spread(vmax[:, t:t + w], off)
        i = _spread(idx[:, t:t + w], off)
        here = (lane >= off) & (lane < off + w)
        wv_ref[chunk] = jnp.where(here, v, wv_ref[chunk])
        wi_ref[chunk] = jnp.where(here, i, wi_ref[chunk])
        if wraps:
            @pl.when(off + w > _LANES)
            def _wrap(chunk=chunk, off=off, v=v, i=i, w=w):
                tail = lane < off + w - _LANES
                wv_ref[chunk + 1] = jnp.where(tail, v, wv_ref[chunk + 1])
                wi_ref[chunk + 1] = jnp.where(tail, i, wi_ref[chunk + 1])


def _select_topk(wv_ref, wi_ref, v_ref, a_ref, k_scan: int):
    """Write the top-``k_scan`` of every bin winner in the buffer, best
    first, into the (block_m, k_scan) outputs.

    Iterative max extraction, once per query block, ``_SELECT_ROWS`` query
    rows at a time, so a group's (chunks, rows, 128) slice of the buffer
    stays in registers.  Ties resolve to the lowest global id,
    which is the lowest bin position (bin p's winner is a row of
    [p·bin_size, (p+1)·bin_size)): the order ``lax.top_k`` over the
    two-pass winner row gives.  Masked winners and the buffer's unused
    lanes all hold (_MASK, -1), so their order cannot show.  An extracted
    lane retires its value AND its id — retiring only the value would let
    it win a later _MASK tie with a stale live id (the phantom-duplicate
    bug, in VMEM).  No ``lax.top_k``/gather inside the kernel: Mosaic only
    needs max/min reductions, compares and selects.
    """
    chunks, block_m, _ = wv_ref.shape
    rows = math.gcd(block_m, _SELECT_ROWS)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, k_scan), 1)
    no_id = jnp.iinfo(jnp.int32).max

    def extract(t, carry):
        v, i, out_v, out_i = carry
        best = jnp.max(jnp.max(v, axis=0), axis=1, keepdims=True)
        hit = v == best[None]
        first = jnp.min(jnp.min(jnp.where(hit, i, no_id), axis=0),
                        axis=1, keepdims=True)
        sel = hit & (i == first[None])
        return (jnp.where(sel, _MASK, v), jnp.where(sel, -1, i),
                jnp.where(col == t, best, out_v),
                jnp.where(col == t, first, out_i))

    def group(g, state):
        r = pl.multiple_of(g * rows, rows)
        carry = (wv_ref[:, pl.ds(r, rows), :], wi_ref[:, pl.ds(r, rows), :],
                 jnp.full((rows, k_scan), _MASK, jnp.float32),
                 jnp.full((rows, k_scan), -1, jnp.int32))
        _, _, out_v, out_i = jax.lax.fori_loop(0, k_scan, extract, carry)
        v_ref[pl.ds(r, rows), :] = out_v
        a_ref[pl.ds(r, rows), :] = out_i
        return state

    jax.lax.fori_loop(0, block_m // rows, group, 0)


def _fused_tile(q_ref, x_ref, scale_ref, bias_ref, v_ref, a_ref,
                wv_ref, wi_ref,
                *, block_n: int, bin_size: int, k_scan: int,
                int4_packed: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # Fresh buffer per query block: lanes past the last bin stay
        # (-inf, sentinel) for the whole stream.
        wv_ref[...] = jnp.full(wv_ref.shape, _MASK, jnp.float32)
        wi_ref[...] = jnp.full(wi_ref.shape, -1, jnp.int32)

    vmax, idx = _tile_winners(
        q_ref, x_ref, scale_ref, bias_ref,
        block_n=block_n, bin_size=bin_size, int4_packed=int4_packed,
    )
    # A fully-masked bin's winner is meaningless — pair its -inf value
    # with the sentinel index in-kernel so it can never alias a live row.
    idx = jnp.where(vmax > _MASK * 0.5, idx, -1)
    _record_winners(wv_ref, wi_ref, vmax, idx, j * vmax.shape[1])

    @pl.when(j == pl.num_programs(1) - 1)
    def _select():
        _select_topk(wv_ref, wi_ref, v_ref, a_ref, k_scan)


def _partial_reduce_kernel(
    q_ref,      # (block_m, d)      VMEM
    x_ref,      # (block_n, d)      VMEM
    bias_ref,   # (1, block_n)      VMEM: -inf mask and/or -||x||^2/2
    v_ref,      # (block_m, bins_per_block) VMEM out
    a_ref,      # (block_m, bins_per_block) VMEM out
    *,
    block_n: int,
    bin_size: int,
    int4_packed: bool,
):
    _reduce_tile(q_ref, x_ref, None, bias_ref, v_ref, a_ref,
                 block_n=block_n, bin_size=bin_size, int4_packed=int4_packed)


def _partial_reduce_kernel_scaled(
    q_ref,      # (block_m, d)      VMEM
    x_ref,      # (block_n, d) VMEM int8 (or packed int4 nibbles)
    scale_ref,  # (1, block_n)      VMEM f32 per-row scale
    bias_ref,   # (1, block_n)      VMEM
    v_ref,
    a_ref,
    *,
    block_n: int,
    bin_size: int,
    int4_packed: bool,
):
    _reduce_tile(q_ref, x_ref, scale_ref, bias_ref, v_ref, a_ref,
                 block_n=block_n, bin_size=bin_size, int4_packed=int4_packed)


def _fused_kernel(q_ref, x_ref, bias_ref, v_ref, a_ref, wv_ref, wi_ref,
                  *, block_n, bin_size, k_scan, int4_packed):
    _fused_tile(q_ref, x_ref, None, bias_ref, v_ref, a_ref, wv_ref, wi_ref,
                block_n=block_n, bin_size=bin_size, k_scan=k_scan,
                int4_packed=int4_packed)


def _fused_kernel_scaled(q_ref, x_ref, scale_ref, bias_ref, v_ref, a_ref,
                         wv_ref, wi_ref,
                         *, block_n, bin_size, k_scan, int4_packed):
    _fused_tile(q_ref, x_ref, scale_ref, bias_ref, v_ref, a_ref,
                wv_ref, wi_ref,
                block_n=block_n, bin_size=bin_size, k_scan=k_scan,
                int4_packed=int4_packed)


def _validate_tiling(queries, database, *, block_m, block_n, bin_size,
                     int4_packed):
    m, d = queries.shape
    n, w = database.shape
    d_db = 2 * w if int4_packed else w
    if d != d_db:
        raise ValueError(f"dim mismatch: {d} vs {d_db}")
    if d % 128 or m % block_m or n % block_n or block_n % bin_size:
        raise ValueError(
            f"tiling contract violated: m={m} d={d} n={n} "
            f"block_m={block_m} block_n={block_n} bin_size={bin_size}"
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "bin_size", "block_m", "block_n", "interpret", "int4_packed",
    ),
)
def partial_reduce_pallas(
    queries: jnp.ndarray,   # (m, d)  m % block_m == 0, d % 128 == 0
    database: jnp.ndarray,  # (n, d)  n % block_n == 0
    bias: jnp.ndarray,      # (1, n)  f32
    scale: jnp.ndarray = None,  # (1, n) f32 per-row scale, or None
    *,
    bin_size: int,
    block_m: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
    int4_packed: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused score+reduce. Returns (values, indices), both (m, n // bin_size).

    Shapes must already satisfy the tiling contract — use
    ``repro.kernels.ops`` for the padding/planning front-end.  ``database``
    may be a reduced-precision storage tier (bf16/int8/int4; bf16 rows are
    scanned against queries rounded to bf16); ``scale`` is the scaled
    tiers' per-row dequantization scale, applied to the score tile in
    VMEM.

    The kernel writes its bin winners as (n // block_n, m, bins_per_block)
    — one (block_m, bins_per_block) block per grid step, whose last dim is
    the array's full last dim, as the TPU block rule requires however few
    bins a tile holds — and the (m, num_bins) row-major result is
    assembled outside the kernel.
    """
    _validate_tiling(queries, database, block_m=block_m, block_n=block_n,
                     bin_size=bin_size, int4_packed=int4_packed)
    queries = _scan_queries(queries, database)
    if int4_packed:
        queries = _int4_query_order(queries)
    m, d = queries.shape
    n, w = database.shape
    num_blocks = n // block_n
    bins_per_block = block_n // bin_size
    grid = (m // block_m, num_blocks)

    in_specs = [
        pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, w), lambda i, j: (j, 0)),
        pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
    ]
    kw = dict(block_n=block_n, bin_size=bin_size, int4_packed=int4_packed)
    if scale is None:
        kernel = functools.partial(_partial_reduce_kernel, **kw)
        operands = (queries, database, bias)
    else:
        kernel = functools.partial(_partial_reduce_kernel_scaled, **kw)
        # scale rides the same (1, block_n) tiling as the bias row.
        in_specs.insert(2, pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
        operands = (queries, database, scale, bias)
    out_block = pl.BlockSpec((pl.Squeezed(), block_m, bins_per_block),
                             lambda i, j: (j, i, 0))
    vals, idxs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((num_blocks, m, bins_per_block),
                                 jnp.float32),
            jax.ShapeDtypeStruct((num_blocks, m, bins_per_block),
                                 jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return (
        vals.transpose(1, 0, 2).reshape(m, num_blocks * bins_per_block),
        idxs.transpose(1, 0, 2).reshape(m, num_blocks * bins_per_block),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_scan", "bin_size", "block_m", "block_n", "interpret",
        "int4_packed",
    ),
)
def partial_reduce_fused_pallas(
    queries: jnp.ndarray,   # (m, d)  m % block_m == 0, d % 128 == 0
    database: jnp.ndarray,  # (n, d)  n % block_n == 0
    bias: jnp.ndarray,      # (1, n)  f32
    scale: jnp.ndarray = None,  # (1, n) f32 per-row scale, or None
    *,
    k_scan: int,
    bin_size: int,
    block_m: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
    int4_packed: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-pass scan→select: (values, indices), both (m, k_scan).

    The winner buffer lives in VMEM scratch across the sequential j-loop
    (TPU grids iterate the last axis innermost) and is reduced to the
    top-k_scan once, at the query block's last database tile, so per
    search the only HBM traffic is the query block, the database stream
    and the final (m, k_scan) result — the paper's Eq. 20 contract.
    Values come out sorted descending; masked entries hold (-inf, -1).
    """
    _validate_tiling(queries, database, block_m=block_m, block_n=block_n,
                     bin_size=bin_size, int4_packed=int4_packed)
    if k_scan <= 0:
        raise ValueError(f"k_scan must be positive, got {k_scan}")
    queries = _scan_queries(queries, database)
    if int4_packed:
        queries = _int4_query_order(queries)
    m, d = queries.shape
    n, w = database.shape
    grid = (m // block_m, n // block_n)
    buffer = (_winner_chunks(n, bin_size), block_m, _LANES)

    in_specs = [
        pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, w), lambda i, j: (j, 0)),
        pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
    ]
    kw = dict(block_n=block_n, bin_size=bin_size, k_scan=k_scan,
              int4_packed=int4_packed)
    if scale is None:
        kernel = functools.partial(_fused_kernel, **kw)
        operands = (queries, database, bias)
    else:
        kernel = functools.partial(_fused_kernel_scaled, **kw)
        in_specs.insert(2, pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
        operands = (queries, database, scale, bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_m, k_scan), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k_scan), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k_scan), jnp.float32),
            jax.ShapeDtypeStruct((m, k_scan), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM(buffer, jnp.float32),
            pltpu.VMEM(buffer, jnp.int32),
        ],
        interpret=interpret,
    )(*operands)

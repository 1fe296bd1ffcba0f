"""Pallas PartialReduce kernel vs pure-jnp oracle: shape/dtype sweeps in
interpret mode (the brief's per-kernel validation contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.knn import exact_mips
from repro.kernels.ops import l2_topk, mips_topk
from repro.kernels.partial_reduce import (
    partial_reduce_fused,
    partial_reduce_packed,
    partial_reduce_pallas,
)
from repro.kernels.ref import partial_reduce_ref


def _recall(approx_idx, exact_idx):
    r = []
    for a, e in zip(np.asarray(approx_idx), np.asarray(exact_idx)):
        r.append(len(set(a.tolist()) & set(e.tolist())) / len(e))
    return float(np.mean(r))


@pytest.mark.parametrize("m,n,d,bin_size,block_m,block_n", [
    (256, 2048, 128, 64, 256, 512),
    (256, 2048, 128, 256, 128, 1024),
    (512, 4096, 256, 128, 256, 1024),
    (256, 1024, 128, 1024, 256, 1024),   # one bin per block
    (256, 2048, 384, 32, 256, 512),      # d > 128 multiple
])
def test_kernel_matches_ref_shapes(m, n, d, bin_size, block_m, block_n):
    key = jax.random.PRNGKey(m + n + d)
    q = jax.random.normal(key, (m, d), jnp.float32)
    db = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.float32)
    bias = jnp.zeros((1, n), jnp.float32)
    kv, ki = partial_reduce_pallas(
        q, db, bias, bin_size=bin_size, block_m=block_m, block_n=block_n,
        interpret=True,
    )
    rv, ri = partial_reduce_ref(q, db, bias, bin_size=bin_size)
    np.testing.assert_allclose(np.asarray(kv), np.asarray(rv), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))


_MASK = float(jnp.finfo(jnp.float32).min)


def _two_pass_top_k(q, db, bias, k, **tiles):
    """The oracle: every bin winner from the two-pass kernel, masked ones
    paired with the sentinel id, then ``lax.top_k`` over the winner row
    (padded with sentinels when k exceeds the bins)."""
    v, i = partial_reduce_pallas(q, db, bias, interpret=True, **tiles)
    i = jnp.where(v > _MASK * 0.5, i, -1)
    short = max(0, k - v.shape[1])
    v = jnp.pad(v, ((0, 0), (0, short)), constant_values=_MASK)
    i = jnp.pad(i, ((0, 0), (0, short)), constant_values=-1)
    top_v, pos = jax.lax.top_k(v, k)
    return top_v, jnp.take_along_axis(i, pos, axis=1)


# (m, n, bin_size, block_m, block_n, k): n // bin_size bins, block_n //
# bin_size of them per tile.
_FUSED_CASES = {
    # one bin a tile, 300 bins: three 128-lane chunks, the last partial
    "one_bin_per_tile": (16, 2400, 8, 8, 8, 7),
    # 3 bins a tile: pieces straddle chunk boundaries (the roll + wrap)
    "three_bins_per_tile": (16, 2400, 16, 8, 48, 6),
    # 64 bins a tile, 320 bins; two 32-row select groups
    "sixty_four_bins_per_tile": (64, 5120, 4, 64, 256, 9),
    # 192 bins a tile: a full 128-lane piece and a 64-lane piece
    "one_hundred_ninety_two_bins_per_tile": (8, 1152, 2, 8, 384, 10),
    # 256 bins a tile: two aligned 128-lane pieces, no roll
    "two_hundred_fifty_six_bins_per_tile": (8, 768, 1, 8, 256, 4),
    # a single query row: the kernel clamps block_m to 8 (the served case)
    "one_row": (1, 2400, 8, 256, 8, 10),
    # 128 bins, no spare lane, and k past them: every lane is extracted
    "more_k_than_bins": (8, 1024, 8, 8, 8, 130),
}


@pytest.mark.parametrize("case", sorted(_FUSED_CASES))
@pytest.mark.parametrize("layout", ["masked", "ties", "sparse", "dead"])
def test_fused_select_matches_two_pass_top_k(case, layout):
    """The fused kernel's deferred select is bit-identical to the two-pass
    kernel plus ``lax.top_k`` over all bin winners, in every bins-per-tile
    class.  ``masked``: an all-tombstoned tile and a masked tail.  ``ties``:
    every tile a copy of the first, so each bin winner ties with one in
    every other tile and the lowest id must win.  ``sparse``: fewer live
    bins than k, so most outputs are (-inf, -1) sentinels; ``dead``: every
    row deleted, so all of them are."""
    m, n, bin_size, block_m, block_n, k = _FUSED_CASES[case]
    d = 128
    rng = np.random.default_rng(n + bin_size + block_n)
    q = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    db = rng.standard_normal((n, d)).astype(np.float32)
    bias = np.zeros((1, n), np.float32)
    if layout == "masked":
        bias[0, block_n:2 * block_n] = _MASK      # a tombstoned tile
        bias[0, n - block_n // 2 - 1:] = _MASK    # the masked tail
    elif layout == "ties":
        db = np.tile(db[:block_n], (n // block_n, 1))
    else:
        bias[0, (k // 2 if layout == "sparse" else 0) * bin_size:] = _MASK
    tiles = dict(bin_size=bin_size, block_m=block_m, block_n=block_n)
    got_v, got_i = partial_reduce_fused(
        q, jnp.asarray(db), jnp.asarray(bias), k_scan=k, interpret=True,
        **tiles,
    )
    # The oracle runs the query block the fused wrapper runs (padded to
    # its clamped block_m), at that block_m.
    bm = min(block_m, 8 * -(-m // 8))
    q_pad = jnp.pad(q, ((0, bm * -(-m // bm) - m), (0, 0)))
    want_v, want_i = _two_pass_top_k(
        q_pad, jnp.asarray(db), jnp.asarray(bias), k,
        **dict(tiles, block_m=bm),
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v[:m]))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i[:m]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (256, 128)).astype(dtype)
    db = jax.random.normal(jax.random.PRNGKey(1), (1024, 128)).astype(dtype)
    bias = jnp.zeros((1, 1024), jnp.float32)
    kv, ki = partial_reduce_pallas(
        q, db, bias, bin_size=64, block_m=256, block_n=512, interpret=True
    )
    rv, ri = partial_reduce_ref(q, db, bias, bin_size=64)
    np.testing.assert_allclose(np.asarray(kv), np.asarray(rv), atol=1e-2)
    # bf16 rounding can flip near-ties; require near-total index agreement
    agree = (np.asarray(ki) == np.asarray(ri)).mean()
    assert agree > 0.995


def test_kernel_bias_fuses_l2(data=None):
    """bias = -||x||^2/2 turns the kernel into Eq. 19 L2 search."""
    q = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    db = jax.random.normal(jax.random.PRNGKey(1), (1500, 32))
    v, idx = l2_topk(q, db, 10, 0.98, interpret=True)
    d = np.linalg.norm(np.asarray(q)[:, None] - np.asarray(db)[None], axis=-1)
    exact = np.argsort(d, axis=-1)[:, :10]
    assert _recall(idx, exact) >= 0.9
    # returned values are the relaxed distances, monotone with true d
    order = np.argsort(np.asarray(v), axis=-1)
    np.testing.assert_array_equal(order, np.tile(np.arange(10), (64, 1)))


def test_fused_mips_end_to_end_unaligned():
    """Non-pow2 N, non-128 D: padding + masking path (Appendix A.5)."""
    q = jax.random.normal(jax.random.PRNGKey(2), (100, 100))
    db = jax.random.normal(jax.random.PRNGKey(3), (5001, 100))
    v, idx = mips_topk(q, db, 10, 0.95, interpret=True)
    _, exact = exact_mips(q, db, 10)
    assert _recall(idx, exact) >= 0.9
    assert int(np.asarray(idx).max()) < 5001  # no padded index leaks


def test_fused_mips_matches_unfused_recall():
    from repro.core.knn import mips as jnp_mips

    q = jax.random.normal(jax.random.PRNGKey(4), (64, 64))
    db = jax.random.normal(jax.random.PRNGKey(5), (4096, 64))
    _, i_kernel = mips_topk(q, db, 10, 0.95, interpret=True)
    _, i_jnp = jnp_mips(q, db, 10, recall_target=0.95)
    _, exact = exact_mips(q, db, 10)
    # same binning plan => identical recall characteristics
    assert abs(_recall(i_kernel, exact) - _recall(i_jnp, exact)) < 0.05


def test_kernel_serves_knn_attention_selection():
    """The fused PartialReduce kernel IS the decode-attention selector:
    scoring q against the KV cache is MIPS with keys as the database, so the
    same kernel drives both the KNN search API and the serving path."""
    import jax.numpy as jnp

    from repro.core.topk import approx_max_k

    b, h, s, hd = 2, 4, 2048, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, hd))
    keys = jax.random.normal(jax.random.PRNGKey(1), (s, hd))
    # jnp path used inside knn_decode_attention:
    scores = jnp.einsum("bhd,kd->bhk", q, keys)
    _, idx_jnp = approx_max_k(scores, 32, recall_target=0.95)
    # fused kernel path: queries are the (B*H) flattened heads.
    _, idx_kernel = mips_topk(
        q.reshape(b * h, hd), keys, 32, 0.95, interpret=True
    )
    agree = (np.asarray(idx_jnp).reshape(b * h, 32) ==
             np.asarray(idx_kernel)).mean()
    assert agree > 0.95  # same plan; near-ties may differ in f32 vs kernel


def _tier_operands(storage, n, d):
    """(f32 queries, stored rows, per-row scale or None, the stored rows'
    canonical codes or values) for one storage tier, seeded."""
    from repro.search import quant

    q = jax.random.normal(jax.random.PRNGKey(7), (32, d), jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(8), (n, d), jnp.float32)
    stored, scale = quant.quantize_rows(rows, storage)
    canonical = stored
    if storage == "int4":
        stored = quant.pack_int4_rows(stored)
    return q, stored, None if scale is None else scale[None, :], canonical


def _plain_scan_scores(q, canonical, scale, bias, storage):
    """The scan's scores in plain jnp, in the operand form each tier's dot
    is given: f32 x f32 at full precision; bf16 x bf16 with f32
    accumulation; integer codes cast to f32 at the default precision
    (int4 with its columns in the kernel's [even | odd] order)."""
    if storage == "f32":
        s = jnp.einsum("ik,jk->ij", q, canonical,
                       precision=jax.lax.Precision.HIGHEST)
    elif storage == "bf16":
        s = jnp.einsum("ik,jk->ij", q.astype(jnp.bfloat16), canonical,
                       preferred_element_type=jnp.float32)
    else:
        x = canonical.astype(jnp.float32)
        if storage == "int4":
            q = jnp.concatenate([q[:, 0::2], q[:, 1::2]], axis=1)
            x = jnp.concatenate([x[:, 0::2], x[:, 1::2]], axis=1)
        s = jnp.einsum("ik,jk->ij", q, x)
    if scale is not None:
        s = s * scale
    return s + bias


@pytest.mark.parametrize("storage", ["f32", "bf16", "int8", "int4"])
def test_each_tier_scans_with_its_stated_operands(storage):
    """Each tier's bin winners are, bit for bit, those of a plain jnp dot
    of its stated operand form: the bf16 tier a bf16 x bf16 -> f32 dot of
    the queries rounded to bf16 and the rows as stored; the f32, int8 and
    int4 tiers the operands and precision they always had."""
    n, d, bin_size = 2048, 256, 64
    q, stored, scale, canonical = _tier_operands(storage, n, d)
    bias = -0.5 * jax.random.uniform(jax.random.PRNGKey(9), (1, n))
    got_v, got_i = partial_reduce_packed(
        q, stored, bias, scale, bin_size=bin_size, block_m=16, block_n=512,
        interpret=True, int4_packed=storage == "int4",
    )
    # Scored tile by tile at the kernel's tile shapes, each tile in one
    # program as the kernel's body is: the CPU's dot sums a row in an
    # order that depends on the operand shapes, and a program may fuse
    # the scale and the bias into one multiply-add.
    scores = jnp.concatenate([
        jnp.concatenate([
            jax.jit(_plain_scan_scores, static_argnums=4)(
                q[i:i + 16], canonical[j:j + 512],
                None if scale is None else scale[:, j:j + 512],
                bias[:, j:j + 512], storage)
            for j in range(0, n, 512)], axis=1)
        for i in range(0, q.shape[0], 16)])
    binned = scores.reshape(q.shape[0], n // bin_size, bin_size)
    want_i = (jnp.argmax(binned, axis=-1)
              + jnp.arange(n // bin_size) * bin_size)
    np.testing.assert_array_equal(np.asarray(got_v),
                                  np.asarray(jnp.max(binned, axis=-1)))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))

"""Attention variants: GQA/MQA/MHA, local-window, MLA (deepseek-v2),
cross-attention, and the paper-integrated KNN top-k decode attention.

Training/prefill use a query-chunked exact attention (scan over query blocks)
so the (S, S) score matrix never materialises — the same "never write O(MN)
bytes" principle the paper applies to KNN scoring.

``knn_decode_attention`` treats the KV cache as the paper's database: scores
are one MXU matmul, PartialReduce selects the top-k keys (Eq. 13 recall
guarantee), and exact softmax runs over the k survivors.  This is Listing 1
with keys as the database, and is our sub-quadratic long-context path.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.topk import approx_max_k
from repro.models.params import ParamDef
from repro.models.rope import apply_mrope, apply_rope
from repro.parallel.sharding import shard

__all__ = [
    "attn_defs",
    "mla_defs",
    "cross_attn_defs",
    "attention_train",
    "attention_decode",
    "mla_train",
    "mla_decode",
    "cross_attention",
    "knn_decode_attention",
    "KVCache",
    "MLACache",
]

_NEG_INF = -1e30  # finite mask value: avoids NaN from (-inf) - (-inf)


class KVCache(NamedTuple):
    k: jnp.ndarray      # (B, S, KV, hd)
    v: jnp.ndarray      # (B, S, KV, hd)


class MLACache(NamedTuple):
    c_kv: jnp.ndarray   # (B, S, kv_lora)
    k_rope: jnp.ndarray  # (B, S, qk_rope)


# --------------------------------------------------------------------------
# Parameter definitions
# --------------------------------------------------------------------------


def attn_defs(d_model: int, num_heads: int, num_kv_heads: int, head_dim: int):
    return {
        "wq": ParamDef((d_model, num_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((num_heads, head_dim, d_model), ("heads", "head_dim", "embed")),
    }


def mla_defs(
    d_model: int,
    num_heads: int,
    *,
    q_lora_rank: int,
    kv_lora_rank: int,
    qk_nope_dim: int = 128,
    qk_rope_dim: int = 64,
    v_head_dim: int = 128,
):
    defs = {
        "wkv_a": ParamDef((d_model, kv_lora_rank + qk_rope_dim), ("embed", "kv_lora")),
        "kv_norm": ParamDef((kv_lora_rank,), ("kv_lora",), "ones"),
        "wk_b": ParamDef((kv_lora_rank, num_heads, qk_nope_dim), ("kv_lora", "heads", "head_dim")),
        "wv_b": ParamDef((kv_lora_rank, num_heads, v_head_dim), ("kv_lora", "heads", "head_dim")),
        "wo": ParamDef((num_heads, v_head_dim, d_model), ("heads", "head_dim", "embed")),
    }
    if q_lora_rank:
        defs["wq_a"] = ParamDef((d_model, q_lora_rank), ("embed", None))
        defs["q_norm"] = ParamDef((q_lora_rank,), (None,), "ones")
        defs["wq_b"] = ParamDef(
            (q_lora_rank, num_heads, qk_nope_dim + qk_rope_dim),
            (None, "heads", "head_dim"),
        )
    else:
        defs["wq"] = ParamDef(
            (d_model, num_heads, qk_nope_dim + qk_rope_dim),
            ("embed", "heads", "head_dim"),
        )
    return defs


def cross_attn_defs(d_model: int, num_heads: int, head_dim: int):
    return attn_defs(d_model, num_heads, num_heads, head_dim)


# --------------------------------------------------------------------------
# Core attend helpers
# --------------------------------------------------------------------------


def _repeat_kv(x: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*groups, hd) by repetition (GQA)."""
    if groups == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, kv, groups, hd)
    ).reshape(b, s, kv * groups, hd)


def _attend_chunked(
    q: jnp.ndarray,              # (B, Sq, H, hd)
    k: jnp.ndarray,              # (B, Skv, H, hd)  (already GQA-expanded)
    v: jnp.ndarray,              # (B, Skv, H, hd)
    q_positions: jnp.ndarray,    # (Sq,)
    kv_positions: jnp.ndarray,   # (Skv,)
    *,
    causal: bool,
    window: Optional[int],
    chunk: int = 512,
) -> jnp.ndarray:
    """Exact attention, scanned over query chunks (scores stay O(chunk*Skv))."""
    b, sq, h, hd = q.shape
    vd = v.shape[-1]  # value head dim may differ from qk dim (MLA)
    scale = hd ** -0.5
    if sq % chunk:
        # Largest power-of-two divisor of sq not exceeding the request;
        # degenerate seqs fall back to a single block.
        c = 1
        while c * 2 <= chunk and sq % (c * 2) == 0:
            c *= 2
        chunk = c if c >= 16 else sq
    if sq <= chunk:
        return _attend_block(q, k, v, q_positions, kv_positions, scale, causal, window)
    n_chunks = sq // chunk
    qs = q.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pos = q_positions.reshape(n_chunks, chunk)

    def body(_, qp):
        qc, pc = qp
        return None, _attend_block(qc, k, v, pc, kv_positions, scale, causal, window)

    _, out = jax.lax.scan(body, None, (qs, pos))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, vd)


_SCORES_DTYPE = jnp.float32  # set via set_scores_dtype (hillclimb cell B)


def set_scores_dtype(dtype):
    """Storage dtype for attention score/exp tiles.

    bf16 tiles halve the O(S_q x S_kv) HBM traffic of unfused attention
    (reductions still accumulate in f32) — the paper's "don't write O(MN)
    bytes" pressure applied to the training attention path.  See
    EXPERIMENTS.md §Perf cell B.
    """
    global _SCORES_DTYPE
    _SCORES_DTYPE = dtype


def _attend_block(q, k, v, q_pos, kv_pos, scale, causal, window):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = shard(scores, "batch", "heads", None, None)
    mask = jnp.ones((q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    if _SCORES_DTYPE == jnp.bfloat16:
        s16 = scores.astype(jnp.bfloat16)
        m = jnp.max(s16, axis=-1, keepdims=True)
        e = jnp.exp((s16 - m).astype(jnp.float32)).astype(jnp.bfloat16)
        denom = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
        probs = (e / denom.astype(jnp.bfloat16)).astype(q.dtype)
    else:
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------------
# Standard (GQA) attention
# --------------------------------------------------------------------------


def _qkv(params, x, positions, *, rope_theta, mrope, mrope_positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    if mrope:
        pos3 = (
            mrope_positions
            if mrope_positions is not None
            else jnp.stack([positions] * 3, axis=0)
        )
        q = apply_mrope(q, pos3, theta=rope_theta)
        k = apply_mrope(k, pos3, theta=rope_theta)
    elif rope_theta:
        q = apply_rope(q, positions, theta=rope_theta)
        k = apply_rope(k, positions, theta=rope_theta)
    return q, k, v


def attention_train(
    params: Dict,
    x: jnp.ndarray,                 # (B, S, d)
    positions: jnp.ndarray,         # (S,)
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    causal: bool = True,
    window: Optional[int] = None,
    mrope: bool = False,
    mrope_positions: Optional[jnp.ndarray] = None,
    q_chunk: int = 512,
    return_cache: bool = False,
):
    """Full-sequence self attention (training / prefill)."""
    q, k, v = _qkv(
        params, x, positions,
        rope_theta=rope_theta, mrope=mrope, mrope_positions=mrope_positions,
    )
    groups = num_heads // num_kv_heads
    ke, ve = _repeat_kv(k, groups), _repeat_kv(v, groups)
    out = _attend_chunked(
        q, ke, ve, positions, positions, causal=causal, window=window, chunk=q_chunk
    )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if return_cache:
        return y, KVCache(k=k, v=v)
    return y


def attention_decode(
    params: Dict,
    x: jnp.ndarray,                 # (B, 1, d)
    cache: KVCache,
    cur_index: jnp.ndarray,         # scalar int32: position being generated
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    window: Optional[int] = None,
    mrope: bool = False,
    knn_k: int = 0,
    knn_recall_target: float = 0.95,
) -> Tuple[jnp.ndarray, KVCache]:
    """Single-token decode with KV cache update.

    With ``knn_k > 0`` key selection runs through the paper's PartialReduce
    (``knn_decode_attention``) instead of full softmax over S.
    """
    b, _, d = x.shape
    positions = jnp.full((1,), cur_index, jnp.int32)
    q, k_new, v_new = _qkv(
        params, x, positions, rope_theta=rope_theta, mrope=mrope, mrope_positions=None
    )
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, cur_index, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, cur_index, 0, 0))
    # Pin the cache layout after the in-place update: without this GSPMD may
    # re-shard (gather) the whole O(S) cache at the next consumer.
    k = shard(k, "batch", "cp_seq", None, None)
    v = shard(v, "batch", "cp_seq", None, None)
    new_cache = KVCache(k=k, v=v)
    groups = num_heads // num_kv_heads

    q1 = q[:, 0]                    # (B, H, hd)
    s = k.shape[1]
    kv_pos = jnp.arange(s, dtype=jnp.int32)
    valid = kv_pos <= cur_index
    if window is not None:
        valid &= cur_index - kv_pos < window
    if knn_k:
        # raw (unexpanded) cache: the GQA expansion happens group-wise inside
        # so the O(S) cache is never rematerialised at H width.
        out = knn_decode_attention(
            q1, k, v, valid, k=knn_k, recall_target=knn_recall_target,
            kv_groups=groups,
        )
    else:
        ke, ve = _repeat_kv(k, groups), _repeat_kv(v, groups)
        scores = jnp.einsum("bhd,bkhd->bhk", q1, ke) * (q1.shape[-1] ** -0.5)
        scores = jnp.where(valid[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q1.dtype)
        out = jnp.einsum("bhk,bkhd->bhd", probs, ve)
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]
    return y, new_cache


def knn_decode_attention(
    q: jnp.ndarray,        # (B, H, hd)
    keys: jnp.ndarray,     # (B, S, KV, hd)  raw (kv_groups expands to H)
    values: jnp.ndarray,   # (B, S, KV, hd)
    valid: jnp.ndarray,    # (S,) bool
    *,
    k: int,
    recall_target: float = 0.95,
    kv_groups: int = 1,
) -> jnp.ndarray:
    """Paper-technique attention over a KV cache.

    When the cache sequence is context-parallel (the "cp_seq" logical axis is
    mapped to mesh axes for this cell), this runs the paper's §7 distributed
    algorithm with shard_map: PartialReduce per shard (recall accounted
    against the global S), all-gather only the L bin winners *with their
    value vectors*, ExactRescore + softmax globally.  The wire cost is
    O(L x hd) per query instead of the O(S)-scores gather GSPMD would emit.
    """
    from repro.parallel.sharding import current_mesh, logical_to_spec

    mesh = current_mesh()
    cp = None
    if mesh is not None:
        spec = logical_to_spec(("cp_seq",))[0]
        if spec is not None:
            cp = spec if isinstance(spec, tuple) else (spec,)
    if cp:
        return _knn_decode_attention_cp(
            q, keys, values, valid, k=k, recall_target=recall_target,
            mesh=mesh, cp_axes=cp, kv_groups=kv_groups,
        )
    return _knn_decode_attention_local(
        q, _repeat_kv(keys, kv_groups), _repeat_kv(values, kv_groups), valid,
        k=k, recall_target=recall_target,
    )


def _knn_decode_attention_local(q, keys, values, valid, *, k, recall_target,
                                global_s: int = -1, index_offset=None):
    b, h, hd = q.shape
    scale = hd ** -0.5
    # MXU: all scores, one matmul (the paper's einsum).
    scores = jnp.einsum("bhd,bkhd->bhk", q, keys) * scale
    scores = jnp.where(valid[None, None], scores, _NEG_INF)
    # PartialReduce + rescoring: top-k keys with E[recall] per Eq. 13.
    top_scores, top_idx = approx_max_k(scores, k, recall_target=recall_target)
    # Exact softmax over the k survivors only.
    probs = jax.nn.softmax(top_scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    # Gather the selected values: (B, H, k, hd).
    v_bhsd = values.transpose(0, 2, 1, 3)  # (B, H, S, hd)
    sel = jnp.take_along_axis(v_bhsd, top_idx[..., None], axis=2)
    return jnp.einsum("bhk,bhkd->bhd", probs, sel)


def _knn_decode_attention_cp(q, keys, values, valid, *, k, recall_target,
                             mesh, cp_axes, kv_groups=1):
    """Distributed KNN attention (paper §7) over a sequence-sharded cache."""
    from jax.sharding import PartitionSpec as P

    global_s = keys.shape[1]

    def local_fn(q, keys_l, values_l, valid_l):
        b, s_l, kv, hd = keys_l.shape
        h = kv * kv_groups
        scale = hd ** -0.5
        # group-wise scores: no H-wide expansion of the O(S) cache.
        qg = q.reshape(b, kv, kv_groups, hd)
        scores = jnp.einsum(
            "bkgd,bskd->bkgs", qg, keys_l
        ).reshape(b, h, s_l) * scale
        values_l = _repeat_kv(values_l, kv_groups)  # (B, s_l, H, hd)
        scores = jnp.where(valid_l[None, None], scores, _NEG_INF)
        # Local PartialReduce: bin budget scaled by the global S (§7 /
        # reduction_input_size_override), keep bin winners only.
        vals, idxs = approx_max_k(
            scores, min(k, s_l), recall_target=recall_target,
            reduction_input_size_override=global_s,
            aggregate_to_topk=False,
        )
        # Attach the value vectors of the local winners: (B, H, L_loc, hd);
        # payloads travel in bf16 (scores stay f32 for the rescoring).
        v_bhsd = values_l.transpose(0, 2, 1, 3)
        sel_v = jnp.take_along_axis(v_bhsd, idxs[..., None], axis=2)
        sel_v = sel_v.astype(jnp.bfloat16)
        # All-gather candidates + payloads along the cp axes (tiny: O(L*hd)).
        for ax in cp_axes:
            vals = jax.lax.all_gather(vals, ax, axis=2, tiled=True)
            sel_v = jax.lax.all_gather(sel_v, ax, axis=2, tiled=True)
        # Global ExactRescoring + softmax over the k survivors.
        top_vals, top_pos = jax.lax.top_k(vals, k)
        probs = jax.nn.softmax(top_vals.astype(jnp.float32), -1).astype(q.dtype)
        top_v = jnp.take_along_axis(sel_v, top_pos[..., None], axis=2)
        return jnp.einsum("bhk,bhkd->bhd", probs, top_v)

    cp_spec = tuple(cp_axes) if len(cp_axes) > 1 else cp_axes[0]
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(),
            P(None, cp_spec, None, None),
            P(None, cp_spec, None, None),
            P(cp_spec),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, keys, values, valid)


# --------------------------------------------------------------------------
# MLA (deepseek-v2 multi-head latent attention)
# --------------------------------------------------------------------------


def _mla_q(params, x, positions, *, qk_nope_dim, qk_rope_dim, rope_theta):
    if "wq_a" in params:
        from repro.models.layers import rms_norm

        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]), params["q_norm"])
        q = jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, theta=rope_theta)
    return q_nope, q_rope


def mla_train(
    params: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    num_heads: int,
    kv_lora_rank: int,
    qk_nope_dim: int = 128,
    qk_rope_dim: int = 64,
    rope_theta: float = 10000.0,
    q_chunk: int = 512,
    return_cache: bool = False,
):
    from repro.models.layers import rms_norm

    b, s, d = x.shape
    q_nope, q_rope = _mla_q(
        params, x, positions,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim, rope_theta=rope_theta,
    )
    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv = rms_norm(kv_a[..., :kv_lora_rank], params["kv_norm"])
    k_rope = apply_rope(
        kv_a[..., None, kv_lora_rank:], positions, theta=rope_theta
    )  # (B, S, 1, rope_dim) shared across heads
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wk_b"])
    value = jnp.einsum("bsr,rhk->bshk", c_kv, params["wv_b"])
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, num_heads, qk_rope_dim))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _attend_chunked(
        q_full, k_full, value, positions, positions,
        causal=True, window=None, chunk=q_chunk,
    )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if return_cache:
        return y, MLACache(c_kv=c_kv, k_rope=k_rope[:, :, 0, :])
    return y


def mla_decode(
    params: Dict,
    x: jnp.ndarray,                # (B, 1, d)
    cache: MLACache,
    cur_index: jnp.ndarray,
    *,
    num_heads: int,
    kv_lora_rank: int,
    qk_nope_dim: int = 128,
    qk_rope_dim: int = 64,
    rope_theta: float = 10000.0,
    knn_k: int = 0,
    knn_recall_target: float = 0.95,
) -> Tuple[jnp.ndarray, MLACache]:
    """Absorbed-matmul MLA decode: attends in the compressed kv_lora space.

    Cache holds (c_kv, k_rope) — (512+64) floats/token instead of
    2*H*head_dim; score = q_nopeᵀ(W_kb c) + q_ropeᵀ k_rope computed by
    absorbing W_kb into the query.
    """
    from repro.models.layers import rms_norm

    b = x.shape[0]
    positions = jnp.full((1,), cur_index, jnp.int32)
    q_nope, q_rope = _mla_q(
        params, x, positions,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim, rope_theta=rope_theta,
    )
    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_new = rms_norm(kv_a[..., :kv_lora_rank], params["kv_norm"])
    kr_new = apply_rope(kv_a[..., None, kv_lora_rank:], positions, theta=rope_theta)[:, :, 0]
    c_kv = jax.lax.dynamic_update_slice(
        cache.c_kv, c_new.astype(cache.c_kv.dtype), (0, cur_index, 0)
    )
    k_rope = jax.lax.dynamic_update_slice(
        cache.k_rope, kr_new.astype(cache.k_rope.dtype), (0, cur_index, 0)
    )
    new_cache = MLACache(c_kv=c_kv, k_rope=k_rope)

    # Absorb W_kb into q: (B, H, kv_lora).
    q_c = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], params["wk_b"])
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    scores = (
        jnp.einsum("bhr,bsr->bhs", q_c, c_kv)
        + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], k_rope)
    ) * scale
    s = c_kv.shape[1]
    valid = jnp.arange(s, dtype=jnp.int32) <= cur_index
    scores = jnp.where(valid[None, None], scores, _NEG_INF)

    if knn_k:
        top_scores, top_idx = approx_max_k(
            scores, knn_k, recall_target=knn_recall_target
        )
        probs = jax.nn.softmax(top_scores.astype(jnp.float32), -1).astype(x.dtype)
        sel = jnp.take_along_axis(
            jnp.broadcast_to(c_kv[:, None], (b, num_heads, s, kv_lora_rank)),
            top_idx[..., None],
            axis=2,
        )
        attn_c = jnp.einsum("bhk,bhkr->bhr", probs, sel)
    else:
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
        attn_c = jnp.einsum("bhs,bsr->bhr", probs, c_kv)
    out = jnp.einsum("bhr,rhk->bhk", attn_c, params["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]
    return y, new_cache


# --------------------------------------------------------------------------
# Cross attention (whisper decoder)
# --------------------------------------------------------------------------


def cross_attention(
    params: Dict,
    x: jnp.ndarray,                # (B, Sq, d)
    enc_kv: KVCache,               # precomputed from encoder output
    *,
    num_heads: int,
    q_chunk: int = 512,
):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    sq = x.shape[1]
    out = _attend_chunked(
        q, enc_kv.k, enc_kv.v,
        jnp.arange(sq), jnp.arange(enc_kv.k.shape[1]),
        causal=False, window=None, chunk=q_chunk,
    )
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def encode_cross_kv(params: Dict, enc_out: jnp.ndarray) -> KVCache:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return KVCache(k=k, v=v)

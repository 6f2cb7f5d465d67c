"""GQA attention: chunked (flash-style) prefill/train path + cached decode path.

The prefill path is chunked over query blocks with a ``lax.scan`` so the full
(S, S) score matrix is never materialized — mandatory for the 32k-prefill input
shape (a naive 32k x 32k score tensor would not fit HBM), and it keeps the HLO
size O(1) in sequence length. Each chunk sees its full key row, so a plain
(numerically stable) softmax suffices — no online rescaling needed here; the
Pallas kernels (kernels/flash_attention, kernels/decode_attention) implement the
true blocked online-softmax versions for TPU and are validated against this
reference logic.

Sliding-window masks are expressed with a *traced* window scalar so that
gemma3-style local:global stacks can scan one homogeneous layer body over a
per-layer window array.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.modules import apply_rope
from repro.models.spec import ParamSpec

NEG_INF = -2.0e30


def attention_specs(d_model: int, n_heads: int, n_kv_heads: int,
                    head_dim: int) -> dict:
    return {
        "wq": ParamSpec((d_model, n_heads, head_dim), ("embed", "heads", None)),
        "wk": ParamSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", None)),
        "wo": ParamSpec((n_heads, head_dim, d_model), ("heads", None, "embed")),
    }


def _sdpa_chunk(q, k, v, mask, scale):
    """q: (B,C,KV,G,dh); k,v: (B,S,KV,dh); mask: (B?,1?,C,S) bool -> (B,C,KV,G,dh)."""
    scores = jnp.einsum("bckgd,bskd->bkgcs", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgcs,bskd->bckgd", probs, v)


def chunked_attention(q, k, v, *, causal: bool, window, q_offset=0,
                      chunk_size: int = 1024, kv_offset: int = 0):
    """Blocked attention.

    q: (B, Sq, H, dh)   k, v: (B, Skv, KVH, dh)
    window: traced or static int — keys j are visible to query i iff
            (not causal or j <= i) and (i - j < window).
    """
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    q = q.reshape(B, Sq, KV, G, dh)
    C = min(chunk_size, Sq)
    if Sq % C:
        C = Sq  # smoke-test sizes: single chunk
    n_chunks = Sq // C
    j = kv_offset + jnp.arange(Skv)

    def one_chunk(carry, qc_and_idx):
        qc, c_idx = qc_and_idx
        i = q_offset + c_idx * C + jnp.arange(C)
        mask = jnp.ones((C, Skv), bool)
        if causal:
            mask &= j[None, :] <= i[:, None]
        if window is not None:
            mask &= (i[:, None] - j[None, :]) < window
        out = _sdpa_chunk(qc, k, v, mask[None], scale)
        return carry, out

    if n_chunks == 1:
        _, out = one_chunk(None, (q, jnp.int32(0)))
    else:
        qs = q.reshape(n_chunks, B, C, KV, G, dh)
        _, out = jax.lax.scan(one_chunk, None,
                              (qs, jnp.arange(n_chunks, dtype=jnp.int32)))
        out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, KV, G, dh)
    return out.reshape(B, Sq, H, dh)


def mq_decode_attention_ref(q, k_cache, v_cache, pos_ids, pos, *, window):
    """q_len>1 decode attention against a (possibly ring-buffer) KV cache:
    the multi-query generalization of `decode_attention_ref` used by
    speculative-decoding verification (DESIGN.md §11).

    q: (B, q_len, H, dh) — query i sits at absolute position pos + i;
    k_cache/v_cache: (B, S_c, KV, dh) with the q_len new K/V already
    written; pos_ids: (S_c,) absolute position per slot (-1 = empty);
    pos: scalar position of query 0. Returns (B, q_len, H, dh).
    """
    B, Q, H, dh = q.shape
    S_c, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = dh ** -0.5
    qg = q.reshape(B, Q, KV, G, dh)
    scores = jnp.einsum("bqkgd,bskd->bqkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    qpos = pos + jnp.arange(Q)                     # (Q,)
    valid = (pos_ids[None, :] >= 0) \
        & (pos_ids[None, :] <= qpos[:, None])      # (Q, S_c)
    if window is not None:
        valid &= (qpos[:, None] - pos_ids[None, :]) < window
    scores = jnp.where(valid[None, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bqkgs,bskd->bqkgd", probs, v_cache)
    return out.reshape(B, Q, H, dh)


def decode_attention_ref(q, k_cache, v_cache, pos_ids, pos, *, window):
    """One-token attention against a (possibly ring-buffer) KV cache.

    q: (B, 1, H, dh); k_cache/v_cache: (B, S_c, KV, dh);
    pos_ids: (S_c,) absolute position stored in each slot (-1 = empty);
    pos: scalar current position. Returns (B, 1, H, dh).
    """
    B, _, H, dh = q.shape
    S_c, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = dh ** -0.5
    qg = q.reshape(B, KV, G, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = (pos_ids >= 0) & (pos_ids <= pos)
    if window is not None:
        valid &= (pos - pos_ids) < window
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(B, 1, H, dh)


# ----------------------------------------------------------------------------
# Full attention block (projections + rope + sdpa)
# ----------------------------------------------------------------------------
def attn_forward(params, x, *, rope_theta, causal=True, window=None,
                 q_offset=0, positions=None, kv=None, impl: str = "ref"):
    """Sequence attention (train / prefill). Returns (out, (k, v)) where k, v
    are the rope'd keys/values for KV-cache seeding.

    kv: optional (k_src, v_src) hidden states for cross-attention.
    """
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    src = x if kv is None else kv[0]
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src if kv is None else kv[1], params["wv"])
    if kv is None:  # self-attention: rotary on q and k
        if positions is None:
            positions = q_offset + jnp.arange(S)[None, :]
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, q_offset * 0 + (positions if kv is None else positions),
                       rope_theta)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                q_offset=0 if kv is None else q_offset)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, (k, v)


def attn_decode(params, x, cache_k, cache_v, pos_ids, pos, slot, *, rope_theta,
                window=None, impl: str = "ref"):
    """Single-token decode. x: (B, 1, D); slot: cache index to write (the model
    computes it once — ring or linear — so layers can be scanned uniformly);
    pos_ids: (S_c,) already updated with `pos` at `slot`.
    Returns (out, new_cache_k, new_cache_v)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    posb = jnp.full((B, 1), pos)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    ck = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    if impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q, ck, cv, pos_ids, pos, window=window)
    else:
        out = decode_attention_ref(q, ck, cv, pos_ids, pos, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, ck, cv


def attn_decode_multi(params, x, cache_k, cache_v, pos_ids, pos, slots, *,
                      rope_theta, window=None, impl: str = "ref"):
    """q_len-token verification decode (speculative decoding, DESIGN.md
    §11). x: (B, q_len, D); slots: (q_len,) cache indices receiving the
    new K/V (position pos + i lands at slots[i]); pos_ids: (S_c,) already
    updated with pos + i at slots[i]. All q_len K/V are written first, so
    the queries attend to each other through the cache; causality between
    them is the per-query validity mask (pos_ids <= pos + i) — exactly the
    arithmetic sequential `attn_decode` steps would have produced.
    Returns (out (B, q_len, D), new_cache_k, new_cache_v)."""
    B, Q, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    posb = pos + jnp.broadcast_to(jnp.arange(Q), (B, Q))
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    # contiguous write at slots[0]: callers guarantee the verify window
    # never wraps the ring (the serving backend caps q_len so
    # pos + q_len <= max_len).
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, slots[0], 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, slots[0], 0, 0))
    if impl == "pallas":
        from repro.kernels.decode_attention import multiquery as mq
        out = mq.mq_decode_attention(q, ck, cv, pos_ids, pos, window=window)
    else:
        out = mq_decode_attention_ref(q, ck, cv, pos_ids, pos,
                                      window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, ck, cv


def paged_attn_decode(params, x, k_pool, v_pool, page_ids, slot,
                      block_tables, ctx_lens, pos, *, rope_theta,
                      window=None, impl: str = "ref"):
    """Single-token decode against a *paged* KV pool (DESIGN.md §10).

    x: (B, 1, D); k_pool/v_pool: (P, page_size, KV, dh) shared physical
    pages; page_ids: (B,) physical page receiving this token; slot: scalar
    offset inside that page (all sequences share `pos`, so it is uniform);
    block_tables: (B, max_pages) int32 (-1 pads); ctx_lens: (B,) tokens
    live *including* this one. Returns (out, new_k_pool, new_v_pool)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    posb = jnp.full((B, 1), pos)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    ck = k_pool.at[page_ids, slot].set(k[:, 0].astype(k_pool.dtype))
    cv = v_pool.at[page_ids, slot].set(v[:, 0].astype(v_pool.dtype))
    if impl == "pallas":
        from repro.kernels.decode_attention import paged as pg
        out = pg.paged_decode_attention(q, ck, cv, block_tables, ctx_lens,
                                        window=window)
    else:
        from repro.kernels.decode_attention.paged import \
            paged_decode_attention_ref
        out = paged_decode_attention_ref(q, ck, cv, block_tables, ctx_lens,
                                         window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, ck, cv


def paged_attn_decode_multi(params, x, k_pool, v_pool, page_ids, slots,
                            block_tables, ctx_lens, pos, *, rope_theta,
                            window=None, impl: str = "ref"):
    """q_len-token verification decode against a paged KV pool (DESIGN.md
    §11). x: (B, q_len, D); page_ids: (B, q_len) physical page per new
    token; slots: (q_len,) offsets inside those pages (shared `pos`
    convention, so uniform across the batch); ctx_lens: (B,) tokens live
    *including* the q_len new ones. Returns (out, new_k_pool, new_v_pool).
    """
    B, Q, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    posb = pos + jnp.broadcast_to(jnp.arange(Q), (B, Q))
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    slot_b = jnp.broadcast_to(slots, (B, Q))
    ck = k_pool.at[page_ids, slot_b].set(k.astype(k_pool.dtype))
    cv = v_pool.at[page_ids, slot_b].set(v.astype(v_pool.dtype))
    from repro.kernels.decode_attention import multiquery as mq
    if impl == "pallas":
        out = mq.mq_paged_decode_attention(q, ck, cv, block_tables,
                                           ctx_lens, window=window)
    else:
        out = mq.mq_paged_decode_attention_ref(q, ck, cv, block_tables,
                                               ctx_lens, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, ck, cv


def cross_attn_decode(params, x, ck, cv, enc_len, impl: str = "ref"):
    """Decode-time cross attention against precomputed encoder K/V.
    ck, cv: (B, S_enc, KV, dh)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    pos_ids = jnp.arange(ck.shape[1])
    valid_to = jnp.asarray(enc_len)
    out = decode_attention_ref(q, ck, cv, jnp.where(pos_ids < valid_to, pos_ids, -1),
                               jnp.int32(2 ** 30), window=None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])

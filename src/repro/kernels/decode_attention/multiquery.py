"""Multi-query flash-decode attention: q_len > 1 query positions per step.

Speculative decoding (repro.specdec, DESIGN.md §11) verifies k drafted
tokens in one pass: the q_len = k+1 newest positions of each sequence
attend to the whole cache — including each other, through the cache,
because their K/V are written before attention runs. Causality between
the new positions is purely a masking question: query row qi (absolute
position P+qi) may see cache token t iff t's position <= P+qi (and the
sliding window). Both kernels here are the q_len=1 kernels of this
package with the G query-head rows widened to q_len*G and the validity
mask made per-row:

  mq_decode_attention        contiguous cache, pos_ids slot validity
                             (the engine's per-stage layout)
  mq_paged_decode_attention  block-table gather over a shared page pool
                             (the paged KV subsystem)

Bit-wise contract (test_specdec.py): each kernel equals its blocked jnp
reference bit-for-bit at bf16, and at q_len=1 reproduces the existing
single-query kernel's output exactly — speculative verification is
provably the same arithmetic as sequential decode, just batched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one definition each — the bit-wise kernel-vs-ref contracts depend on
# every module in this package masking with the same constant
from repro.kernels import auto_interpret, tuning
from repro.kernels.decode_attention.kernel import NEG_INF
from repro.kernels.decode_attention.ops import GLOBAL_WINDOW


# ============================================================================
# Contiguous-cache kernel (pos_ids validity, per-query positions)
# ============================================================================
def _mq_decode_kernel(scalars_ref,                   # SMEM: [pos, window]
                      q_ref, k_ref, v_ref, ids_ref,  # VMEM blocks
                      o_ref,                         # VMEM out
                      m_ref, l_ref, acc_ref,         # VMEM scratch
                      *, dh_real: int, block_k: int, q_len: int, g: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (q_len*G, dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (block_k, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (dh_real ** -0.5)                     # (q_len*G, block_k)

    pos = scalars_ref[0]                          # first query's position
    window = scalars_ref[1]
    ids = ids_ref[0]                              # (block_k,) int32
    rows = s.shape[0]
    qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // g
    valid = (ids[None, :] >= 0) & (ids[None, :] <= qpos) \
        & ((qpos - ids[None, :]) < window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def mq_decode_attention_kernel(q, k, v, pos_ids, pos, window, *,
                               dh_real: int, q_len: int,
                               block_k: int = 512, interpret: bool = False):
    """q: (B, KV, q_len*G, dh) — row qi*G + g is query head g of position
    pos + qi; k, v: (B, KV, S_c, dh); pos_ids: (1, S_c) int32; pos (first
    query's absolute position), window: int32 scalars.
    Returns (B, KV, q_len*G, dh)."""
    B, KV, R, dh = q.shape
    assert R % q_len == 0, (R, q_len)
    g = R // q_len
    S_c = k.shape[2]
    block_k = min(block_k, S_c)
    grid = (B, KV, S_c // block_k)
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32),
                         jnp.asarray(window, jnp.int32)])

    kernel = functools.partial(_mq_decode_kernel, dh_real=dh_real,
                               block_k=block_k, q_len=q_len, g=g)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, R, dh),
                             lambda b, h, ik, sc: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, dh),
                             lambda b, h, ik, sc: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k, dh),
                             lambda b, h, ik, sc: (b, h, ik, 0)),
                pl.BlockSpec((1, block_k),
                             lambda b, h, ik, sc: (0, ik)),
            ],
            out_specs=pl.BlockSpec((1, 1, R, dh),
                                   lambda b, h, ik, sc: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, dh), q.dtype),
        interpret=interpret,
    )(scalars, q, k, v, pos_ids)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def mq_decode_attention(q, k_cache, v_cache, pos_ids, pos, *, window=None,
                        block_k=None, interpret=None):
    """q: (B, q_len, H, dh); k/v_cache: (B, S_c, KV, dh); pos_ids: (S_c,);
    pos: int32 scalar, the absolute position of query 0 (query i sits at
    pos + i) -> (B, q_len, H, dh). block_k=None consults the tuned table
    (repro.kernels.tuning) at trace time; 512 with none installed."""
    if interpret is None:
        interpret = auto_interpret()
    B, Q, H, dh = q.shape
    S_c, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if window is None:
        window = GLOBAL_WINDOW
    block_k = tuning.resolve("mq_decode_attention", S_c, dh, "block_k",
                             block_k)

    bk = min(block_k, max(S_c, 128))
    pad_s = (-S_c) % bk
    pad_d = (-dh) % 128

    # (B, Q, KV, G, dh) -> (B, KV, Q*G, dh): row qi*G + g
    qk = q.reshape(B, Q, KV, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, Q * G, dh)
    kt = jnp.moveaxis(k_cache, 2, 1)                       # (B, KV, S_c, dh)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad_s or pad_d:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
    ids = jnp.pad(pos_ids.astype(jnp.int32), (0, pad_s),
                  constant_values=-1).reshape(1, -1)

    out = mq_decode_attention_kernel(qk, kt, vt, ids, pos, window,
                                     dh_real=dh, q_len=Q, block_k=bk,
                                     interpret=interpret)
    out = out[..., :dh].reshape(B, KV, Q, G, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Q, H, dh)


# ============================================================================
# Paged kernel (block-table gather, per-query positions)
# ============================================================================
def _mq_paged_kernel(bt_ref, lens_ref, win_ref,     # SMEM scalar prefetch
                     q_ref, k_ref, v_ref,           # VMEM blocks
                     o_ref,                         # VMEM out
                     m_ref, l_ref, acc_ref,         # VMEM scratch
                     *, dh_real: int, page_size: int, q_len: int, g: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (q_len*G, dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (page_size, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (dh_real ** -0.5)                     # (q_len*G, page_size)

    ctx = lens_ref[b]                             # incl. the q_len new ones
    window = win_ref[0]
    allocated = bt_ref[b, ip] >= 0
    rows = s.shape[0]
    t = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    qpos = ctx - q_len \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) // g
    valid = allocated & (t <= qpos) & ((qpos - t) < window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def mq_paged_decode_attention_kernel(q, k_pool, v_pool, block_tables,
                                     ctx_lens, window, *, dh_real: int,
                                     q_len: int, interpret: bool = False):
    """q: (B, KV, q_len*G, dh); k/v_pool: (P, KV, page_size, dh);
    block_tables: (B, max_pages) int32 (-1 = unallocated); ctx_lens: (B,)
    int32 counting tokens *including* the q_len new positions; window:
    int32 scalar. Returns (B, KV, q_len*G, dh)."""
    B, KV, R, dh = q.shape
    assert R % q_len == 0, (R, q_len)
    g = R // q_len
    page_size = k_pool.shape[2]
    max_pages = block_tables.shape[1]
    grid = (B, KV, max_pages)

    kernel = functools.partial(_mq_paged_kernel, dh_real=dh_real,
                               page_size=page_size, q_len=q_len, g=g)

    def kv_index(b, h, ip, bt, lens, win):
        return (jnp.maximum(bt[b, ip], 0), h, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, R, dh),
                             lambda b, h, ip, bt, lens, win: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page_size, dh), kv_index),
                pl.BlockSpec((1, 1, page_size, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, R, dh),
                                   lambda b, h, ip, bt, lens, win:
                                   (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(window, jnp.int32)[None], q, k_pool, v_pool)


# ============================================================================
# Pure-jnp blocked oracle (bit-wise contract with the paged kernel)
# ============================================================================
def mq_paged_decode_attention_ref(q, k_pool, v_pool, block_tables, ctx_lens,
                                  *, window=None):
    """Same layouts as the public wrapper: q (B, q_len, H, dh); k/v_pool
    (P, page_size, KV, dh); block_tables (B, max_pages); ctx_lens (B,)
    incl. the q_len new positions. Walks pages with the kernel's exact
    online-softmax arithmetic, so interpret-mode kernel output must equal
    this bit-for-bit. Returns (B, q_len, H, dh)."""
    B, Q, H, dh = q.shape
    page_size, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    R = Q * G
    max_pages = block_tables.shape[1]
    if window is None:
        window = GLOBAL_WINDOW

    qg = q.reshape(B, Q, KV, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, R, dh).astype(jnp.float32)
    kt = jnp.moveaxis(k_pool, 2, 1)               # (P, KV, page_size, dh)
    vt = jnp.moveaxis(v_pool, 2, 1)
    safe_bt = jnp.maximum(block_tables, 0)
    ctx = ctx_lens.astype(jnp.int32)

    # per-(b, kv-head) 2D dots, one per kernel grid step, rows padded to
    # the 8-row sublane tile (same rationale as paged_decode_attention_ref)
    Rp = max(R, 8)

    def _dot(a2, c2, contract):
        a2 = jnp.pad(a2, ((0, Rp - R), (0, 0)))
        out = jax.lax.dot_general(a2, c2, (((1,), (contract,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return out[:R]

    def dot_qk(a, c):
        return jnp.stack([jnp.stack([_dot(a[b, h], c[b, h], 1)
                                     for h in range(KV)]) for b in range(B)])

    def dot_pv(a, c):
        return jnp.stack([jnp.stack([_dot(a[b, h], c[b, h], 0)
                                     for h in range(KV)]) for b in range(B)])

    rows = jnp.arange(R) // G                     # query index per row
    m = jnp.full((B, KV, R, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, R, 1), jnp.float32)
    acc = jnp.zeros((B, KV, R, dh), jnp.float32)
    for ip in range(max_pages):
        k = kt[safe_bt[:, ip]].astype(jnp.float32)   # (B, KV, ps, dh)
        v = vt[safe_bt[:, ip]].astype(jnp.float32)
        s = dot_qk(qg, k) * (dh ** -0.5)             # (B, KV, R, ps)
        t = ip * page_size + jnp.arange(page_size)
        qpos = (ctx[:, None] - Q) + rows[None, :]    # (B, R)
        valid = (block_tables[:, ip] >= 0)[:, None, None] \
            & (t[None, None, :] <= qpos[:, :, None]) \
            & ((qpos[:, :, None] - t[None, None, :]) < window)
        s = jnp.where(valid[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        acc = acc * corr + dot_pv(p, v)
        m = m_new
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)
    return out.reshape(B, KV, Q, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Q, H, dh)


# ============================================================================
# Public wrapper (model layout in)
# ============================================================================
@functools.partial(jax.jit, static_argnames=("interpret",))
def mq_paged_decode_attention(q, k_pool, v_pool, block_tables, ctx_lens, *,
                              window=None, interpret=None):
    """q: (B, q_len, H, dh); k/v_pool: (P, page_size, KV, dh);
    block_tables: (B, max_pages) int32 (-1 pads); ctx_lens: (B,) int32
    counting tokens incl. the q_len new positions -> (B, q_len, H, dh)."""
    if interpret is None:
        interpret = auto_interpret()
    B, Q, H, dh = q.shape
    page_size, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    if window is None:
        window = GLOBAL_WINDOW
    assert page_size % 8 == 0, f"page_size {page_size} not sublane-aligned"

    pad_d = (-dh) % 128
    qk = q.reshape(B, Q, KV, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, Q * G, dh)
    kt = jnp.moveaxis(k_pool, 2, 1)               # (P, KV, page_size, dh)
    vt = jnp.moveaxis(v_pool, 2, 1)
    if pad_d:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, pad_d)))

    out = mq_paged_decode_attention_kernel(qk, kt, vt, block_tables,
                                           ctx_lens, window, dh_real=dh,
                                           q_len=Q, interpret=interpret)
    out = out[..., :dh].reshape(B, KV, Q, G, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Q, H, dh)

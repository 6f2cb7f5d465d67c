"""Paged flash-decode GQA attention: gather K/V through a block table.

The contiguous decode kernel (kernel.py) streams one request's cache as a
single slab. Under the paged KV subsystem (repro.kvcache, DESIGN.md §10)
a request's cache is `page_size`-token pages scattered anywhere in a
shared pool, named by a per-request block table. This kernel walks the
table: grid (B, KV, max_pages), and the *index map* of the K/V operands
reads the scalar-prefetched block table to DMA the right physical page
for each (request, page) grid step — the gather costs nothing over the
contiguous kernel because the page id is known before the block loads.

Layouts (arranged by the public wrapper):
  q            (B, KV, G, dh)        G = H/KV query heads per KV group
  k/v pool     (P, KV, page_size, dh) physical pages, any owner
  block table  (B, max_pages) int32  physical page per logical page,
                                     -1 = unallocated (masked out)
  ctx_lens     (B,) int32            tokens live per request

Validity per slot is positional: slot j of logical page ip holds absolute
token ip*page_size + j, live iff < ctx_lens[b] (and within the sliding
window). A partially-filled last page and garbage in unallocated pages
are therefore masked identically to the contiguous kernel's pos_ids mask.

`paged_decode_attention_ref` is the pure-jnp oracle: the same blocked
online-softmax walk, page by page, in the same operation order. The
bit-wise contract (test_kvcache.py) is two-fold: the kernel equals this
reference bit-for-bit at the model's cache dtype (bf16), and equals the
*contiguous* decode kernel on the gathered cache bit-for-bit at every
dtype — so the block-table gather is provably lossless, not just close.
(f32 kernel-vs-jnp-ref is ulp-level: XLA lowers the eager reference and
the jitted interpreter graph through different dot shapes.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret

NEG_INF = -2.0e30
GLOBAL_WINDOW = 2 ** 30


# ============================================================================
# Pallas kernel
# ============================================================================
def _paged_decode_kernel(bt_ref, lens_ref, win_ref,     # SMEM scalar prefetch
                         q_ref, k_ref, v_ref,           # VMEM blocks
                         o_ref,                         # VMEM out
                         m_ref, l_ref, acc_ref,         # VMEM scratch
                         *, dh_real: int, page_size: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (page_size, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (dh_real ** -0.5)                     # (G, page_size)

    ctx = lens_ref[b]
    window = win_ref[0]
    allocated = bt_ref[b, ip] >= 0
    t = ip * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size),
                                                  1)[0]
    valid = allocated & (t < ctx) & ((ctx - 1 - t) < window)
    s = jnp.where(valid[None, :], s, NEG_INF)

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


def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables, ctx_lens,
                                  window, *, dh_real: int,
                                  interpret: bool = False):
    """q: (B, KV, G, dh); k/v_pool: (P, KV, page_size, dh);
    block_tables: (B, max_pages) int32 (-1 = unallocated); ctx_lens: (B,)
    int32; window: int32 scalar. dh % 128 == 0, page_size % 8 == 0.
    Returns (B, KV, G, dh)."""
    B, KV, G, dh = q.shape
    page_size = k_pool.shape[2]
    max_pages = block_tables.shape[1]
    grid = (B, KV, max_pages)

    kernel = functools.partial(_paged_decode_kernel, dh_real=dh_real,
                               page_size=page_size)
    # unallocated entries are masked in-kernel; the index map only needs a
    # resident page to (harmlessly) load, so clamp -1 -> page 0
    def kv_index(b, h, ip, bt, lens, win):
        return (jnp.maximum(bt[b, ip], 0), h, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, dh),
                             lambda b, h, ip, bt, lens, win: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page_size, dh), kv_index),
                pl.BlockSpec((1, 1, page_size, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, dh),
                                   lambda b, h, ip, bt, lens, win:
                                   (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(window, jnp.int32)[None], q, k_pool, v_pool)


# ============================================================================
# Pure-jnp blocked oracle (bit-wise contract with the kernel)
# ============================================================================
def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, ctx_lens, *,
                               window=None):
    """Same layouts as the public wrapper: q (B, 1, H, dh); k/v_pool
    (P, page_size, KV, dh); block_tables (B, max_pages); ctx_lens (B,).
    Walks pages with the kernel's exact online-softmax arithmetic (same
    dot_generals, masking, and final division), so interpret-mode kernel
    output must equal this bit-for-bit. Returns (B, 1, H, dh)."""
    B, _, H, dh = q.shape
    page_size, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    max_pages = block_tables.shape[1]
    if window is None:
        window = GLOBAL_WINDOW

    qg = q.reshape(B, KV, G, dh).astype(jnp.float32)
    kt = jnp.moveaxis(k_pool, 2, 1)               # (P, KV, page_size, dh)
    vt = jnp.moveaxis(v_pool, 2, 1)
    safe_bt = jnp.maximum(block_tables, 0)
    ctx = ctx_lens.astype(jnp.int32)

    # per-(b, kv-head) 2D dots, exactly one per kernel grid step, with the
    # G dim padded to the 8-row sublane tile the kernel's blocks occupy —
    # batched matmuls (and M=1 gemv lowerings) reduce in a different order
    # than the tiled gemm, an ulp-level drift that would break the
    # bit-wise contract
    Gp = max(G, 8)

    def _dot(a2, c2, contract):
        a2 = jnp.pad(a2, ((0, Gp - G), (0, 0)))
        out = jax.lax.dot_general(a2, c2, (((1,), (contract,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return out[:G]

    def dot_qk(a, c):
        return jnp.stack([jnp.stack([_dot(a[b, h], c[b, h], 1)
                                     for h in range(KV)]) for b in range(B)])

    def dot_pv(a, c):
        return jnp.stack([jnp.stack([_dot(a[b, h], c[b, h], 0)
                                     for h in range(KV)]) for b in range(B)])

    m = jnp.full((B, KV, G, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, G, 1), jnp.float32)
    acc = jnp.zeros((B, KV, G, dh), jnp.float32)
    for ip in range(max_pages):
        k = kt[safe_bt[:, ip]].astype(jnp.float32)   # (B, KV, ps, dh)
        v = vt[safe_bt[:, ip]].astype(jnp.float32)
        s = dot_qk(qg, k) * (dh ** -0.5)             # (B, KV, G, ps)
        t = ip * page_size + jnp.arange(page_size)
        valid = (block_tables[:, ip] >= 0)[:, None] \
            & (t[None, :] < ctx[:, None]) \
            & ((ctx[:, None] - 1 - t[None, :]) < window)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        acc = acc * corr + dot_pv(p, v)
        m = m_new
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype).reshape(B, 1, H, dh)


# ============================================================================
# Public wrapper (model layout in)
# ============================================================================
@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, ctx_lens, *,
                           window=None, interpret=None):
    """q: (B, 1, H, dh); k/v_pool: (P, page_size, KV, dh); block_tables:
    (B, max_pages) int32 (-1 pads); ctx_lens: (B,) int32
    -> (B, 1, H, dh). Pads dh to the 128-lane tile; page_size must be a
    multiple of 8 (f32 sublane tile)."""
    if interpret is None:
        interpret = auto_interpret()
    B, _, H, dh = q.shape
    page_size, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    if window is None:
        window = GLOBAL_WINDOW
    assert page_size % 8 == 0, f"page_size {page_size} not sublane-aligned"

    pad_d = (-dh) % 128
    qk = q.reshape(B, KV, G, dh)
    kt = jnp.moveaxis(k_pool, 2, 1)               # (P, KV, page_size, dh)
    vt = jnp.moveaxis(v_pool, 2, 1)
    if pad_d:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, pad_d)))

    out = paged_decode_attention_kernel(qk, kt, vt, block_tables, ctx_lens,
                                        window, dh_real=dh,
                                        interpret=interpret)
    return out[..., :dh].reshape(B, 1, H, dh)


def gather_page_row(pool, table_row):
    """Materialize one request's cache contiguously: pool (P, page_size,
    KV, dh), table_row (max_pages,) -> (max_pages*page_size, KV, dh).
    Unallocated (-1) entries gather page 0 — callers mask by position
    exactly like the kernel does. Oracle-side helper for tests/adoption."""
    pages = pool[jnp.maximum(table_row, 0)]        # (max_pages, ps, KV, dh)
    return pages.reshape(-1, *pool.shape[2:])

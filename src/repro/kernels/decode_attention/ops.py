"""Jit'd public wrapper for flash-decode attention.

Model layout in: q (B, 1, H, dh), cache (B, S_c, KV, dh), pos_ids (S_c,).
Pads S_c to the kv block and dh to 128 lanes; padded slots get pos_id = -1
so the kernel's validity mask drops them — no separate padding mask needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret, tuning
from repro.kernels.decode_attention.kernel import decode_attention_kernel

GLOBAL_WINDOW = 2 ** 30


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, pos_ids, pos, *, window=None,
                     block_k=None, interpret=None):
    """q: (B, 1, H, dh); k/v_cache: (B, S_c, KV, dh); pos_ids: (S_c,);
    pos: int32 scalar -> (B, 1, H, dh). block_k=None consults the tuned
    table (repro.kernels.tuning) at trace time; 512 with none installed."""
    if interpret is None:
        interpret = auto_interpret()
    B, _, H, dh = q.shape
    S_c, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if window is None:
        window = GLOBAL_WINDOW
    block_k = tuning.resolve("decode_attention", S_c, dh, "block_k", block_k)

    bk = min(block_k, max(S_c, 128))
    pad_s = (-S_c) % bk
    pad_d = (-dh) % 128

    qk = jnp.moveaxis(q.reshape(B, KV, G, dh), 0, 0)       # already (B,KV,G,dh)
    kt = jnp.moveaxis(k_cache, 2, 1)                       # (B, KV, S_c, dh)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if pad_s or pad_d:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
    ids = jnp.pad(pos_ids.astype(jnp.int32), (0, pad_s),
                  constant_values=-1).reshape(1, -1)

    out = decode_attention_kernel(qk, kt, vt, ids, pos, window,
                                  dh_real=dh, block_k=bk,
                                  interpret=interpret)
    return out[..., :dh].reshape(B, 1, H, dh)

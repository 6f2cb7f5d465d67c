"""Jit'd public wrapper for the RWKV6 WKV kernel.

Model layout in: r/k/v/w (B, S, H, dh), u (H, dh), state (B, H, dh, dh).
Pads time to the block multiple with identity steps (w = 1, k = 0: the state
passes through unchanged and padded outputs are sliced off) and dh to the
128-lane width (padded lanes carry zero k/v so they never contaminate S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret, tuning
from repro.kernels.rwkv6_scan.kernel import wkv_kernel


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv(r, k, v, w, u, state, *, block_t=None, interpret=None):
    """r/k/v/w: (B, S, H, dh); u: (H, dh); state: (B, H, dh, dh) fp32.
    Returns (out (B, S, H, dh) fp32, new_state fp32). block_t=None
    consults the tuned table (repro.kernels.tuning); 256 with none
    installed."""
    if interpret is None:
        interpret = auto_interpret()
    B, S, H, dh = r.shape
    block_t = tuning.resolve("rwkv6_scan", S, dh, "block_t", block_t)
    bt = min(block_t, max(S, 8))
    pad_t = (-S) % bt
    pad_d = (-dh) % 128 if not interpret else 0

    def to_kernel(x, pad_value=0.0):
        x = jnp.moveaxis(x, 1, 2)                     # (B, H, S, dh)
        if pad_t or pad_d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_t), (0, pad_d)),
                        constant_values=pad_value)
        return x

    rk = to_kernel(r.astype(jnp.float32))
    kk = to_kernel(k.astype(jnp.float32))
    vk = to_kernel(v.astype(jnp.float32))
    wk = to_kernel(w.astype(jnp.float32), pad_value=1.0)
    uk = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, pad_d))) if pad_d else \
        u.astype(jnp.float32)
    uk = uk[:, None, :]                               # (H, 1, dh)
    sk = jnp.pad(state, ((0, 0), (0, 0), (0, pad_d), (0, pad_d))) if pad_d \
        else state

    out, s_final = wkv_kernel(rk, kk, vk, wk, uk, sk, block_t=bt,
                              interpret=interpret)
    out = jnp.moveaxis(out[:, :, :S, :dh], 1, 2)      # (B, S, H, dh)
    return out, s_final[:, :, :dh, :dh]

"""Jit'd public wrapper for the selective-scan kernel.

Model layout in (`repro.models.ssm.ssm_scan_ref`): xh (B, S, H, dh),
dt (B, S, H), B_in/C_in (B, S, N), A (H,), state (B, H, N, dh) fp32.
Pads time with dt = 0 (identity steps) and dh to the lane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret, tuning
from repro.kernels.ssm_scan.kernel import ssm_scan_kernel


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ssm_scan(xh, dt, B_in, C_in, A, state, *, block_t=None,
             interpret=None):
    """Returns (y (B, S, H, dh) fp32, new_state (B, H, N, dh) fp32).
    block_t=None consults the tuned table (repro.kernels.tuning); 256
    with none installed."""
    if interpret is None:
        interpret = auto_interpret()
    B, S, H, dh = xh.shape
    N = B_in.shape[-1]
    block_t = tuning.resolve("ssm_scan", S, dh, "block_t", block_t)
    bt = min(block_t, max(S, 8))
    pad_t = (-S) % bt
    pad_d = (-dh) % 128 if not interpret else 0

    x = jnp.moveaxis(xh.astype(jnp.float32), 1, 2)       # (B, H, S, dh)
    d = jnp.moveaxis(dt.astype(jnp.float32), 1, 2)[..., None]  # (B,H,S,1)
    if pad_t or pad_d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_t), (0, pad_d)))
        d = jnp.pad(d, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    bmat = jnp.pad(B_in.astype(jnp.float32), ((0, 0), (0, pad_t), (0, 0)))
    cmat = jnp.pad(C_in.astype(jnp.float32), ((0, 0), (0, pad_t), (0, 0)))
    a = A.astype(jnp.float32).reshape(H, 1, 1)
    s = jnp.pad(state, ((0, 0), (0, 0), (0, 0), (0, pad_d))) if pad_d \
        else state

    y, sT = ssm_scan_kernel(x, d, bmat, cmat, a, s, block_t=bt,
                            interpret=interpret)
    y = jnp.moveaxis(y[:, :, :S, :dh], 1, 2)             # (B, S, H, dh)
    return y, sT[..., :dh]

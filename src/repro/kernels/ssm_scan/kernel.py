"""Mamba-style selective SSM scan for TPU (Pallas) — hymba's SSM heads.

    h_t = exp(A·dt_t) ⊙ h_{t-1} + (dt_t · B_t) ⊗ x_t      h: (N, dh)
    y_t = C_t · h_t

TPU adaptation (vs. the CUDA selective-scan): the per-(batch, head) state
matrix h (N × dh, fp32) lives in VMEM scratch across the whole sequence —
grid (B, H, n_time_blocks) with the time dimension sequential, identical
in structure to the RWKV6 WKV kernel (the two recurrences differ only in
how the rank-1 update and the decay are parameterized). Per step the work
is a rank-1 outer product + an N-row reduction: VPU work on (N, dh) tiles.

Padding contract (ops.py): time padded with dt = 0 (decay = exp(0) = 1 and
update = 0 — identity steps); dh lane-padded with x = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, s0_ref,   # in
                y_ref, sT_ref,                                # out
                state_ref,                                    # scratch
                *, block_t: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _load():
        state_ref[...] = s0_ref[0, 0]

    a = a_ref[0]                                  # (1, 1) this head's A (<0)

    # each timestep's row is read straight from the refs: a dynamic slice
    # of a loaded tile has no Mosaic lowering
    def step(t, h):
        x_t = x_ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)    # (1, dh)
        dt_t = dt_ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, 1)
        b_t = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)       # (1, N)
        c_t = c_ref[0, pl.ds(t, 1), :].astype(jnp.float32)       # (1, N)
        decay = jnp.exp(a * dt_t)                                # (1, 1)
        h = decay * h + (dt_t * b_t.T) * x_t                     # (N, dh)
        y = c_t @ h                                              # (1, dh)
        y_ref[0, 0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, state_ref[...])
    state_ref[...] = h

    @pl.when(it == nt - 1)
    def _emit():
        sT_ref[0, 0] = h


def ssm_scan_kernel(x, dt, b, c, a, s0, *, block_t: int = 256,
                    interpret: bool = False):
    """x: (B, H, S, dh); dt: (B, H, S, 1) fp32; b, c: (B, S, N) fp32
    (shared across heads); a: (H, 1, 1) fp32 negative; s0: (B, H, N, dh) fp32.
    S % block_t == 0. Returns (y (B, H, S, dh) fp32, sT (B, H, N, dh))."""
    B, H, S, dh = x.shape
    N = b.shape[-1]
    block_t = min(block_t, S)
    grid = (B, H, S // block_t)

    t_spec = pl.BlockSpec((1, 1, block_t, dh), lambda bb, h, it: (bb, h, it, 0))
    dt_spec = pl.BlockSpec((1, 1, block_t, 1), lambda bb, h, it: (bb, h, it, 0))
    bc_spec = pl.BlockSpec((1, block_t, N), lambda bb, h, it: (bb, it, 0))
    s_spec = pl.BlockSpec((1, 1, N, dh), lambda bb, h, it: (bb, h, 0, 0))

    return pl.pallas_call(
        functools.partial(_ssm_kernel, block_t=block_t),
        grid=grid,
        in_specs=[t_spec, dt_spec, bc_spec, bc_spec,
                  pl.BlockSpec((1, 1, 1), lambda bb, h, it: (h, 0, 0)),
                  s_spec],
        out_specs=[t_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, N, dh), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, dh), jnp.float32)],
        interpret=interpret,
    )(x, dt, b, c, a, s0)

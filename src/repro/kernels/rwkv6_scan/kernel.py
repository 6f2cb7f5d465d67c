"""RWKV6 (Finch) WKV recurrence for TPU (Pallas).

    a_t = k_t^T v_t                    (dh, dh) rank-1 update
    o_t = r_t · (S + u ⊙_rows a_t)
    S  <- diag(w_t) S + a_t            (data-dependent decay on the k index)

TPU adaptation (vs. the CUDA kernels in the RWKV repo): the per-(batch, head)
state matrix S (dh × dh, fp32) lives in VMEM scratch for the *entire*
sequence — the grid is (B, H, n_time_blocks) with the time dimension
sequential, so S never round-trips HBM between steps. Within a block the
time loop is a `fori_loop` over rows of the (block_t, dh) r/k/v/w tiles;
each step is a rank-1 outer product + row-scaled matvec, i.e. VPU work on
(dh, dh) tiles with dh a multiple of the 128-lane register width (dh = 64
heads are lane-padded by ops.py; decay padding uses w = 1 and k = 0 so
padded lanes stay zero).

VMEM working set: 4·block_t·dh·4B (tiles) + 2·dh²·4B (state + out) ≈ 0.3 MB
at block_t = 256, dh = 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,   # in
                o_ref, sT_ref,                               # out
                state_ref,                                   # scratch
                *, block_t: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _load_state():
        state_ref[...] = s0_ref[0, 0]

    u = u_ref[0].astype(jnp.float32).T               # (dh, 1)

    # each timestep's row is read straight from the refs: a dynamic slice
    # of a loaded tile has no Mosaic lowering
    def step(t, S):
        row = (0, 0, pl.ds(t, 1), slice(None))
        r_t = r_ref[row].astype(jnp.float32)                 # (1, dh)
        k_t = k_ref[row].astype(jnp.float32)
        v_t = v_ref[row].astype(jnp.float32)
        w_t = w_ref[row].astype(jnp.float32)
        a = k_t.T * v_t                                      # (dh, dh)
        o = r_t @ (S + u * a)                                # (1, dh)
        o_ref[row] = o.astype(o_ref.dtype)
        return w_t.T * S + a

    S = jax.lax.fori_loop(0, block_t, step, state_ref[...])
    state_ref[...] = S

    @pl.when(it == nt - 1)
    def _emit_state():
        sT_ref[0, 0] = S


def wkv_kernel(r, k, v, w, u, s0, *, block_t: int = 256,
               interpret: bool = False):
    """r/k/v/w: (B, H, S, dh) [w fp32 decay in (0,1)]; u: (H, 1, dh);
    s0: (B, H, dh, dh) fp32. S % block_t == 0 (ops.py pads).
    Returns (out (B, H, S, dh) fp32, final state (B, H, dh, dh) fp32)."""
    B, H, S, dh = r.shape
    block_t = min(block_t, S)
    grid = (B, H, S // block_t)

    t_spec = pl.BlockSpec((1, 1, block_t, dh), lambda b, h, it: (b, h, it, 0))
    s_spec = pl.BlockSpec((1, 1, dh, dh), lambda b, h, it: (b, h, 0, 0))

    return pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=block_t),
        grid=grid,
        in_specs=[t_spec, t_spec, t_spec, t_spec,
                  pl.BlockSpec((1, 1, dh), lambda b, h, it: (h, 0, 0)),
                  s_spec],
        out_specs=[t_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)

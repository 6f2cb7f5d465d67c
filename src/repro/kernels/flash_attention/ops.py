"""Jit'd public wrapper: layout/padding glue around the flash kernel.

Model code calls ``flash_attention(q, k, v, causal=..., window=...)`` with the
model-native (B, S, H, dh) layout; this wrapper transposes to the kernel's
(B, H, S, dh) layout, pads S to block multiples and dh to 128 lanes (zero-pad
keys leave scores untouched because padded q·k terms are 0; padded kv *rows*
are masked via skv_real), and slices the result back.

On CPU the kernel runs in interpret mode; on TPU it compiles
to Mosaic. ``interpret=None`` auto-detects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret, tuning
from repro.kernels.flash_attention.kernel import flash_attention_kernel

GLOBAL_WINDOW = 2 ** 30


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q=None, block_k=None,
                    q_offset: int = 0, interpret=None):
    """q: (B, Sq, H, dh); k, v: (B, Skv, KV, dh) -> (B, Sq, H, dh).
    block_q/block_k=None consult the tuned table (repro.kernels.tuning)
    at trace time; (128, 512) with none installed."""
    if interpret is None:
        interpret = auto_interpret()
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    if window is None:
        window = GLOBAL_WINDOW
    block_q = tuning.resolve("flash_attention", Skv, dh, "block_q", block_q)
    block_k = tuning.resolve("flash_attention", Skv, dh, "block_k", block_k)
    ws = jnp.asarray(window, jnp.int32).reshape(1)

    qt = _pad_to(_pad_to(jnp.moveaxis(q, 2, 1), 2, block_q), 3, 128)
    kt = _pad_to(_pad_to(jnp.moveaxis(k, 2, 1), 2, block_k), 3, 128)
    vt = _pad_to(_pad_to(jnp.moveaxis(v, 2, 1), 2, block_k), 3, 128)

    out = flash_attention_kernel(qt, kt, vt, ws, causal=causal,
                                 sq_real=Sq, skv_real=Skv, dh_real=dh,
                                 block_q=block_q, block_k=block_k,
                                 q_offset=q_offset, interpret=interpret)
    return jnp.moveaxis(out[:, :, :Sq, :dh], 1, 2)

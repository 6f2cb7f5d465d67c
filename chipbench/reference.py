"""The plain float32 reference that decides `correct`.

A dense decoder as published (pre-RMSNorm, GQA attention with rotate-half
RoPE, SwiGLU MLP, untied LM head), written here in jax.numpy at float32
with `Precision.HIGHEST` matmuls, and run layer by layer over whole
sequences so it fits beside what is left on the device. It imports
nothing of the program and takes none of its arrays: the weights are
regenerated from the seed (`weights.py`).

`forward_rows` runs the reference over each prompt followed by the tokens
the program served, and returns the logits rows that predicted each
served token. `served_gaps` is the number that is compared: by how much
a served token's logit lies below the reference's best at its position,
in units of that row's standard deviation. With `control=True` the same
pass also runs the control: the reference with every linear layer
computed in fp8 (e4m3, per-tensor weight scales, per-row activation
scales), the precision a later change would be tempted to serve in.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

from chipbench.model import Dims
from chipbench.weights import layer_f32, seed_key, shared_f32

FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(a, axis=None):
    """Round to fp8 e4m3 with a scale per tensor (axis None) or per row
    (axis -1), and back to f32."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rope(x, theta: float):
    """x: (S, heads, dh) at positions 0..S-1, rotate-half convention."""
    import jax.numpy as jnp
    S, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, offset, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset)


@functools.lru_cache(maxsize=None)
def _block(d: Dims, fp8: bool):
    """One decoder layer over a whole sequence (S, D) -> (S, D)."""
    import jax
    import jax.numpy as jnp
    HI = jax.lax.Precision.HIGHEST
    G = d.n_heads // d.n_kv_heads

    def mm(eq, a, w):
        if fp8:
            a = _q8(a, axis=-1)
        return jnp.einsum(eq, a, w, precision=HI)

    @jax.jit
    def block(x, W):
        if fp8:
            W = {k: (_q8(v) if v.ndim > 1 else v) for k, v in W.items()}
        S = x.shape[0]
        h = _rms(x, W["ln1"], d.norm_eps)
        q = _rope(mm("sd,dhk->shk", h, W["attn/wq"]), d.rope_theta)
        k = _rope(mm("sd,dhk->shk", h, W["attn/wk"]), d.rope_theta)
        v = mm("sd,dhk->shk", h, W["attn/wv"])
        qg = q.reshape(S, d.n_kv_heads, G, d.head_dim)
        s = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) \
            * d.head_dim ** -0.5
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
        o = o.reshape(S, d.n_heads, d.head_dim)
        x = x + mm("shk,hkd->sd", o, W["attn/wo"])
        h = _rms(x, W["ln2"], d.norm_eps)
        g = jax.nn.silu(mm("sd,df->sf", h, W["mlp/wi_gate"])) \
            * mm("sd,df->sf", h, W["mlp/wi_up"])
        return x + mm("sf,fd->sd", g, W["mlp/wo"])
    return block


@functools.lru_cache(maxsize=None)
def _head(d: Dims, fp8: bool):
    import jax
    import jax.numpy as jnp
    HI = jax.lax.Precision.HIGHEST

    @jax.jit
    def head(x, rows, final_norm, lm_head):
        h = _rms(x[rows], final_norm, d.norm_eps)
        w = lm_head[:, :d.vocab]
        if fp8:
            h, w = _q8(h, axis=-1), _q8(w)
        return jnp.einsum("sd,dv->sv", h, w, precision=HI)
    return head


def forward_rows(d: Dims, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                          np.ndarray]],
                 length: int, *, control: bool = False):
    """seqs: (tokens, rows) pairs; each sequence is right-padded to
    `length` (causal attention keeps the pad out of earlier rows), and the
    logits of `rows` are returned: a list of (len(rows), vocab) f32
    arrays, and with `control=True` a second list from the fp8 control."""
    import jax.numpy as jnp
    key = seed_key(seed)
    sh = shared_f32(d, key)
    streams = [False] + ([True] if control else [])
    xs = {}
    for fp8 in streams:
        xs[fp8] = []
        for toks, _ in seqs:
            if len(toks) > length:
                raise ValueError(f"sequence of {len(toks)} > {length}")
            pad = np.zeros(length, np.int32)
            pad[:len(toks)] = toks
            xs[fp8].append(sh["embedding"][jnp.asarray(pad)])
    for layer in range(d.n_layers):
        W = layer_f32(d, key, layer)
        for fp8 in streams:
            block = _block(d, fp8)
            xs[fp8] = [block(x, W) for x in xs[fp8]]
        del W
    out = []
    for fp8 in streams:
        head = _head(d, fp8)
        out.append([np.asarray(head(x, jnp.asarray(rows, jnp.int32),
                                    sh["final_norm"], sh["lm_head"]))
                    for x, (_, rows) in zip(xs[fp8], seqs)])
    return out if control else out[0]


def served_gaps(rows: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per served token: reference best logit minus the served token's
    logit, in units of the row's standard deviation. 0 where the served
    token is the reference's own greedy choice."""
    rows = np.asarray(rows, np.float64)
    best = rows.max(axis=-1)
    got = rows[np.arange(len(served)), np.asarray(served)]
    return (best - got) / rows.std(axis=-1)


def control_gaps(rows: np.ndarray, control_rows: np.ndarray) -> np.ndarray:
    """The same gap for the token the control would put first."""
    return served_gaps(rows, np.argmax(control_rows, axis=-1))


def request_sequences(prompts: List[np.ndarray],
                      outputs: List[List[int]]):
    """(tokens, rows) for each served request: the prompt and every served
    token but the last as input, and the rows that predicted each served
    token (the prompt's last position onward)."""
    seqs = []
    for p, out in zip(prompts, outputs):
        toks = np.concatenate([np.asarray(p, np.int32),
                               np.asarray(out[:-1], np.int32)])
        rows = np.arange(len(p) - 1, len(p) - 1 + len(out))
        seqs.append((toks, rows))
    return seqs

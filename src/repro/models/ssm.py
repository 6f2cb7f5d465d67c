"""State-space / linear-recurrence blocks.

* RWKV6 ("Finch") time-mix with **data-dependent decay** (the paper's headline
  feature) + channel-mix FFN. [arXiv:2404.05892]
* Mamba-style selective-SSM heads used by Hymba's hybrid blocks.
  [arXiv:2411.13676]

Projections are computed for the whole sequence in parallel (MXU-friendly);
only the O(dh^2)-per-step recurrence runs under ``lax.scan``. The Pallas kernel
(kernels/rwkv6_scan) keeps that recurrence's state in VMEM across the time loop.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.spec import ParamSpec
from repro.models.modules import rms_norm


# ----------------------------------------------------------------------------
# RWKV6
# ----------------------------------------------------------------------------
def rwkv_timemix_specs(d: int, n_heads: int, head_dim: int,
                       decay_lora: int = 64) -> dict:
    return {
        "mu_r": ParamSpec((d,), ("embed",), init="small"),
        "mu_k": ParamSpec((d,), ("embed",), init="small"),
        "mu_v": ParamSpec((d,), ("embed",), init="small"),
        "mu_g": ParamSpec((d,), ("embed",), init="small"),
        "mu_w": ParamSpec((d,), ("embed",), init="small"),
        "wr": ParamSpec((d, d), ("embed", "ffn")),
        "wk": ParamSpec((d, d), ("embed", "ffn")),
        "wv": ParamSpec((d, d), ("embed", "ffn")),
        "wg": ParamSpec((d, d), ("embed", "ffn")),
        "wo": ParamSpec((d, d), ("ffn", "embed")),
        # data-dependent decay: w_t = exp(-exp(w0 + tanh(x_w A) B))
        "w0": ParamSpec((d,), ("embed",), init="small"),
        "wA": ParamSpec((d, decay_lora), ("embed", None), init="small"),
        "wB": ParamSpec((decay_lora, d), (None, "embed"), init="small"),
        "u": ParamSpec((n_heads, head_dim), (None, None), init="small"),
        "ln_out": ParamSpec((d,), ("embed",), init="zeros"),
    }


def rwkv_channelmix_specs(d: int, d_ff: int) -> dict:
    return {
        "mu_k": ParamSpec((d,), ("embed",), init="small"),
        "mu_r": ParamSpec((d,), ("embed",), init="small"),
        "wk": ParamSpec((d, d_ff), ("embed", "ffn")),
        "wv": ParamSpec((d_ff, d), ("ffn", "embed")),
        "wr": ParamSpec((d, d), ("embed", None)),
    }


def _token_shift(x, last):
    """x: (B,S,D); last: (B,D) token preceding x[:,0]. Returns shifted seq + new last."""
    shifted = jnp.concatenate([last[:, None, :], x[:, :-1, :]], axis=1)
    return shifted, x[:, -1, :]


def _rwkv_proj(p, x, xs):
    def mix(mu):
        return x + mu.astype(x.dtype) * (xs - x)
    r = mix(p["mu_r"]) @ p["wr"]
    k = mix(p["mu_k"]) @ p["wk"]
    v = mix(p["mu_v"]) @ p["wv"]
    g = jax.nn.silu(mix(p["mu_g"]) @ p["wg"])
    xw = mix(p["mu_w"]).astype(jnp.float32)
    logw = p["w0"].astype(jnp.float32) + jnp.tanh(xw @ p["wA"].astype(jnp.float32)) \
        @ p["wB"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(logw))                  # (B,S,D) in (0,1)
    return r, k, v, g, w


def wkv_scan_ref(r, k, v, w, u, state):
    """Sequential WKV recurrence (the pure-jnp oracle for the Pallas kernel).

    r,k,v,w: (B, S, H, dh) [w fp32]; u: (H, dh); state: (B, H, dh, dh) fp32.
    Returns (out (B,S,H,dh) fp32, new_state).
      a_t = k_t^T v_t;  o_t = r_t (S + u*a_t);  S' = w_t*S_rows + a_t
    (decay applies along the k-index of the state.)
    """
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    uf = u.astype(jnp.float32)

    if r.shape[1] == 1:
        # single decode token: unrolled (a length-1 scan is pure overhead)
        r1, k1, v1, w1 = rf[:, 0], kf[:, 0], vf[:, 0], w[:, 0]
        a = k1[..., :, None] * v1[..., None, :]
        o = jnp.einsum("bhk,bhkd->bhd", r1,
                       state + uf[None, :, :, None] * a)
        return o[:, None], w1[..., :, None] * state + a

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                     # (B,H,dh)
        a = k_t[..., :, None] * v_t[..., None, :]    # (B,H,dh,dh)
        o = jnp.einsum("bhk,bhkd->bhd", r_t, S + uf[None, :, :, None] * a)
        S = w_t[..., :, None] * S + a
        return S, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, w))
    state, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1), state


def rwkv_timemix(p, x, last_x, state, *, n_heads: int, head_dim: int,
                 norm_eps: float, impl: str = "ref"):
    """x: (B,S,D). Returns (out, new_last_x, new_state)."""
    B, S, D = x.shape
    xs, new_last = _token_shift(x, last_x)
    r, k, v, g, w = _rwkv_proj(p, x, xs)
    hd = (B, S, n_heads, head_dim)
    r, k, v, w = (t.reshape(hd) for t in (r, k, v, w))
    if impl == "pallas":
        from repro.kernels.rwkv6_scan import ops as wkv_ops
        out, state = wkv_ops.wkv(r, k, v, w, p["u"], state)
    else:
        out, state = wkv_scan_ref(r, k, v, w, p["u"], state)
    out = rms_norm(out.reshape(B, S, D).astype(x.dtype), p["ln_out"], norm_eps)
    return (out * g) @ p["wo"], new_last, state


def rwkv_channelmix(p, x, last_x):
    xs, new_last = _token_shift(x, last_x)
    xk = x + p["mu_k"].astype(x.dtype) * (xs - x)
    xr = x + p["mu_r"].astype(x.dtype) * (xs - x)
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"]), new_last


# ----------------------------------------------------------------------------
# Mamba-style selective SSM heads (Hymba)
# ----------------------------------------------------------------------------
def mamba_head_specs(d: int, n_heads: int, head_dim: int, state: int,
                     conv_k: int = 4) -> dict:
    d_inner = n_heads * head_dim
    return {
        "in_x": ParamSpec((d, d_inner), ("embed", "ffn")),
        "in_z": ParamSpec((d, d_inner), ("embed", "ffn")),
        "conv": ParamSpec((conv_k, d_inner), (None, "ffn"), init="small"),
        "w_dt": ParamSpec((d, n_heads), ("embed", None), init="small"),
        "dt_bias": ParamSpec((n_heads,), (None,), init="small"),
        "w_B": ParamSpec((d, state), ("embed", None), init="small"),
        "w_C": ParamSpec((d, state), ("embed", None), init="small"),
        "A_log": ParamSpec((n_heads,), (None,), init="small"),
        "D_skip": ParamSpec((n_heads,), (None,), init="small"),
        "ln": ParamSpec((d_inner,), ("ffn",), init="zeros"),
    }


def _causal_conv(x, kernel, conv_state):
    """Depthwise causal conv. x: (B,S,C), kernel: (K,C), conv_state: (B,K-1,C)."""
    K = kernel.shape[0]
    xp = jnp.concatenate([conv_state, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * kernel[i] for i in range(K))
    return out, xp[:, -(K - 1):, :] if K > 1 else conv_state


def ssm_scan_ref(xh, dt, B_in, C_in, A, state):
    """Selective scan. xh: (B,S,H,dh); dt: (B,S,H); B_in/C_in: (B,S,N);
    A: (H,) negative; state: (B,H,N,dh) fp32."""
    decay = jnp.exp(A[None, None, :, None] * dt[..., None])        # (B,S,H,1)

    def step(h, inp):
        x_t, dt_t, b_t, c_t, dec_t = inp
        dbx = (dt_t[..., None, None] * b_t[:, None, :, None]
               * x_t[..., None, :].astype(jnp.float32))            # (B,H,N,dh)
        h = dec_t[..., None] * h + dbx
        y = jnp.einsum("bn,bhnd->bhd", c_t, h)
        return h, y

    xs = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B_in.astype(jnp.float32), 1, 0),
          jnp.moveaxis(C_in.astype(jnp.float32), 1, 0),
          jnp.moveaxis(decay, 1, 0))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def mamba_forward(p, x, conv_state, ssm_state, *, n_heads: int, head_dim: int,
                  ssm_size: int, norm_eps: float, impl: str = "ref"):
    """x: (B,S,D) -> (out_heads (B,S,H*dh), new_conv_state, new_ssm_state)."""
    B, S, D = x.shape
    xi = x @ p["in_x"]
    z = x @ p["in_z"]
    xi, conv_state = _causal_conv(xi, p["conv"], conv_state)
    xi = jax.nn.silu(xi)
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    Bm = x @ p["w_B"]
    Cm = x @ p["w_C"]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xi.reshape(B, S, n_heads, head_dim)
    if impl == "pallas":
        from repro.kernels.ssm_scan import ops as ssm_ops
        y, ssm_state = ssm_ops.ssm_scan(xh, dt, Bm, Cm, A, ssm_state)
    else:
        y, ssm_state = ssm_scan_ref(xh, dt, Bm, Cm, A, ssm_state)
    y = y + p["D_skip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(B, S, -1).astype(x.dtype)
    y = rms_norm(y, p["ln"], norm_eps) * jax.nn.silu(z)
    return y, conv_state, ssm_state

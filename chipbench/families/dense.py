"""The dense decoder as published (InternLM2, Qwen2): pre-RMSNorm, GQA
attention with rotate-half RoPE, SwiGLU MLP, untied LM head. Every layer
is alike, in the program's one `layers` stack. What a family gives is
listed in `chipbench/model.py`.

Conventions of the program's layout: RMSNorm weights are stored as
offsets from one (`x * (1 + s)`); attention weights are per head, wq
(D, H, dh), wk/wv (D, KV, dh), wo (H, dh, D); MLP wi_gate/wi_up (D, F),
wo (F, D); the embedding (PV, D) and LM head (D, PV) are padded to PV
rows.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

from chipbench.model import KernelLayer, Shape, shape_fields
from chipbench.reference import rms, rope
from chipbench.weights import NORM_STD, QK_GAIN

Leaves = Dict[str, Tuple[Tuple[int, ...], float]]


@dataclass(frozen=True)
class Dims(Shape):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float


def dims(conf: dict) -> Dims:
    if conf.get("tie_word_embeddings"):
        raise ValueError(f"{conf['name']}: tied embeddings are not supported")
    return Dims(
        **shape_fields(conf), n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim",
                          conf["hidden_size"] // conf["num_attention_heads"]),
        d_ff=conf["intermediate_size"], rope_theta=float(conf["rope_theta"]))


def model_config(d: Dims):
    """The program's ModelConfig for these widths (imports the program)."""
    from repro.configs.base import AttnKind, Family, ModelConfig
    return ModelConfig(
        name=d.name, family=Family.DENSE, n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        attn_kind=AttnKind.FULL, rope_theta=d.rope_theta,
        norm_eps=d.norm_eps)


# -- the parameter tree ------------------------------------------------------

def shared_leaves(d: Dims) -> Leaves:
    PV, D = d.padded_vocab, d.d_model
    return {
        "embedding": ((PV, D), 1.0),
        "lm_head": ((D, PV), D ** -0.5),
        "final_norm": ((D,), NORM_STD),
    }


def stacks(d: Dims) -> Dict[str, range]:
    return {"layers": range(d.n_layers)}


def layer_leaves(d: Dims, layer: int) -> Leaves:
    D, H, KV, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    return {
        "ln1": ((D,), NORM_STD),
        "attn/wq": ((D, H, dh), QK_GAIN * D ** -0.5),
        "attn/wk": ((D, KV, dh), D ** -0.5),
        "attn/wv": ((D, KV, dh), D ** -0.5),
        "attn/wo": ((H, dh, D), (H * dh) ** -0.5),
        "ln2": ((D,), NORM_STD),
        "mlp/wi_gate": ((D, F), D ** -0.5),
        "mlp/wi_up": ((D, F), D ** -0.5),
        "mlp/wo": ((F, D), F ** -0.5),
    }


# -- the reference -----------------------------------------------------------

def layer_kind(d: Dims, layer: int) -> str:
    return "decoder"


def attention(d: Dims, h, W, mm):
    """Causal GQA self-attention of the normed sequence h: (S, D)."""
    import jax
    import jax.numpy as jnp
    HI = jax.lax.Precision.HIGHEST
    S, G = h.shape[0], d.n_heads // d.n_kv_heads
    q = rope(mm("sd,dhk->shk", h, W["attn/wq"]), d.rope_theta)
    k = rope(mm("sd,dhk->shk", h, W["attn/wk"]), d.rope_theta)
    v = mm("sd,dhk->shk", h, W["attn/wv"])
    qg = q.reshape(S, d.n_kv_heads, G, d.head_dim)
    s = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) \
        * d.head_dim ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
    o = o.reshape(S, d.n_heads, d.head_dim)
    return mm("shk,hkd->sd", o, W["attn/wo"])


def mlp(h, W, mm):
    """SwiGLU of the normed sequence h: (S, D)."""
    import jax
    g = jax.nn.silu(mm("sd,df->sf", h, W["mlp/wi_gate"])) \
        * mm("sd,df->sf", h, W["mlp/wi_up"])
    return mm("sf,fd->sd", g, W["mlp/wo"])


def block(d: Dims, kind: str, x, W, mm):
    x = x + attention(d, rms(x, W["ln1"], d.norm_eps), W, mm)
    return x + mlp(rms(x, W["ln2"], d.norm_eps), W, mm)


def head(d: Dims, x, shared, mm):
    h = rms(x, shared["final_norm"], d.norm_eps)
    return mm("sd,dv->sv", h, shared["lm_head"][:, :d.vocab])


# -- the counts --------------------------------------------------------------

def layer_matmul_flops(d: Dims) -> float:
    """Per token per layer: q, k, v, o projections and the gated MLP."""
    D, H, KV, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    return 2.0 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)


def attn_flops(d: Dims, keys: float) -> float:
    """Per query per layer: scores and the weighted sum over `keys`."""
    return 4.0 * d.n_heads * d.head_dim * keys


def decode_flops(d: Dims, ctx: int) -> float:
    """One decoded token whose query sees ctx + 1 keys."""
    return d.n_layers * (layer_matmul_flops(d) + attn_flops(d, ctx + 1)) \
        + 2.0 * d.d_model * d.vocab


def prefill_flops(d: Dims, n: int) -> float:
    """A prompt of n tokens, causal, logits at its last position."""
    return d.n_layers * (n * layer_matmul_flops(d)
                         + attn_flops(d, n * (n + 1) / 2.0)) \
        + 2.0 * d.d_model * d.vocab


def kernel_layers(d: Dims) -> List[KernelLayer]:
    return [KernelLayer(d.n_heads, d.n_kv_heads, d.head_dim)] * d.n_layers

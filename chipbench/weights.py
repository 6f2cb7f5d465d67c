"""Seeded random weights, made on the device.

The benchmark makes the weights itself, hands them to the program in the
program's parameter layout (`program_params`, one jitted call, bf16 as
served), and regenerates the same values layer by layer for the
reference (`layer_f32`, `shared_f32`), so the reference takes nothing the
program has made. Every leaf is drawn from its own key, folded from the
seed by leaf index and layer, so one layer can be drawn alone.

Conventions of the program's layout that the reference must know:
  * RMSNorm weights are stored as offsets from one (`x * (1 + s)`);
  * attention weights are per head: wq (D, H, dh), wk/wv (D, KV, dh),
    wo (H, dh, D); MLP wi_gate/wi_up (D, F), wo (F, D);
  * the embedding (PV, D) and LM head (D, PV) are padded to PV rows.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

from chipbench.model import Dims

NORM_STD = 0.05        # spread of the stored norm offsets
# Query gain: attention scores then spread with a standard deviation of
# about QK_GAIN, so each query leans on a few keys (as trained models do)
# and the tokens depend on the cache, not only on the last token: a fault
# in prefill, the cache hand-off or decode attention then changes what is
# served. Much sharper attention makes the random network chaotic with
# depth: at 4, bf16 rounding alone moves the 24-layer residual stream by a
# third and reads as high as an fp8 model would; at 2 it settles near 2%.
QK_GAIN = 2.0


def seed_key(seed: int):
    """A threefry key from any whole number: jax.random.PRNGKey keeps
    only the low 32 bits of a large seed, so seeds are hashed first."""
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(words, jnp.uint32)


def layer_leaves(d: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Per-layer leaves: path -> (shape, std)."""
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


def shared_leaves(d: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    PV, D = d.padded_vocab, d.d_model
    return {
        "embedding": ((PV, D), 1.0),
        "lm_head": ((D, PV), D ** -0.5),
        "final_norm": ((D,), NORM_STD),
    }


def _draw(key, index: int, layer, shape, std):
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(
        jnp.bfloat16)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def program_params(d: Dims, seed: int):
    """The whole parameter tree in the program's layout, bf16, made on
    the default device in one jitted call."""
    import jax
    import jax.numpy as jnp

    def make(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(
                sorted(shared_leaves(d).items())):
            flat[path] = _draw(key, i, 0, shape, std)
        base = len(shared_leaves(d))
        layers = {}
        for i, (path, (shape, std)) in enumerate(
                sorted(layer_leaves(d).items())):
            layers[path] = jax.vmap(
                lambda l, i=i, shape=shape, std=std:
                _draw(key, base + i, l, shape, std))(
                    jnp.arange(d.n_layers))
        flat.update({"layers/" + p: v for p, v in layers.items()})
        return _nest(flat)

    return jax.jit(make)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_maker(d: Dims):
    import jax
    import jax.numpy as jnp
    base = len(shared_leaves(d))

    @jax.jit
    def make(key, layer):
        return {path: _draw(key, base + i, layer, shape, std).astype(
                    jnp.float32)
                for i, (path, (shape, std)) in enumerate(
                    sorted(layer_leaves(d).items()))}
    return make


@functools.lru_cache(maxsize=None)
def _shared_maker(d: Dims):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        return {path: _draw(key, i, 0, shape, std).astype(jnp.float32)
                for i, (path, (shape, std)) in enumerate(
                    sorted(shared_leaves(d).items()))}
    return make


def layer_f32(d: Dims, key, layer: int) -> dict:
    """One layer's weights as the program holds them, widened to f32."""
    import jax.numpy as jnp
    return _layer_maker(d)(key, jnp.int32(layer))


def shared_f32(d: Dims, key) -> dict:
    """The embedding, LM head and final norm, widened to f32."""
    return _shared_maker(d)(key)

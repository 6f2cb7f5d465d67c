"""Seeded random weights, made on the device.

The benchmark makes the weights itself, hands them to the program in the
program's parameter layout (`program_params`, one jitted call, bf16 as
served), and regenerates the same values layer by layer for the
reference (`layer_f32`, `shared_f32`), so the reference takes nothing the
program has made. The leaves, their shapes and spreads and the stacks
they sit in are the configuration's family's (`chipbench/model.py`).
Every leaf is drawn from its own key: a shared leaf's from its index in
sorted order, a layer leaf's from len(shared) + its index in its layer's
sorted leaf set, folded with the global layer number. So one layer can
be drawn alone.
"""
from __future__ import annotations

import functools

import numpy as np

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


def program_params(d, seed: int):
    """The whole parameter tree in the program's layout, bf16, made on
    the default device in one jitted call."""
    import jax
    import jax.numpy as jnp
    fam = d.family
    shared = sorted(fam.shared_leaves(d).items())
    stacks = fam.stacks(d)
    if sorted(l for r in stacks.values() for l in r) != list(
            range(d.n_layers)):
        raise ValueError(f"{d.name}: stacks {stacks} do not hold each of "
                         f"the {d.n_layers} layers once")
    for name, r in stacks.items():
        if any(fam.layer_leaves(d, l) != fam.layer_leaves(d, r[0])
               for l in r):
            raise ValueError(f"{d.name}: the layers of stack {name!r} "
                             f"differ in their leaves")

    def make(key):
        flat = {path: _draw(key, i, 0, shape, std)
                for i, (path, (shape, std)) in enumerate(shared)}
        for name, r in stacks.items():
            layers = jnp.arange(r.start, r.stop)
            for i, (path, (shape, std)) in enumerate(
                    sorted(fam.layer_leaves(d, r.start).items())):
                flat[name + "/" + path] = jax.vmap(
                    lambda l, i=i, shape=shape, std=std:
                    _draw(key, len(shared) + i, l, shape, std))(layers)
        return _nest(flat)

    return jax.jit(make)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_maker(base: int, leaves: tuple):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, layer):
        return {path: _draw(key, base + i, layer, shape, std).astype(
                    jnp.float32)
                for i, (path, (shape, std)) in enumerate(leaves)}
    return make


@functools.lru_cache(maxsize=None)
def _shared_maker(leaves: tuple):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        return {path: _draw(key, i, 0, shape, std).astype(jnp.float32)
                for i, (path, (shape, std)) in enumerate(leaves)}
    return make


def layer_f32(d, key, layer: int) -> dict:
    """One layer's weights as the program holds them, widened to f32."""
    import jax.numpy as jnp
    fam = d.family
    leaves = tuple(sorted(fam.layer_leaves(d, layer).items()))
    return _layer_maker(len(fam.shared_leaves(d)), leaves)(
        key, jnp.int32(layer))


def shared_f32(d, key) -> dict:
    """The embedding, final norm and LM head (if any), widened to f32."""
    return _shared_maker(tuple(sorted(d.family.shared_leaves(d).items())))(
        key)

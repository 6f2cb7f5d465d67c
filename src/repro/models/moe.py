"""Mixture-of-Experts layer: top-k routing + sort-based grouped expert compute.

TPU-native formulation (MegaBlocks/MaxText-style, no (T, E, C) dispatch einsum):
tokens are *sorted by expert id*, packed into a capacity-bounded (E, C, D)
buffer, experts run as one batched einsum, and outputs scatter back weighted by
router probabilities. Under a mesh, the layer runs inside ``shard_map``:
routing is replicated per data-shard, each model-shard computes only its
E/|model| experts, and the combine is a single ``psum`` over the model axis —
the same collective cost as a Megatron MLP, with no global sort.

This matters for LIME: for MoE architectures the expert tensors dominate layer
memory (p_M ~ 0.97-0.99), so the paper's fine-grained MHA/MLP offload split
becomes an attention/expert split (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.spec import ParamSpec
from repro.models.modules import mlp, mlp_specs


def moe_specs(d_model: int, n_experts: int, moe_d_ff: int,
              n_shared: int) -> dict:
    out = {
        "router": ParamSpec((d_model, n_experts), ("embed", None),
                            dtype=jnp.float32, init="small"),
        "wi_gate": ParamSpec((n_experts, d_model, moe_d_ff),
                             ("expert", "embed", None)),
        "wi_up": ParamSpec((n_experts, d_model, moe_d_ff),
                           ("expert", "embed", None)),
        "wo": ParamSpec((n_experts, moe_d_ff, d_model),
                        ("expert", None, "embed")),
    }
    if n_shared:
        out["shared"] = mlp_specs(d_model, n_shared * moe_d_ff)
    return out


def _route(router, x_flat, top_k: int):
    """Returns (weights (T,K) f32, ids (T,K) i32, probs (T,E) f32)."""
    logits = (x_flat.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, ids, probs


def _group_tokens(ids, capacity: int, n_experts: int):
    """Sort token-slots by expert; compute packed buffer indices.

    ids: (T, K) -> returns (order (T*K,), buf_idx (T*K,), keep (T*K,)).
    buf_idx indexes an (E*C + 1)-row buffer; dropped slots go to the dump row.
    """
    TK = ids.size
    e_flat = ids.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    # rank within expert = position - first occurrence of this expert id
    first = jnp.searchsorted(e_sorted, e_sorted, side="left")
    rank = jnp.arange(TK) - first
    keep = rank < capacity
    buf_idx = jnp.where(keep, e_sorted * capacity + rank, n_experts * capacity)
    return order, buf_idx, keep


def _expert_ffn(wg, wu, wo, buf):
    """buf: (E_l, C, D) -> (E_l, C, D)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", g * u, wo)


def _moe_local(params, x_flat, *, top_k: int, n_experts: int,
               capacity_factor: float, expert_slice=None, n_local: int = 0,
               constraint_mesh=None):
    """MoE on a local token shard. expert_slice: traced start index of this
    shard's experts (None = all experts local). constraint_mesh: GSPMD-auto
    context — pin expert-dim sharding instead of manual collectives."""
    T, D = x_flat.shape
    weights, ids, probs = _route(params["router"], x_flat, top_k)
    cap = max(1, int(T * top_k / n_experts * capacity_factor + 0.999))
    order, buf_idx, keep = _group_tokens(ids, cap, n_experts)
    tok = jnp.repeat(jnp.arange(T), top_k)[order]
    w_sorted = weights.reshape(-1)[order]

    dump = jnp.zeros((n_experts * cap + 1, D), x_flat.dtype)
    buf = dump.at[buf_idx].set(x_flat[tok] * keep[:, None].astype(x_flat.dtype))
    buf = buf[:-1].reshape(n_experts, cap, D)

    if expert_slice is None:
        if constraint_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as _P
            pin = lambda t: jax.lax.with_sharding_constraint(
                t, NamedSharding(constraint_mesh, _P("model")))
            buf = pin(buf)
        y = _expert_ffn(params["wi_gate"], params["wi_up"], params["wo"], buf)
        if constraint_mesh is not None:
            y = pin(y)
        y = jnp.concatenate([y.reshape(-1, D),
                             jnp.zeros((1, D), x_flat.dtype)], 0)
    else:
        buf_l = jax.lax.dynamic_slice_in_dim(buf, expert_slice * n_local,
                                             n_local, axis=0)
        y_l = _expert_ffn(params["wi_gate"], params["wi_up"], params["wo"], buf_l)
        # place local experts' outputs back at their global offset
        y = jnp.zeros((n_experts, cap, D), x_flat.dtype)
        y = jax.lax.dynamic_update_slice_in_dim(y, y_l, expert_slice * n_local, 0)
        y = jnp.concatenate([y.reshape(-1, D),
                             jnp.zeros((1, D), x_flat.dtype)], 0)

    gathered = y[buf_idx] * (w_sorted * keep).astype(x_flat.dtype)[:, None]
    out = jnp.zeros_like(x_flat).at[tok].add(gathered)

    # Switch-style load-balance aux loss (per shard; psum'd by caller if needed)
    me = probs.mean(0)                                       # (E,)
    ce = jnp.zeros((n_experts,)).at[ids.reshape(-1)].add(1.0) / (T * top_k)
    aux = n_experts * jnp.sum(me * ce)
    return out, aux


def moe_forward(params, x, *, cfg, mesh=None, capacity_factor: float = 1.25,
                mode: str = "shard_map"):
    """x: (B, S, D). Returns (out, aux_loss).

    mode="shard_map": explicit manual experts over 'model' (train/prefill).
    mode="auto": GSPMD constraints only — for callers already inside a
    partial-auto shard_map (the LIME engine), where nesting manual
    collectives over 'model' is not an option. The constraint pins the
    expert einsum to expert-sharded compute; without it the partitioner
    all-gathers the expert weights (TBs for kimi-k2 — see EXPERIMENTS §Perf).
    """
    B, S, D = x.shape
    x_flat = x.reshape(-1, D)
    E, K = cfg.n_experts, cfg.top_k

    if mode == "auto" and mesh is not None and "model" in mesh.shape \
            and E % mesh.shape["model"] == 0:
        out_flat, aux = _moe_local(
            {k: params[k] for k in ("router", "wi_gate", "wi_up", "wo")},
            x_flat, top_k=K, n_experts=E, capacity_factor=capacity_factor,
            constraint_mesh=mesh)
        if "shared" in params:
            out_flat = out_flat + mlp(params["shared"], x_flat)
        return out_flat.reshape(B, S, D), aux

    if mode != "auto" and mesh is not None and "model" in mesh.shape \
            and mesh.shape["model"] > 1 \
            and E % mesh.shape["model"] == 0:
        n_local = E // mesh.shape["model"]
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        ba = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(ba, None),
                      {"router": P(None, None),
                       "wi_gate": P("model", None, None),
                       "wi_up": P("model", None, None),
                       "wo": P("model", None, None)}),
            out_specs=(P(ba, None), P()),
            check_vma=False)
        def _sharded(x_l, p_l):
            idx = jax.lax.axis_index("model")
            out, aux = _moe_local(p_l, x_l, top_k=K, n_experts=E,
                                  capacity_factor=capacity_factor,
                                  expert_slice=idx, n_local=n_local)
            out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(aux, "model")
            if ba is not None:
                aux = jax.lax.pmean(aux, ba)
            return out, aux

        core = {k: params[k] for k in ("router", "wi_gate", "wi_up", "wo")}
        out_flat, aux = _sharded(x_flat, core)
    else:
        out_flat, aux = _moe_local(
            {k: params[k] for k in ("router", "wi_gate", "wi_up", "wo")},
            x_flat, top_k=K, n_experts=E, capacity_factor=capacity_factor)

    if "shared" in params:
        out_flat = out_flat + mlp(params["shared"], x_flat)
    return out_flat.reshape(B, S, D), aux


def moe_forward_naive(params, x, *, cfg):
    """O(T*E) per-token oracle for tests: every expert on every token."""
    B, S, D = x.shape
    x_flat = x.reshape(-1, D)
    weights, ids, _ = _route(params["router"], x_flat, cfg.top_k)
    ys = _expert_ffn(params["wi_gate"], params["wi_up"], params["wo"],
                     jnp.broadcast_to(x_flat, (cfg.n_experts,) + x_flat.shape))
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (T,K,E)
    w_e = (weights[..., None] * onehot).sum(1)                      # (T,E)
    out = jnp.einsum("te,etd->td", w_e.astype(x.dtype), ys)
    if "shared" in params:
        out = out + mlp(params["shared"], x_flat)
    return out.reshape(B, S, D)

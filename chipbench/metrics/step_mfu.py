"""Model FLOP/s utilization of the whole serving step: the FLOPs the
model needs for the work finished in the traced part of the window
(prefill of each admitted prompt, and each decoded token at its live
context), over that time, the chips and the chip's bf16 peak.

Counted from the configuration's shapes: the matmuls of every layer, the
LM head where logits are produced (every decoded token; a prompt's last
position), and attention over the keys each query sees (causal in
prefill). Padding slots, bubbles and recomputation are not model FLOPs."""


def layer_matmul_flops(d) -> float:
    """Per token per layer: q, k, v, o projections and the gated MLP."""
    D, H, KV, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    return 2.0 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)


def attn_flops(d, keys: float) -> float:
    """Per query per layer: scores and the weighted sum over `keys`."""
    return 4.0 * d.n_heads * d.head_dim * keys


def decode_flops(d, ctx: int) -> float:
    """One decoded token whose query sees ctx + 1 keys."""
    return d.n_layers * (layer_matmul_flops(d) + attn_flops(d, ctx + 1)) \
        + 2.0 * d.d_model * d.vocab


def prefill_flops(d, n: int) -> float:
    """A prompt of n tokens, causal, logits at its last position."""
    return d.n_layers * (n * layer_matmul_flops(d)
                         + attn_flops(d, n * (n + 1) / 2.0)) \
        + 2.0 * d.d_model * d.vocab


def window_flops(run, t0: float, t1: float) -> float:
    total = 0.0
    for a, b, kind, ctx in run.window.steps:
        if not t0 <= b <= t1:
            continue
        if kind == "decode":
            total += sum(decode_flops(run.dims, c) for c in ctx)
        else:
            total += sum(prefill_flops(run.dims, n) for n in ctx)
    return total


def read(run):
    if run.trace is None or run.traced_open_s is None:
        return None
    t0, t1 = run.traced_open_s, run.window.close_s
    flops = window_flops(run, t0, t1)
    if flops <= 0:
        return None
    return 100.0 * flops / ((t1 - t0) * run.chips
                            * run.peak["bf16_flops_per_s"])

"""Share of its roofline that the Pallas decode-attention kernel reaches:
the least time the chip could take for the attention the decode steps in
the traced window need, over the kernel's device time there.

Needed per live request, per step, in each layer the kernel serves (the
configuration's family lists them, `kernel_layers`), at live context c
and keys min(c + 1, the layer's window): bytes = K and V rows of those
keys (bf16) + the query in and the output out; FLOPs = scores and
weighted sum. Least time = max(FLOPs / bf16 peak, bytes / HBM
bandwidth). Cache rows past the live context or the window, padding
slots and bubbles are not needed work."""

from collections import Counter

KERNEL = "decode_attention"      # the Pallas custom call's HLO name


def needed_s(layer, keys: int, peak: dict) -> float:
    """One query at `keys` keys in one kernel-served layer (a
    model.KernelLayer, or any record with its heads and head width)."""
    kv_bytes = 2.0 * keys * layer.n_kv_heads * layer.head_dim * 2
    qo_bytes = 2.0 * layer.n_heads * layer.head_dim * 2
    flops = 4.0 * layer.n_heads * layer.head_dim * keys
    return max(flops / peak["bf16_flops_per_s"],
               (kv_bytes + qo_bytes) / peak["hbm_bytes_per_s"])


def step_needed_s(layers: Counter, ctx: int, peak: dict) -> float:
    """One decoded token at live context ctx, over the kernel-served
    layers counted by kind."""
    return sum(n * needed_s(a, ctx + 1 if a.window is None
                            else min(ctx + 1, a.window), peak)
               for a, n in layers.items())


def read(run):
    if run.trace is None or run.traced_open_s is None:
        return None
    t0, t1 = run.traced_open_s, run.window.close_s
    lo, hi = run.traced[0]
    kernel = sum(min(b, hi) - max(a, lo)
                 for evs in run.trace.ops.values() for n, a, b in evs
                 if KERNEL in n and b > lo and a < hi)
    layers = Counter(run.dims.family.kernel_layers(run.dims))
    need = sum(step_needed_s(layers, c, run.peak)
               for a, b, kind, ctx in run.window.steps
               if kind == "decode" and t0 <= b <= t1 for c in ctx)
    return 100.0 * need / kernel if kernel > 0 and need > 0 else None

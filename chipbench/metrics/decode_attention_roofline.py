"""Share of its roofline that the Pallas decode-attention kernel reaches:
the least time the chip could take for the attention the decode steps in
the traced window need, over the kernel's device time there.

Needed per live request, per layer, per step, at live context c (keys
c + 1): bytes = K and V rows of the live context (bf16) + the query in
and the output out; FLOPs = scores and weighted sum. Least time =
max(FLOPs / bf16 peak, bytes / HBM bandwidth). Cache rows past the live
context, padding slots and bubbles are not needed work."""

KERNEL = "decode_attention"      # the Pallas custom call's HLO name


def needed_s(d, keys: int, peak: dict) -> float:
    kv_bytes = 2.0 * keys * d.n_kv_heads * d.head_dim * 2
    qo_bytes = 2.0 * d.n_heads * d.head_dim * 2
    flops = 4.0 * d.n_heads * d.head_dim * keys
    return max(flops / peak["bf16_flops_per_s"],
               (kv_bytes + qo_bytes) / peak["hbm_bytes_per_s"])


def read(run):
    if run.trace is None or run.traced_open_s is None:
        return None
    t0, t1 = run.traced_open_s, run.window.close_s
    lo, hi = run.traced[0]
    kernel = sum(min(b, hi) - max(a, lo)
                 for evs in run.trace.ops.values() for n, a, b in evs
                 if KERNEL in n and b > lo and a < hi)
    need = sum(run.dims.n_layers * needed_s(run.dims, c + 1, run.peak)
               for a, b, kind, ctx in run.window.steps
               if kind == "decode" and t0 <= b <= t1 for c in ctx)
    return 100.0 * need / kernel if kernel > 0 and need > 0 else None

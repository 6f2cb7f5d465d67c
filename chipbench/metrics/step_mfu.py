"""Model FLOP/s utilization of the whole serving step: the FLOPs the
model needs for the work finished in the traced part of the window
(prefill of each admitted prompt, and each decoded token at its live
context), over that time, the chips and the chip's bf16 peak.

Counted from the configuration's shapes by its family
(`families/<family>.py`): the matmuls of every layer (the active experts
only, where there are experts), the LM head where logits are produced
(every decoded token; a prompt's last position), and attention over the
keys each query sees (causal in prefill). Padding slots, bubbles and
recomputation are not model FLOPs."""


def decode_flops(d, ctx: int) -> float:
    """One decoded token whose query sees ctx + 1 keys."""
    return d.family.decode_flops(d, ctx)


def prefill_flops(d, n: int) -> float:
    """A prompt of n tokens, causal, logits at its last position."""
    return d.family.prefill_flops(d, n)


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

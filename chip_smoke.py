#!/usr/bin/env python3
"""Smoke run of the serving engine on TPU: the quickest proof that the
system still starts on the chip. Not a benchmark.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the four-stage pipeline on four chips

One process; it starts no children and reads no tune cache. It serves
internlm2-1.8b at its published width (24 layers, d_model 2048, 16/8
heads, vocab 92544) with seeded random bf16 weights through the objects
`launch/serve.py` builds — LimeServer -> ContinuousBatchingScheduler ->
EngineBackend -> InterleavedEngine (impl="pallas", serve.py's uniform
plan) — and serves the same greedy requests through the engine-less
single-device decode (`EngineBackend(engine=None)`, jnp reference
attention) on device 0 as the reference.

  default      --stages 1: plan seg=2 k_res=11 k_off=1, so the streamed
               layer fetch runs on one chip.
  --chips 4    --stages 4, n_mb 4: plan seg=2 k_res=2 k_off=1 — the
               all_to_all weight fetch and the ppermute activation ring.

Pass: at every compared step the engine's logits row agrees with the
reference's within bf16 tolerance (RMS difference <= REL_TOL, in units
of the reference row's RMS), and every greedy token equals the
reference's except at a near-tie: a step where the reference's margin
between its top-1 and the engine's token is below TIE_TOL (same units),
which differences of that size can flip. Near-ties are
counted, and a request's comparison ends at its first one (later tokens
follow a different context). The last line of stdout is one JSON object,
printed only when every check passed:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The compile cache is `$JAX_COMPILATION_CACHE_DIR` when set, else
`<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "internlm2-1.8b"
N_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 128, 32      # greedy
# The engine's Pallas attention keeps scores and softmax in f32; the
# reference rounds them to bf16. On an internlm2 config cut to d_model 256
# (CPU, 24 layers of random weights) that alone made decode rows differ by
# 0.05-0.15 RMS while tokens agree (with jnp attention in the engine they
# agree to 1e-6) — and by 1.3-1.5 RMS once the two contexts differ, which
# is also what a wrong layer or cache row gives. The largest entry of a
# difference is reported, not gated: over a 92544-entry row it is ~4.8x
# the RMS for noise alone. A token flip needs two entries to move apart by
# the margin: TIE_TOL is ~4 sigma of that at the RMS tolerance.
REL_TOL = 0.35
TIE_TOL = 2.0

def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-stage engine on one chip (default); "
                         "4: four-stage pipeline on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and prompts")
    return ap.parse_args(argv)


class LogitsRecorder:
    """Wraps one backend so every emitted token's logits row is kept, per
    request, in emission order (row j is what output[j] was sampled
    from). Rows stay on the device until compare() reads them."""

    def __init__(self, backend):
        self.rows = {}
        self._slot_rid = {}
        self._last = None
        V = backend.cfg.vocab_size
        sample, start, decode = (backend._sample, backend.start_batch,
                                 backend.decode_active)

        def _sample(logits):
            self._last = logits[:, :V]
            return sample(logits)

        def start_batch(reqs):
            out = start(reqs)
            self._slot_rid = {i: r.rid for i, r in enumerate(reqs)}
            for i, r in enumerate(reqs):
                self.rows.setdefault(r.rid, []).append(self._last[i])
            return out

        def decode_active(slots):
            out = decode(slots)
            for s in slots:
                self.rows[self._slot_rid[s]].append(self._last[s])
            return out

        backend._sample = _sample
        backend.start_batch = start_batch
        backend.decode_active = decode_active


def serve(srv, prompts, max_new: int):
    """Submit prompts, serve them, return (requests in submit order, wall
    seconds)."""
    reqs = [srv.queue.submit(p, max_new_tokens=max_new) for p in prompts]
    t0 = time.perf_counter()
    srv.serve_all()
    return reqs, time.perf_counter() - t0


def compare(eng_reqs, ref_reqs, eng_rec, ref_rec) -> dict:
    """The check of the module doc. Returns the steps compared, the
    near-ties, the failures, and the worst RMS and largest-entry logits
    differences in units of the reference row's RMS."""
    import numpy as np
    out = {"steps": 0, "near_ties": 0, "failures": [], "rms": 0.0,
           "max": 0.0}
    bad = out["failures"]
    for e, r in zip(eng_reqs, ref_reqs):
        eo, ro = list(e.output), list(r.output)
        e_rows, r_rows = eng_rec.rows.get(e.rid, []), ref_rec.rows.get(r.rid, [])
        if not ro or e.rejected or r.rejected or len(e_rows) < len(eo) \
                or len(r_rows) < len(ro):
            bad.append(f"req {r.rid}: missing output or logits")
            continue
        for j in range(min(len(eo), len(ro))):
            out["steps"] += 1
            rl = np.asarray(r_rows[j], np.float32)
            d = np.asarray(e_rows[j], np.float32) - rl
            rms = float(np.sqrt(np.mean(rl * rl)))
            d_rms = float(np.sqrt(np.mean(d * d))) / rms
            out["rms"] = max(out["rms"], d_rms)
            out["max"] = max(out["max"], float(np.abs(d).max()) / rms)
            if d_rms > REL_TOL:
                bad.append(f"req {r.rid} step {j}: logits differ by "
                           f"{d_rms:.3f} RMS")
                break
            if eo[j] != ro[j]:
                margin = float(rl[ro[j]] - rl[eo[j]]) / rms
                if margin < TIE_TOL:
                    out["near_ties"] += 1
                else:
                    bad.append(f"req {r.rid} step {j}: engine {eo[j]} vs "
                               f"reference {ro[j]}, reference margin "
                               f"{margin:.3f} RMS")
                break
        else:
            if len(eo) != len(ro):
                bad.append(f"req {r.rid}: lengths {len(eo)} vs {len(ro)}")
    return out


def run(cfg, chips: int, *, seed: int, n_requests: int, prompt_len: int,
        max_new: int, log=None) -> dict:
    """Serve through the engine and through the reference, compare, and
    return what was measured; out["failures"] lists every failed check."""
    import jax
    import numpy as np

    from repro.launch import serve as S
    from repro.launch.compile_cache import compile_stats
    from repro.serving import LimeServer, SamplerConfig

    args = S.parse_args([
        "--arch", cfg.name, "--stages", str(chips), "--impl", "pallas",
        "--pattern", "bursty", "--requests", str(n_requests),
        "--prompt-len", str(prompt_len), "--max-new", str(max_new),
        "--max-len", str(prompt_len + max_new), "--seed", str(seed)])
    S.resolve_stages(args, len(jax.devices()))
    out = {"stages": args.stages}

    t0 = time.perf_counter()
    srv = S.build_server(cfg, args, log=log)
    jax.block_until_ready(srv.params)
    out["build_s"] = time.perf_counter() - t0
    eng = srv.engine
    if eng is None or eng.impl != "pallas":
        raise RuntimeError("serve.py built no Pallas engine")
    out["plan"] = {"n_seg": eng.plan.n_seg, "k_res": eng.plan.k_res_list,
                   "k_off": eng.plan.k_off_list, "n_mb": eng.n_mb,
                   "fetch_mode": eng.fetch_mode}

    # the decode step (weight fetch + slot scan) as one program: the
    # Pallas kernels must be in it as Mosaic calls, not interpreted
    c0 = compile_stats()["compile_s"]
    text = eng.lower_step().compile().as_text()
    out["step_compile_s"] = compile_stats()["compile_s"] - c0
    out["tpu_custom_call"] = "tpu_custom_call" in text
    out["all_to_all"] = "all-to-all" in text
    out["collective_permute"] = "collective-permute" in text
    failures = [] if out["tpu_custom_call"] else \
        ["no Mosaic kernel in the decode step"]

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len, dtype=np.int32)
               for _ in range(n_requests)]
    ref = LimeServer(cfg, srv.params, engine=None, max_len=args.max_len,
                     pattern="sporadic" if srv.slots == 1 else "bursty",
                     sampler=SamplerConfig())
    if ref.slots != srv.slots:
        raise RuntimeError(f"reference batches {ref.slots} requests, the "
                           f"engine {srv.slots}")

    results = {}
    for name, server in (("engine", srv), ("reference", ref)):
        rec = LogitsRecorder(server.make_backend())
        c0 = compile_stats()["compile_s"]
        # warm-up: one request of the same shapes compiles every program
        _, warm_s = serve(server, prompts[:1], 2)
        reqs, wall = serve(server, prompts, max_new)
        n_tok = sum(len(r.output) for r in reqs)
        ttft = sorted(r.ttft_s for r in reqs)
        results[name] = (reqs, rec)
        out[name] = {"warmup_s": warm_s,
                     "compile_s": compile_stats()["compile_s"] - c0,
                     "wall_s": wall, "tokens": n_tok,
                     "tokens_per_s": n_tok / wall,
                     "ttft_p50_s": ttft[len(ttft) // 2],
                     "ttft_max_s": ttft[-1]}

    (e_reqs, e_rec), (r_reqs, r_rec) = results["engine"], results["reference"]
    out["check"] = compare(e_reqs, r_reqs, e_rec, r_rec)
    out["failures"] = failures + out["check"].pop("failures")
    out["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:max(chips, 1)]]
    out["cache"] = compile_stats()
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isfile(os.path.join(SRC, "repro", "launch", "serve.py")):
        fail(f"no repro package under {SRC}: run chip_smoke.py from a "
             f"checkout of the repository")
    sys.path.insert(0, SRC)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devs[0].platform!r}); this "
             f"smoke run has no CPU fallback")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices; "
             f"{len(devs)} exist")

    from repro.configs.registry import get_config
    from repro.obs.log import get_logger
    log = get_logger("chip_smoke")
    cfg = get_config(ARCH)
    print(f"chip_smoke: {ARCH} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, vocab "
          f"{cfg.vocab_size}) on {args.chips} x {devs[0].device_kind}; "
          f"compile cache {cache_dir}", flush=True)
    out = run(cfg, args.chips, seed=args.seed, n_requests=N_REQUESTS,
              prompt_len=PROMPT_LEN, max_new=MAX_NEW, log=log)
    for k in ("stages", "plan", "build_s", "step_compile_s",
              "tpu_custom_call", "all_to_all", "collective_permute"):
        print(f"  {k}: {out[k]}")
    for name in ("engine", "reference"):
        print(f"  {name} (smoke run, not a benchmark): {out[name]}")
    ck = out["check"]
    print(f"  greedy tokens: {ck['steps']} steps compared, identical except "
          f"{ck['near_ties']} near-tie step(s) (margin < {TIE_TOL} RMS); "
          f"logits differ by at most {ck['rms']:.4g} RMS (tolerance "
          f"{REL_TOL}), largest entry {ck['max']:.4g} RMS")
    print(f"  peak_bytes_in_use: {out['peak_bytes_in_use']}")
    print(f"  compile cache: {out['cache']}", flush=True)
    if out["failures"]:
        fail("; ".join(out["failures"]))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""LIME-Serve benchmark: request patterns through the serving stack
(EXPERIMENTS.md §Serving).

Arrival streams (serving/traffic.py) run through the continuous-batching
scheduler against either substrate and the run is reported as JSON:
ms/token, p50/p99 TTFT, p50/p99 end-to-end latency, token/request
throughput.

  # discrete-event substrate, default 4-device heterogeneous fleet (E3):
  python benchmarks/bench_serving.py --pattern sporadic --backend sim
  python benchmarks/bench_serving.py --pattern bursty   --backend sim
  python benchmarks/bench_serving.py --pattern poisson  --backend sim
  python benchmarks/bench_serving.py --pattern all      --backend sim

  # real execution (1-device smoke fallback; multi-device uses the engine):
  python benchmarks/bench_serving.py --pattern bursty --backend engine \
      --n-requests 6 --max-new 8

The headline sanity check the paper implies: bursty throughput >= sporadic
throughput on the same fleet (micro-batches amortize each segment's weight
streaming). `--pattern all` prints the comparison explicitly.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

PATTERN_CHOICES = ("sporadic", "bursty", "poisson", "trace",
                   "shared_prefix", "multiturn", "all")


def spec_config(args):
    """--spec: speculative decoding on both substrates (DESIGN.md §11)."""
    if not args.spec:
        return None
    from repro.specdec import SpecConfig
    return SpecConfig(k=args.spec_k, draft=args.spec_draft,
                      acceptance=args.spec_acceptance, seed=args.seed)


def build_sim_backend(args, slots: int):
    from repro.configs.registry import get_config
    from repro.core.cost_model import CostEnv, Workload
    from repro.core.profiles import (env_E1, env_E2, env_E3, env_lowmem,
                                     mbps, tpu_pod_stage_devices)
    from repro.serving import SimBackend

    fleets = {"E1": env_E1, "E2": env_E2, "E3": env_E3,
              "lowmem1": lambda: env_lowmem(1),
              "tpu4": lambda: tpu_pod_stage_devices(4)}
    devices = fleets[args.fleet]()
    cfg = get_config(args.arch)
    w = Workload(cfg, mb=1, ctx=args.prompt_len, n_micro=slots)
    env = CostEnv(devices, mbps(args.bw_mbps), w)
    return SimBackend(env, n_slots=slots, prompt_tokens=args.prompt_len,
                      spec=spec_config(args))


def build_engine_backend(args, slots: int, max_prompt: int = 0):
    import jax

    from repro.configs.registry import get_smoke_config
    from repro.models import model as M
    from repro.serving import EngineBackend, SamplerConfig

    engine_arch = args.arch if args.arch in ("gemma3-1b", "internlm2-1.8b") \
        else "gemma3-1b"
    if engine_arch != args.arch:
        print(f"# --backend engine runs smoke configs only: benchmarking "
              f"{engine_arch} (smoke), not {args.arch}", file=sys.stderr)
    cfg = get_smoke_config(engine_arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # size the per-slot cache off the stream's longest prompt — multiturn
    # conversations outgrow the nominal --prompt-len
    max_len = max(max_prompt, args.prompt_len) + args.max_new + 8
    engine = None
    n_dev = len(jax.devices())
    if n_dev >= 4 and n_dev % 4 == 0:   # make_mesh needs prod == n_dev
        import dataclasses

        from repro.core.engine import InterleavedEngine, UniformPlan
        cfg = dataclasses.replace(cfg, n_layers=8)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, n_dev // 4), ("data", "model"))
        plan = UniformPlan(4, 2, 0, 1)
        engine = InterleavedEngine(cfg, mesh, plan, n_mb=slots, mb=1,
                                   max_len=max_len)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
    return EngineBackend(cfg, params, engine=engine, n_slots=slots,
                         max_len=max_len,
                         sampler=SamplerConfig(), spec=spec_config(args),
                         prefix_cache=(args.prefix_cache and engine is None),
                         prefill_chunk_tokens=args.prefill_chunk or 0,
                         page_size=args.page_size)


def trace_path(base: str, pattern: str, multi: bool) -> str:
    """Per-pattern trace file when --pattern all: out.json ->
    out.sporadic.json (one Perfetto file per run, not a concatenation)."""
    if not multi:
        return base
    root, ext = os.path.splitext(base)
    return f"{root}.{pattern}{ext or '.json'}"


def run_pattern(args, pattern: str, trace_out: str = None) -> dict:
    from repro.obs.trace import Tracer, set_tracer
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               cli_arrivals, requests_from_arrivals,
                               summarize)

    slots = 1 if pattern == "sporadic" else args.slots
    arrivals = cli_arrivals(pattern, args.n_requests, seed=args.seed,
                            prompt_len=args.prompt_len,
                            max_new_tokens=args.max_new, gap_s=args.gap_s,
                            burst_size=args.slots, rate_rps=args.rate_rps,
                            n_templates=args.n_templates,
                            prefix_len=args.prefix_len, turns=args.turns,
                            trace=args.arrival_trace)

    if args.replicas > 1 and args.backend != "sim":
        raise SystemExit("--replicas > 1 runs on --backend sim (the "
                         "launcher serves engine-backed fleets)")
    backend = build_sim_backend(args, slots) if args.backend == "sim" \
        else build_engine_backend(args, slots,
                                  max(ev.prompt_len for ev in arrivals))
    kv_policy = args.kv_policy
    if args.prefix_cache and args.backend == "sim":
        kv_policy = "paged"             # the radix tree lives in the pool
    scfg = SchedulerConfig(
        kv_policy=kv_policy, page_size=args.page_size,
        prefix_cache=(args.prefix_cache and args.backend == "sim"),
        prefill_chunk_tokens=args.prefill_chunk)
    # flight recorder: install BEFORE schedulers are built — they cache
    # the tracer and bind its clock to backend.now at construction
    tracer = None
    if trace_out:
        tracer = Tracer(capacity=args.trace_capacity)
        set_tracer(tracer)
    try:
        # template prompts materialize real ids: keep them inside the
        # engine's (smoke) vocab so prefix keys equal what the model
        # actually embeds
        vocab = backend.cfg.vocab_size if args.backend == "engine" else 32768
        reqs = requests_from_arrivals(arrivals, vocab_size=vocab,
                                      seed=args.seed)
        def mk_slo():
            if not args.slo_report:
                return None
            from repro.obs.slo import SLOEngine
            return SLOEngine()

        if args.replicas > 1:
            # fleet mode (DESIGN.md §16): N replica pipelines behind the
            # router; the report's `aggregate` carries the pooled metrics
            from repro.fleet import Fleet, Replica, RouterConfig
            reps = [Replica(0, backend, scfg)]
            reps += [Replica(i, build_sim_backend(args, slots), scfg)
                     for i in range(1, args.replicas)]
            for rep in reps:
                slo = mk_slo()
                if slo is not None:
                    rep.sched.attach_slo(slo)
            fleet = Fleet(reps, config=RouterConfig(policy=args.router,
                                                    seed=args.seed))
            result = fleet.run(reqs)
            out = result.report(
                pattern=pattern,
                backend=f"{args.backend}/fleet{args.replicas}").to_dict()
        else:
            sched = ContinuousBatchingScheduler(backend, scfg)
            slo = mk_slo()
            if slo is not None:
                sched.attach_slo(slo)
            served = sched.serve(reqs)
            out = summarize(served, pattern=pattern, backend=args.backend,
                            stats=sched.stats).to_dict()
            if slo is not None:
                out["slo"] = slo.snapshot(sched.now())
    finally:
        if tracer is not None:
            set_tracer(None)
    if tracer is not None:
        tracer.export(trace_out)
        print(f"# trace: {trace_out} ({tracer.emitted} events, "
              f"{tracer.dropped} dropped)", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pattern", choices=PATTERN_CHOICES, default="all")
    ap.add_argument("--backend", choices=("sim", "engine"), default="sim")
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet mode (DESIGN.md §16): route the stream "
                         "across N replica pipelines (sim backend)")
    ap.add_argument("--router", default="prefix",
                    choices=("prefix", "sticky", "random", "roundrobin"),
                    help="fleet placement policy (--replicas > 1)")
    ap.add_argument("--arch", default="llama2-13b")
    ap.add_argument("--fleet", default="E3",
                    choices=("E1", "E2", "E3", "lowmem1", "tpu4"),
                    help="device profile set (E3 = the paper's 4-device "
                         "heterogeneous testbed)")
    ap.add_argument("--bw-mbps", type=float, default=200.0)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="micro-batch slots for bursty/poisson/trace")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--gap-s", type=float, default=4.0)
    ap.add_argument("--rate-rps", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (DESIGN.md §11): k-token "
                         "draft + one multi-token verify round per step")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--spec-draft", default="ngram",
                    choices=("ngram", "model"))
    ap.add_argument("--spec-acceptance", type=float, default=0.6,
                    help="sim acceptance model (engine verifies for real)")
    ap.add_argument("--kv-policy", choices=("reserve", "paged"),
                    default="reserve",
                    help="admission accounting: worst-case reservation or "
                         "page-granular (bench_kvcache.py compares both)")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache (DESIGN.md §12): match prompt"
                         " prefixes against cached KV pages, prefill only "
                         "the uncached suffix (sim: scheduler-level over "
                         "the paged pool; engine: real KV pages)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: prompts drain this many tokens "
                         "per mixed round alongside live decode streams")
    ap.add_argument("--n-templates", type=int, default=4,
                    help="shared_prefix: distinct prompt templates")
    ap.add_argument("--prefix-len", type=int, default=256,
                    help="shared_prefix: shared template span per prompt")
    ap.add_argument("--turns", type=int, default=3,
                    help="multiturn: conversation turns per session")
    ap.add_argument("--arrival-trace", default=None,
                    help="JSON arrival trace for --pattern trace")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="flight-recorder output (DESIGN.md §15): Chrome "
                         "trace-event JSON loadable in Perfetto, or JSONL "
                         "when PATH ends in .jsonl; --pattern all writes "
                         "one file per pattern")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="flight-recorder ring size (oldest events drop)")
    ap.add_argument("--slo-report", action="store_true",
                    help="attach the online SLO engine (DESIGN.md §17) "
                         "and embed its burn-rate/breach snapshot in the "
                         "report (fleet mode: per-replica under "
                         "membership)")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    if args.pattern == "trace" and not args.arrival_trace:
        ap.error("--pattern trace requires --arrival-trace <arrivals.json>")

    patterns = ["sporadic", "bursty", "poisson"] if args.pattern == "all" \
        else [args.pattern]
    results = [run_pattern(args, p,
                           trace_out=(trace_path(args.trace, p,
                                                 len(patterns) > 1)
                                      if args.trace else None))
               for p in patterns]
    payload = results[0] if len(results) == 1 else results
    text = json.dumps(payload, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")

    if args.pattern == "all":
        by = {r["pattern"]: r.get("aggregate", r) for r in results}
        s, b = by["sporadic"], by["bursty"]
        ratio = b["throughput_tok_s"] / max(s["throughput_tok_s"], 1e-12)
        print(f"# bursty/sporadic throughput: {ratio:.2f}x "
              f"({b['throughput_tok_s']:.2f} vs "
              f"{s['throughput_tok_s']:.2f} tok/s)", file=sys.stderr)
        if ratio < 1.0:
            print("# WARNING: bursty below sporadic — interleave not "
                  "amortizing", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

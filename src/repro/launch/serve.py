"""Serving launcher: LIME-Serve over the interleaved pipeline (DESIGN.md §9).

The engine runs on `--stages x --tp` devices (default: every device as a
stage); asking for more devices than exist is an error. Only fleet mode
(`--replicas > 1`) and `--prefix-cache` serve without the engine.

  # one chip (or one CPU device), Pallas kernels:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --stages 1 --impl pallas --pattern bursty --requests 4 --max-new 16

  # CPU demo (4 virtual stages), Poisson arrivals at 2 req/s:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --stages 4 --pattern poisson --rate-rps 2 --requests 8
"""
from __future__ import annotations

import argparse
import json


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--stages", type=int, default=None,
                    help="pipeline stages (default: device count // --tp)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--impl", choices=("ref", "pallas"), default="ref",
                    help="engine attention: jnp reference or Pallas "
                         "kernels (compiled on a TPU, interpreted "
                         "elsewhere)")
    ap.add_argument("--pattern",
                    choices=("sporadic", "bursty", "poisson", "trace",
                             "shared_prefix", "multiturn"),
                    default="sporadic")
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gap-s", type=float, default=2.0)
    ap.add_argument("--rate-rps", type=float, default=1.0)
    ap.add_argument("--arrival-trace", default=None,
                    help="JSON arrival trace for --pattern trace")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="flight-recorder output (DESIGN.md §15): Chrome "
                         "trace-event JSON loadable in Perfetto, or JSONL "
                         "when PATH ends in .jsonl")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet mode (DESIGN.md §16): N replica serving "
                         "stacks behind the router, each its own backend "
                         "(real execution, single-device fallback each — "
                         "one engine cannot back N independent replicas)")
    ap.add_argument("--router", default="prefix",
                    choices=("prefix", "sticky", "random", "roundrobin"),
                    help="fleet placement policy (--replicas > 1)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: draft k tokens, verify "
                         "them in one pipeline round (DESIGN.md §11)")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--spec-draft", default="ngram",
                    choices=("ngram", "model", "resident"),
                    help="draft provider; 'resident' self-drafts through "
                         "the target's own resident tier with retier-"
                         "adaptive depth (DESIGN.md §14)")
    ap.add_argument("--plan", choices=("uniform", "hetero"),
                    default="uniform",
                    help="uniform: hand-built homogeneous split; hetero: "
                         "run the offline allocation scheduler over "
                         "per-stage device profiles and execute its "
                         "heterogeneous ExecutionPlan (DESIGN.md §13)")
    ap.add_argument("--adapt", action="store_true",
                    help="online memory adaptation: an OnlinePlanner walks "
                         "KV page occupancy and retiers the live engine — "
                         "resident layers demote to the streamed tier, "
                         "their HBM becomes KV pages (DESIGN.md §13)")
    ap.add_argument("--retier-headroom", type=int, default=1,
                    help="streamed-store slots per stage reserved for "
                         "runtime demotions (--adapt)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over real KV pages "
                         "(single-device fallback only — DESIGN.md §12)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill span (0 = monolithic)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV page size (prefix sharing is page-granular: "
                         "pick <= prefix length for smoke prompts)")
    ap.add_argument("--n-templates", type=int, default=4)
    ap.add_argument("--prefix-len", type=int, default=32)
    ap.add_argument("--turns", type=int, default=3)
    ap.add_argument("--slo", action="store_true",
                    help="online SLO engine (DESIGN.md §17): burn-rate "
                         "alerts on TTFT/TPOT/goodput/reject targets, "
                         "health fed to router scoring and planner "
                         "pressure; final report gains an 'slo' section")
    ap.add_argument("--slo-ttft", type=float, default=8.0,
                    help="TTFT p99 threshold in seconds (--slo)")
    ap.add_argument("--slo-tpot", type=float, default=1.0,
                    help="TPOT p50 threshold in seconds/token (--slo)")
    ap.add_argument("--measure", action="store_true",
                    help="measured-profile autotune (DESIGN.md §18): run "
                         "the microbenchmark harness on this device and "
                         "plan from timed FLOP/s + stream bandwidth "
                         "instead of the analytic knobs; results persist "
                         "to --profile-cache")
    ap.add_argument("--profile-cache", default=None, metavar="PATH",
                    help="tune-cache JSON (measured profiles + swept "
                         "kernel block configs, keyed by device kind); "
                         "loaded at startup — tuned kernel configs are "
                         "installed before the first trace — and updated "
                         "by --measure. Default: ~/.cache/repro/"
                         "tune_cache.json when --measure is set")
    ap.add_argument("--refit", action="store_true",
                    help="online re-fit (DESIGN.md §18): EWMA-track "
                         "measured weight-stream bandwidth during "
                         "serving and rebuild the planner's TS ladders "
                         "when it drifts >20%% from the planned model")
    ap.add_argument("--dash-interval", type=float, default=0.0,
                    help="seconds between live dashboard snapshots on "
                         "stdout (0 = off; backend clock, so virtual "
                         "seconds in sim runs)")
    return ap.parse_args(argv)


def build_engine(cfg, args, *, measured=None, log=None):
    """The InterleavedEngine on args.stages x args.tp devices, plus the
    OnlinePlanner when --adapt. Returns (engine, planner)."""
    import jax

    from repro.core.engine import InterleavedEngine, UniformPlan
    from repro.launch.mesh import make_mesh

    # tp 1: a stage-only mesh, so the step's shard_map is fully manual —
    # Mosaic kernels cannot be partitioned over an auto 'model' axis
    if args.tp == 1:
        mesh = make_mesh((args.stages,), ("data",))
    else:
        mesh = make_mesh((args.stages, args.tp), ("data", "model"))
    n_mb = args.stages if args.pattern != "sporadic" else 1
    env = None
    planner = None
    if args.plan == "hetero" or args.adapt:
        # per-stage profiles scaled to the model so the offline
        # scheduler actually offloads (real 16 GB chips would hold a
        # smoke model outright); --plan hetero varies the memory per
        # stage, so the emitted ExecutionPlan has unequal splits
        import dataclasses as _dc

        from repro.core.cost_model import CostEnv, Workload
        from repro.core.profiles import TPU_V5E, mbps
        base = cfg.total_params() * 2.0 / args.stages
        fracs = ([2.0, 1.2, 1.6, 1.0] if args.plan == "hetero"
                 else [1.5])

        # measured throughputs override the synthetic knobs (memory
        # stays the enforced budget — DESIGN.md §18)
        overrides = {}
        if measured is not None:
            from repro.tune.profiles import MEASURED_FIELDS
            overrides = {f: getattr(measured, f)
                         for f in MEASURED_FIELDS
                         if getattr(measured, f) > 0}

        def mk_env(scale):
            devs = [_dc.replace(TPU_V5E, name=f"stage{i}",
                                mem_bytes=base * scale
                                * fracs[i % len(fracs)],
                                **overrides)
                    for i in range(args.stages)]
            return CostEnv(devs, mbps(200.0),
                           Workload(cfg, mb=1, ctx=args.prompt_len,
                                    n_micro=n_mb))
        env = mk_env(1.0)
    if args.plan == "hetero":
        from repro.core.offline_scheduler import allocate_with_retry
        r, env, scale = allocate_with_retry(mk_env, cfg.n_layers,
                                            n_emp=args.max_len)
        if not r.feasible:
            raise SystemExit(f"hetero allocation infeasible: {r.reason}")
        if scale > 1.0 and log is not None:
            log.info(f"hetero allocation relaxed memory x{scale:.2f} "
                     f"for feasibility")
        plan = r.plan
    else:
        # pad layers to a chunk grid; one streamed layer per chunk
        import math
        n_seg = 2
        k = math.ceil(cfg.n_layers / (n_seg * args.stages))
        plan = UniformPlan(args.stages, n_seg, max(k - 1, 0),
                           1 if k >= 1 else 0)
    engine = InterleavedEngine(
        cfg, mesh, plan, n_mb=n_mb, mb=1, max_len=args.max_len,
        impl=args.impl,
        retier_headroom=args.retier_headroom if args.adapt else 0)
    if args.adapt:
        from repro.core.online_planner import OnlinePlanner
        planner = OnlinePlanner(env, plan,
                                horizon_tokens=4 * n_mb * args.max_len)
    if log is not None:
        log.info(f"engine: {args.stages} stages x tp{args.tp} on "
                 f"{jax.devices()[0].platform}, impl={args.impl}, plan "
                 f"seg={plan.n_seg} k_res={plan.k_res_list} "
                 f"k_off={plan.k_off_list} adapt={args.adapt}")
    return engine, planner


def resolve_stages(args, n_dev: int) -> None:
    """Fill the --stages default (every device) and refuse a mesh larger
    than the devices that exist, or one the Pallas kernels cannot run on."""
    if args.stages is None:
        args.stages = max(n_dev // args.tp, 1)
    if args.impl == "pallas" and args.tp > 1:
        raise SystemExit("--impl pallas needs --tp 1: Mosaic kernels "
                         "cannot be partitioned over the 'model' axis")
    need = args.stages * args.tp
    if need > n_dev:
        raise SystemExit(f"--stages {args.stages} x --tp {args.tp} needs "
                         f"{need} devices; {n_dev} exist")


def build_server(cfg, args, *, measured=None, log=None):
    """The LimeServer main() serves with: seeded random params, the
    engine (unless fleet mode or --prefix-cache, which serve engine-less
    by design — one engine cannot back N replicas, and the engine's
    per-stage cache has no shared page pool), sampler and spec config."""
    import jax

    from repro.models import model as M
    from repro.serving import LimeServer, SamplerConfig

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = planner = None
    if args.replicas > 1 or args.prefix_cache:
        if log is not None:
            log.info("engine-less single-device backend ("
                     + ("fleet mode" if args.replicas > 1
                        else "--prefix-cache") + ")")
    else:
        engine, planner = build_engine(cfg, args, measured=measured,
                                       log=log)
    spec = None
    if args.spec:
        from repro.specdec import SpecConfig
        spec = SpecConfig(k=args.spec_k, draft=args.spec_draft,
                          seed=args.seed)
    return LimeServer(cfg, params, engine=engine, max_len=args.max_len,
                      pattern="sporadic" if args.pattern == "sporadic"
                      else "bursty",
                      sampler=SamplerConfig(temperature=args.temperature),
                      spec=spec,
                      prefix_cache=args.prefix_cache,
                      prefill_chunk_tokens=args.prefill_chunk,
                      page_size=args.page_size,
                      planner=planner, refit=args.refit)


def main(argv=None):
    args = parse_args(argv)

    import jax

    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.log import get_logger
    from repro.obs.trace import Tracer, set_tracer
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               cli_arrivals, requests_from_arrivals,
                               summarize)

    log = get_logger("repro.launch.serve")
    resolve_stages(args, len(jax.devices()))
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)

    # measured-profile autotune (DESIGN.md §18): load the tune cache and
    # install tuned kernel block configs BEFORE any model code traces
    # (jit caches do not retrace on a later install); --measure runs the
    # harness and persists the profile for next launch
    measured = None
    if args.measure or args.profile_cache:
        from repro.tune import TuneCache, default_cache_path
        from repro.tune.measure import device_kind
        cache_path = args.profile_cache or default_cache_path()
        tune_cache = TuneCache.load(cache_path)
        dk = device_kind()
        n_installed = tune_cache.install(dk)
        if n_installed:
            log.info(f"installed {n_installed} tuned kernel configs "
                     f"for {dk} from {cache_path}")
        measured = tune_cache.get_profile(dk)
        if args.measure:
            from repro.core.profiles import TPU_V5E
            from repro.tune.measure import measure_profile
            log.info("running microbenchmark harness (--measure)...")
            measured = measure_profile(dk, TPU_V5E)
            tune_cache.put_profile(measured)
            tune_cache.save(cache_path)
            log.info(f"measured profile for {dk}: "
                     f"flops={measured.flops:.3g} "
                     f"load_bw={measured.load_bw:.3g} -> {cache_path}")
        elif measured is not None:
            log.info(f"planning from cached measured profile for {dk} "
                     f"(measured {measured.measured_at})")
    srv = build_server(cfg, args, measured=measured, log=log)
    engine, params, spec = srv.engine, srv.params, srv.spec
    if args.refit and srv.planner is None:
        log.info("--refit needs an OnlinePlanner to rebuild (engine path "
                 "with --adapt); ignoring")

    arrivals = cli_arrivals(args.pattern, args.requests, seed=args.seed,
                            prompt_len=args.prompt_len,
                            max_new_tokens=args.max_new, gap_s=args.gap_s,
                            burst_size=srv.slots, rate_rps=args.rate_rps,
                            n_templates=args.n_templates,
                            prefix_len=args.prefix_len, turns=args.turns,
                            trace=args.arrival_trace)

    # adaptation rides page-granular admission: note_kv_pages feeds the
    # planner, and the scheduler can reclaim retier headroom pre-preempt
    scfg = SchedulerConfig(kv_policy="paged", page_size=args.page_size) \
        if args.adapt else SchedulerConfig()
    # flight recorder: installed before the scheduler is built (it caches
    # the tracer and binds its clock to the backend at construction)
    tracer = None
    if args.trace:
        tracer = Tracer()
        set_tracer(tracer)

    def mk_slo():
        if not args.slo:
            return None
        from repro.obs.slo import SLOEngine, default_targets
        return SLOEngine(default_targets(ttft_p99_s=args.slo_ttft,
                                         tpot_p50_s=args.slo_tpot))

    fleet_report = None
    slo = None
    try:
        reqs = requests_from_arrivals(arrivals, vocab_size=cfg.vocab_size)
        if args.replicas > 1:
            # fleet mode (DESIGN.md §16): N real-execution replicas (each
            # the single-device fallback backend — one InterleavedEngine
            # cannot back N independent replicas) behind the router
            from repro.fleet import Fleet, Replica, RouterConfig
            from repro.serving import EngineBackend
            reps = [Replica(i, EngineBackend(
                        cfg, params, engine=None, n_slots=srv.slots,
                        max_len=args.max_len, sampler=srv.sampler,
                        spec=spec, prefix_cache=args.prefix_cache,
                        prefill_chunk_tokens=args.prefill_chunk,
                        page_size=args.page_size), scfg)
                    for i in range(args.replicas)]
            if args.slo:
                # one engine per replica: health is a per-replica signal
                # (the router sheds off the breaching one, not the fleet)
                for rep in reps:
                    rep.sched.attach_slo(mk_slo())
            fleet = Fleet(reps, config=RouterConfig(policy=args.router,
                                                    seed=args.seed))
            result = fleet.run(reqs)
            done = result.requests
            fleet_report = result.report(
                pattern=args.pattern, backend=f"fleet{args.replicas}")
        else:
            sched = ContinuousBatchingScheduler(srv.make_backend(), scfg)
            slo = mk_slo()
            if slo is not None:
                sched.attach_slo(slo)
            if args.dash_interval > 0:
                from repro.obs.dashboard import Dashboard
                dash = Dashboard(slo=slo, sched=sched, tracer=tracer,
                                 interval_s=args.dash_interval)
                sched.begin(reqs)
                while sched.step():
                    snap = dash.tick(sched.now())
                    if snap is not None:
                        print(snap)
                done = sched.finish_run()
                print(dash.render(sched.now()))
            else:
                done = sched.serve(reqs)
    finally:
        if tracer is not None:
            set_tracer(None)
    if tracer is not None:
        tracer.export(args.trace)
        log.info(f"trace: {args.trace} ({tracer.emitted} events, "
                 f"{tracer.dropped} dropped)")
    for r in sorted(done, key=lambda r: r.rid):
        status = "REJECTED" if r.rejected else \
            f"ttft {r.ttft_s:.2f}s total {r.latency_s:.2f}s " \
            f"out[:8]={r.output[:8]}"
        print(f"req {r.rid}: {status}")
    if fleet_report is not None:
        print(fleet_report.to_json())
    else:
        report = summarize(done, pattern=args.pattern,
                           backend="engine" if engine else "fallback",
                           stats=sched.stats)
        doc = report.to_dict()
        if slo is not None:
            doc["slo"] = slo.snapshot(sched.now())
        print(json.dumps(doc, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

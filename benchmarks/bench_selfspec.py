"""Resident-tier self-speculative decoding vs n-gram drafting
(EXPERIMENTS.md §Self-Spec).

Two exit-enforced claims (DESIGN.md §14):

 1. Throughput: on the E3 fleet, serving with the resident self-draft
    (acceptance scales with the live resident fraction, depth adapts per
    retier rung) beats the n-gram draft baseline in decode tokens/s at at
    least one rung of the retier ladder. Rungs are built by demoting j
    layers of the allocated plan into the streamed tier — the state the
    online planner leaves the pipeline in after KV pressure (the n-gram
    draft's flat acceptance does not care where the tier boundary sits;
    the self-draft's does — the bench maps where each one wins).
 2. Losslessness: a raw-engine resident-draft spec loop (draft k on the
    resident tier -> rollback -> one multi-query verify -> greedy commit),
    with a mid-stream retier demotion AND promotion, emits tokens
    identical to plain autoregressive greedy decode at bf16, on both the
    ref and Pallas attention paths (subprocess: forced host device count).

  python benchmarks/bench_selfspec.py
  python benchmarks/bench_selfspec.py --rungs 0,8,16,24,32 \
      --out benchmarks/baselines/selfspec_sim.json
  python benchmarks/bench_selfspec.py --no-engine-check   # sim sweep only
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

# --------------------------------------------------------------------------
# part 2: engine token-identity (subprocess, forced host device count)
# --------------------------------------------------------------------------
ENGINE_WORKER = r"""
import jax, jax.numpy as jnp, numpy as np, sys
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.specdec import greedy_verify

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
params = M.init_params(cfg, jax.random.PRNGKey(0))
PLAN = E.UniformPlan(4, 2, 1, 1)
STEPS = 12


def make(mesh, impl):
    eng = E.InterleavedEngine(cfg, mesh, PLAN, n_mb=1, mb=2, max_len=48,
                              impl=impl, retier_headroom=1)
    return eng, eng.init_state(params)


def greedy(lg):
    return jnp.argmax(lg[:, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)


fails = []
for impl, shape, axes in (("ref", (4, 2), ("data", "model")),
                          ("pallas", (4,), ("data",))):
    mesh = make_mesh(shape, axes)
    tok0 = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0,
                              cfg.vocab_size)
    # plain autoregressive greedy reference
    eng, st = make(mesh, impl)
    t, ref = tok0, []
    for _ in range(STEPS):
        lg, st = eng.decode_step(st, t)
        t = greedy(lg)
        ref.append(np.asarray(t)[:, 0].copy())
    ref = np.stack(ref)

    # resident self-spec loop with retier events between rounds
    eng, st = make(mesh, impl)
    t = np.array(tok0, np.int32)
    out = [[], []]
    pos, k, rounds = 0, 3, 0
    while min(len(o) for o in out) < STEPS:
        cur = jnp.asarray(t)
        drafts = np.zeros((2, k), np.int32)
        for i in range(k):
            lg, st = eng.draft_step(st, cur)
            cur = greedy(lg)
            drafts[:, i] = np.asarray(cur)[:, 0]
        st = eng.rollback(st, pos)
        lg, st = eng.verify_step(
            st, jnp.asarray(np.concatenate([t, drafts], 1)))
        lgn = np.asarray(lg, np.float32)
        committed = [greedy_verify(lgn[b], drafts[b], cfg.vocab_size)
                     for b in range(2)]
        c = min(len(x) for x in committed)
        pos += c
        st = eng.rollback(st, pos)
        for b in range(2):
            out[b].extend(committed[b][:c])
            t[b, 0] = committed[b][c - 1]
        rounds += 1
        if rounds == 2:       # demote one resident slot mid-stream ...
            st, freed = eng.retier(st, 0, +1)
            assert freed > 0
        if rounds == 4:       # ... and promote it back two rounds later
            st, freed = eng.retier(st, 0, -1)
            assert freed < 0
    got = np.stack([np.asarray(o[:STEPS]) for o in out], 1).T
    ok = (got == ref.reshape(STEPS, 2).T).all()
    print(f"{impl}: resident-spec tokens "
          f"{'identical' if ok else 'MISMATCH'} ({rounds} rounds)")
    if not ok:
        fails.append(impl)
print("SELFSPEC_ENGINE_OK" if not fails else f"FAILS {fails}")
sys.exit(1 if fails else 0)
"""


def engine_identity_check() -> bool:
    # eight virtual CPU devices: the forced count exists only on the CPU
    # backend, so the worker is held there even where a chip is attached
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "src")
    r = subprocess.run([sys.executable, "-c", ENGINE_WORKER], env=env,
                       capture_output=True, text=True, timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-2000:])
    return r.returncode == 0 and "SELFSPEC_ENGINE_OK" in r.stdout


# --------------------------------------------------------------------------
# part 1: sim throughput sweep over retier-ladder rungs
# --------------------------------------------------------------------------
def rung_plan(base, demoted: int):
    """Demote `demoted` layers of the allocated plan into the streamed
    tier: resident_total falls / off_full_seg rises one layer at a time,
    always on the currently most-resident stage — the shape the online
    planner's right-to-left ladder leaves behind. Only exact per-segment
    moves are expressible, so demotions step in units of n_seg."""
    import dataclasses

    from repro.core.cost_model import ExecutionPlan
    stages = [dataclasses.replace(st) for st in base.stages]
    left = demoted
    while left >= base.n_seg:
        d = max(range(len(stages)), key=lambda i: stages[i].resident_total)
        if stages[d].resident_total < base.n_seg:
            break
        stages[d] = dataclasses.replace(
            stages[d], resident_total=stages[d].resident_total - base.n_seg,
            off_full_seg=stages[d].off_full_seg + 1)
        left -= base.n_seg
    return ExecutionPlan(n_seg=base.n_seg, stages=stages)


def build_backend(args, plan, slots: int, spec):
    from repro.configs.registry import get_config
    from repro.core.cost_model import CostEnv, Workload
    from repro.core.profiles import env_E1, env_E2, env_E3, mbps
    from repro.serving import SimBackend

    fleets = {"E1": env_E1, "E2": env_E2, "E3": env_E3}
    cfg = get_config(args.arch)
    w = Workload(cfg, mb=1, ctx=args.prompt_len, n_micro=slots)
    env = CostEnv(fleets[args.fleet](), mbps(args.bw_mbps), w)
    return SimBackend(env, plan, n_slots=slots,
                      prompt_tokens=args.prompt_len, spec=spec)


def base_plan(args):
    from repro.configs.registry import get_config
    from repro.core.cost_model import CostEnv, Workload
    from repro.core.offline_scheduler import allocate
    from repro.core.profiles import env_E1, env_E2, env_E3, mbps

    fleets = {"E1": env_E1, "E2": env_E2, "E3": env_E3}
    cfg = get_config(args.arch)
    w = Workload(cfg, mb=1, ctx=args.prompt_len, n_micro=1)
    env = CostEnv(fleets[args.fleet](), mbps(args.bw_mbps), w)
    r = allocate(env, cfg.n_layers, n_emp=max(args.prompt_len, 1))
    if not r.feasible:
        raise SystemExit(f"infeasible {args.fleet} allocation: {r.reason}")
    return r.plan


def run_one(args, plan, spec) -> dict:
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)

    arrivals = make_arrivals("sporadic", args.n_requests, seed=args.seed,
                             prompt_len=args.prompt_len, gap_s=args.gap_s,
                             max_new_tokens=args.max_new)
    backend = build_backend(args, plan, 1, spec)
    sched = ContinuousBatchingScheduler(backend, SchedulerConfig())
    served = sched.serve(requests_from_arrivals(arrivals))
    rep = summarize(served, pattern="sporadic",
                    backend=f"sim/{spec.draft}", stats=sched.stats)
    out = rep.to_dict()
    out["draft"] = spec.draft
    return out


def compare_rung(args, base, demoted: int) -> dict:
    from repro.specdec import SpecConfig

    plan = rung_plan(base, demoted)
    total = max(plan.layers_total(), 1)
    frac = sum(st.resident_total for st in plan.stages) / total
    res = run_one(args, plan, SpecConfig(
        k=args.k, draft="resident", acceptance=args.resident_acceptance,
        seed=args.seed))
    ngram = run_one(args, plan, SpecConfig(
        k=args.k, draft="ngram", acceptance=args.ngram_acceptance,
        seed=args.seed))
    return {
        "rung_demoted_layers": demoted,
        "resident_fraction": frac,
        "resident_tok_s": res["throughput_tok_s"],
        "ngram_tok_s": ngram["throughput_tok_s"],
        "resident_wins": res["throughput_tok_s"] > ngram["throughput_tok_s"],
        "resident_acceptance_rate": res["spec_acceptance_rate"],
        "ngram_acceptance_rate": ngram["spec_acceptance_rate"],
        "resident": res, "ngram": ngram,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="llama2-13b")
    ap.add_argument("--fleet", default="E3", choices=("E1", "E2", "E3"))
    ap.add_argument("--bw-mbps", type=float, default=200.0)
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--gap-s", type=float, default=4.0)
    ap.add_argument("--k", type=int, default=4,
                    help="draft depth cap (DepthController adapts below)")
    ap.add_argument("--resident-acceptance", type=float, default=0.9,
                    help="full-residency acceptance of the self-draft "
                         "(scaled by the live resident fraction)")
    ap.add_argument("--ngram-acceptance", type=float, default=0.35,
                    help="flat acceptance of the n-gram baseline")
    ap.add_argument("--rungs", default="0,8,16,24,32",
                    help="comma-separated demoted-layer counts")
    ap.add_argument("--no-engine-check", action="store_true",
                    help="skip the subprocess token-identity check")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)

    base = base_plan(args)
    rungs = [int(x) for x in args.rungs.split(",") if x != ""]
    results = [compare_rung(args, base, j) for j in rungs]
    payload = {"config": {k: v for k, v in vars(args).items()},
               "results": results}
    text = json.dumps(payload, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")

    rc = 0
    wins = [r for r in results if r["resident_wins"]]
    for r in results:
        print(f"# rung {r['rung_demoted_layers']:>2} "
              f"(res frac {r['resident_fraction']:.2f}): resident "
              f"{r['resident_tok_s']:.2f} vs ngram {r['ngram_tok_s']:.2f} "
              f"tok/s {'WIN' if r['resident_wins'] else 'loss'}",
              file=sys.stderr)
    if not wins:
        print("# WARNING: resident draft never beat the n-gram baseline "
              "at any retier rung — acceptance scaling or depth control "
              "broke", file=sys.stderr)
        rc = 1
    if not args.no_engine_check:
        if not engine_identity_check():
            print("# WARNING: resident-spec decode is NOT token-identical "
                  "to autoregressive greedy on the engine", file=sys.stderr)
            rc = 1
    return rc


def run():
    """benchmarks.run harness hook: sim rung sweep + engine identity."""
    class _Row:
        def __init__(self, name, ms):
            self.name, self.ms = name, ms

        def csv(self):
            return f"selfspec,{self.name},{self.ms:.1f},ok"

    rc = main(["--n-requests", "2", "--max-new", "16", "--rungs", "0,16,32"])
    if rc:
        raise SystemExit("bench_selfspec smoke failed")
    return [_Row("resident_vs_ngram_rungs", 0.0)]


if __name__ == "__main__":
    raise SystemExit(main())

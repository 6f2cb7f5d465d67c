"""Serve a model through the LIME interleaved-pipeline engine on a virtual
4-stage cluster (CPU devices stand in for pipeline stages), demonstrating:

  * offline planning -> uniform engine plan (resident + streamed layers)
  * prefill on GSPMD, cache adoption into the engine layout
  * bursty vs sporadic request patterns
  * Poisson traffic through the continuous-batching scheduler + metrics
  * losslessness spot-check vs a single-device decode

This is a CPU demo: it re-execs itself on the CPU backend with eight
forced host devices (the forced count exists only there), even where a
chip is attached. `chip_smoke.py` is the path that runs on the chip.

  PYTHONPATH=src python examples/serve_cluster.py
"""
import os
import sys

_FORCE = "--xla_force_host_platform_device_count=8"
_FLAGS = os.environ.get("XLA_FLAGS", "")
if os.environ.get("JAX_PLATFORMS") != "cpu" or _FORCE not in _FLAGS:
    os.environ["JAX_PLATFORMS"] = "cpu"
    if _FORCE not in _FLAGS:
        os.environ["XLA_FLAGS"] = f"{_FLAGS} {_FORCE}"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs.registry import get_smoke_config           # noqa: E402
from repro.core.engine import InterleavedEngine, UniformPlan  # noqa: E402
from repro.launch.mesh import make_mesh                       # noqa: E402
from repro.models import model as M                           # noqa: E402
from repro.serving import LimeServer, SamplerConfig           # noqa: E402


def main():
    cfg = get_smoke_config("internlm2-1.8b")
    import dataclasses
    cfg = dataclasses.replace(cfg, n_layers=8)   # 2 segments x 4 stages x 1
    mesh = make_mesh((4, 2), ("data", "model"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    plan = UniformPlan(n_stage=4, n_seg=2, k_res=0, k_off=1)
    print(f"plan: {plan.n_seg} segments x {plan.n_stage} stages, "
          f"k_res={plan.k_res} k_off={plan.k_off} (all layers streamed)")

    for pattern, n_mb in (("sporadic", 1), ("bursty", 4)):
        engine = InterleavedEngine(cfg, mesh, plan, n_mb=n_mb, mb=1,
                                   max_len=64)
        srv = LimeServer(cfg, params, engine=engine, max_len=64,
                         pattern=pattern, sampler=SamplerConfig())
        rng = np.random.default_rng(1)
        n_req = 4
        for i in range(n_req):
            srv.queue.submit(rng.integers(1, cfg.vocab_size, 6),
                             max_new_tokens=8)
        done = srv.serve_all()
        print(f"[{pattern}] served {len(done)} requests:")
        for r in done:
            print(f"   req {r.rid}: {r.output}")

    # LIME-Serve: a seeded Poisson arrival stream through the
    # continuous-batching scheduler, reported with serving metrics
    # (reuses the loop's final bursty engine/server — same plan, and a
    # fresh engine would recompile the slowest program of the demo)
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)
    arrivals = make_arrivals("poisson", 6, rate_rps=2.0, prompt_len=6,
                             max_new_tokens=8, seed=7)
    backend = srv.make_backend()
    reqs = requests_from_arrivals(arrivals)
    for r in reqs:                 # traffic times are relative to "now":
        r.arrival_s += backend.now()   # re-base onto the running clock
    sched = ContinuousBatchingScheduler(backend, SchedulerConfig())
    served = sched.serve(reqs)
    rep = summarize(served, pattern="poisson", backend="engine")
    print(f"[poisson] {rep.n_requests} served, "
          f"ttft p50 {rep.ttft_p50_s:.2f}s, "
          f"latency p99 {rep.latency_p99_s:.2f}s, "
          f"{rep.throughput_tok_s:.1f} tok/s")

    # losslessness spot check: engine greedy tokens == plain decode greedy
    # (the loop's final engine has the same (n_mb=4, mb=1, max_len=64)
    # signature — reuse it rather than recompiling)
    state = engine.init_state(params)
    tok = jnp.arange(4, dtype=jnp.int32)[:, None] + 3
    cache = M.init_cache(cfg, 4, 64)
    agree = 0
    for _ in range(6):
        lg_e, state = engine.decode_step(state, tok)
        lg_r, cache = M.decode_step(cfg, params, cache, tok)
        a = jnp.argmax(lg_e[:, :cfg.vocab_size], -1)
        b = jnp.argmax(lg_r[:, 0, :cfg.vocab_size], -1)
        agree += int((a == b).all())
        tok = b[:, None].astype(jnp.int32)
    print(f"greedy agreement engine vs single-device: {agree}/6")


if __name__ == "__main__":
    main()

"""LIME engine losslessness: pipelined output == single-device decode.

The engine needs >= 4 devices; this module re-execs its worker in a
subprocess with a forced host device count (the only sanctioned way to get
multiple CPU devices without polluting the whole test session's jax state).
"""
import json
import os
import subprocess
import sys

import pytest

WORKER = r"""
import jax, jax.numpy as jnp, functools, sys
jnp.bfloat16 = jnp.float32   # fp32 => losslessness must be (near-)exact
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family, AttnKind
from repro.models import model as M
from repro.launch.mesh import make_mesh

CASES = {
 "dense": ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16),
 "moe": ModelConfig(name="m", family=Family.MOE, n_layers=8, d_model=64,
                    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                    head_dim=16, n_experts=4, top_k=2, n_shared_experts=1,
                    moe_d_ff=64),
 "ssm": ModelConfig(name="s", family=Family.SSM, n_layers=8, d_model=64,
                    n_heads=4, n_kv_heads=0, d_ff=128, vocab_size=256,
                    head_dim=16, attn_kind=AttnKind.NONE, ssm_state_size=16),
 "hybrid": ModelConfig(name="h", family=Family.HYBRID, n_layers=8,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=256, head_dim=16,
                       attn_kind=AttnKind.SLIDING, window_size=16,
                       ssm_state_size=8, ssm_heads=4),
 "local_global": ModelConfig(name="lg", family=Family.DENSE, n_layers=8,
                             d_model=64, n_heads=4, n_kv_heads=1, d_ff=128,
                             vocab_size=256, head_dim=16,
                             attn_kind=AttnKind.LOCAL_GLOBAL, window_size=8,
                             tie_embeddings=True),
}
key = jax.random.PRNGKey(0)
mesh = make_mesh((4, 2), ("data", "model"))
fails = []
for name, cfg in CASES.items():
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        M.init_params(cfg, key))
    ref_step = jax.jit(functools.partial(M.decode_step, cfg))
    for fm in ("slot", "step"):
        for n_mb, mb, plan in ((4, 2, E.UniformPlan(4, 2, 0, 1)),
                               (1, 2, E.UniformPlan(4, 2, 1, 1))):
            eng = E.InterleavedEngine(cfg, mesh, plan, n_mb=n_mb, mb=mb,
                                      max_len=32, fetch_mode=fm)
            state = eng.init_state(params)
            caches = [jax.tree.map(
                lambda a: a.astype(jnp.float32)
                if a.dtype == jnp.bfloat16 else a,
                M.init_cache(cfg, mb, 32)) for _ in range(n_mb)]
            tok = jax.random.randint(key, (n_mb * mb, 1), 0, cfg.vocab_size)
            worst = 0.0
            for step in range(3):
                rls = []
                for m in range(n_mb):
                    rl, caches[m] = ref_step(params, caches[m],
                                             tok[m*mb:(m+1)*mb])
                    rls.append(rl[:, 0].astype(jnp.float32))
                rl = jnp.concatenate(rls, 0)
                lg, state = eng.decode_step(state, tok)
                worst = max(worst, float(jnp.abs(lg - rl).max()))
                tok = jnp.argmax(rl, -1)[:, None].astype(jnp.int32)
            ok = worst < 5e-4
            print(f"{name} fetch={fm} n_mb={n_mb} plan={plan}: "
                  f"worst={worst:.2e} {'OK' if ok else 'FAIL'}")
            if not ok:
                fails.append((name, fm, n_mb, worst))
sys.exit(1 if fails else 0)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_lossless_all_families():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", WORKER], env=env,
                       capture_output=True, text=True, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    assert r.returncode == 0


def test_uniform_plan_arithmetic():
    from repro.core.engine import UniformPlan
    p = UniformPlan(n_stage=16, n_seg=2, k_res=1, k_off=1)
    assert p.k == 2 and p.n_chunks == 32 and p.n_layers == 64


def test_stage_shard_dim_prefers_largest_divisible():
    from repro.core.engine import stage_shard_dim
    assert stage_shard_dim((384, 7168, 2048), 16) == 1
    assert stage_shard_dim((25,), 16) is None
    assert stage_shard_dim((64, 64), 4) == 0


@pytest.fixture(scope="module")
def in_place_engine():
    """One stage, 2 segments of 2 resident + 1 streamed layers, step
    fetch mode, on the test process's one CPU device."""
    import jax
    import repro.core.engine as E
    from repro.configs.base import Family, ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=6, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16)
    eng = E.InterleavedEngine(cfg, make_mesh((1,), ("data",)),
                              E.UniformPlan(1, 2, 2, 1), n_mb=1, mb=1,
                              max_len=32, fetch_mode="step")
    assert (eng.k_res_cap, eng.k_off_cap, eng.plan.n_seg) == (2, 1, 2)
    return eng, eng.init_state(M.init_params(cfg, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("program", ["step", "verify", "draft"])
def test_layer_scan_reads_weights_in_place(in_place_engine, program):
    """The decode step (q_len 1), the verify step (q_len 2) and the
    resident-only draft compile no copy of a stack of layers' weights:
    each layer is read where its store holds it, so no instruction but a
    parameter yields a multi-layer stack of a weight leaf."""
    import jax
    import jax.numpy as jnp
    from conftest import hlo_results, is_layer_stack, matrix_leaf_shapes
    eng, st = in_place_engine
    q_len = 2 if program == "verify" else 1
    tail = (st["shared"], st["cache"], st["glob"],
            jnp.ones((1, 1, q_len), jnp.int32), eng._kl_dev, eng._win_dev,
            eng._live_dev)
    if program == "draft":
        prog, args = eng._build_step(1, resident_only=True), \
            (st["resident"],) + tail
    else:
        prog = eng._build_step(q_len)
        args = (st["resident"], eng._fetch(st["offload"])) + tail
    leaves = matrix_leaf_shapes(x.shape[3:]
                                for x in jax.tree.leaves(st["resident"]))
    assert leaves
    results = hlo_results(prog.lower(*args).compile().as_text())
    assert len(results) > 100
    stacks = [(name, opc, dims) for name, opc, shapes in results
              for dims in shapes
              if opc != "parameter" and is_layer_stack(dims, leaves)]
    assert not stacks, stacks[:8]


MULTIPOD_WORKER = r"""
import jax, jax.numpy as jnp, functools, sys
jnp.bfloat16 = jnp.float32
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family
from repro.models import model as M
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="t", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
key = jax.random.PRNGKey(0)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
n_mb, mb = 2, 4       # mb=4 shards over pod=2 (bursty replicas per pod)
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_params(cfg, key))
eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(2, 2, 1, 1),
                          n_mb=n_mb, mb=mb, max_len=32)
state = eng.init_state(params)
ref_step = jax.jit(functools.partial(M.decode_step, cfg))
caches = [jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_cache(cfg, mb, 32)) for _ in range(n_mb)]
tok = jax.random.randint(key, (n_mb * mb, 1), 0, cfg.vocab_size)
worst = 0.0
for step in range(3):
    rls = []
    for m in range(n_mb):
        rl, caches[m] = ref_step(params, caches[m], tok[m*mb:(m+1)*mb])
        rls.append(rl[:, 0].astype(jnp.float32))
    rl = jnp.concatenate(rls, 0)
    lg, state = eng.decode_step(state, tok)
    worst = max(worst, float(jnp.abs(lg - rl).max()))
    tok = jnp.argmax(rl, -1)[:, None].astype(jnp.int32)
print(f"multipod worst={worst:.2e}")
sys.exit(0 if worst < 5e-4 else 1)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_lossless_multipod():
    """Decode through the 3-axis production mesh shape (pod, data, model):
    pod shards the bursty replicas, data is the pipeline, model is TP."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", MULTIPOD_WORKER], env=env,
                       capture_output=True, text=True, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    assert r.returncode == 0


LONGMODE_WORKER = r"""
import jax, jax.numpy as jnp, functools, sys
jnp.bfloat16 = jnp.float32
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family, AttnKind
from repro.models import model as M
from repro.launch.mesh import make_mesh

# sliding-window arch decoding PAST the ring-buffer length (the long_500k
# serving mode: cache is window-capped, slots wrap via pos_ids)
cfg = ModelConfig(name="sw", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, attn_kind=AttnKind.SLIDING, window_size=8)
key = jax.random.PRNGKey(0)
mesh = make_mesh((4, 2), ("data", "model"))
n_mb, mb, max_len = 4, 1, 16
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_params(cfg, key))
eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(4, 2, 1, 1), n_mb=n_mb,
                          mb=mb, max_len=max_len, long_mode=True)
state = eng.init_state(params)
caches = [jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_cache(cfg, mb, max_len, long_mode=True)) for _ in range(n_mb)]
tok = jax.random.randint(key, (n_mb * mb, 1), 0, cfg.vocab_size)
worst = 0.0
for step in range(14):        # window S_c = 8: wraps around
    rls = []
    for m in range(n_mb):
        rl, caches[m] = M.decode_step(cfg, params, caches[m],
                                      tok[m*mb:(m+1)*mb], long_mode=True)
        rls.append(rl[:, 0].astype(jnp.float32))
    rl = jnp.concatenate(rls, 0)
    lg, state = eng.decode_step(state, tok)
    worst = max(worst, float(jnp.abs(lg - rl).max()))
    tok = jnp.argmax(rl, -1)[:, None].astype(jnp.int32)
print(f"ring worst={worst:.2e}")
sys.exit(0 if worst < 5e-4 else 1)
"""


PAGED_WORKER = r"""
import jax, jax.numpy as jnp, functools, sys
import numpy as np
jnp.bfloat16 = jnp.float32
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family
from repro.models import model as M
from repro.launch.mesh import make_mesh

# paged KV accounting (DESIGN.md §10): seed_cache adoption routed through
# block-table pages must stay lossless, and slot occupancy must be
# page-granular (alloc on seed, extend per decode step, free on release)
cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
key = jax.random.PRNGKey(0)
mesh = make_mesh((4, 2), ("data", "model"))
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_params(cfg, key))
ref_step = jax.jit(functools.partial(M.decode_step, cfg))
n_mb, mb, max_len, ps = 4, 2, 32, 8
eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(4, 2, 0, 1), n_mb=n_mb,
                          mb=mb, max_len=max_len, paged=True, page_size=ps)
state = eng.init_state(params)
B = n_mb * mb
toks = jax.random.randint(key, (B, 10), 1, cfg.vocab_size)
cache = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_cache(cfg, B, max_len))
logits, cache = jax.jit(functools.partial(M.prefill, cfg))(params, toks,
                                                           cache)
state = eng.seed_cache(state, cache)
st = eng.paged_stats()
assert st["slot_tokens"] == [10] * B, st              # prompt adopted
assert st["pages_in_use"] == B * 2, st                # ceil(10/8) pages
tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
worst = 0.0
active = np.ones(B, bool)
for step in range(6):
    rl, cache = ref_step(params, cache, tok)
    lg, state = eng.decode_requests(state, tok, active)
    worst = max(worst, float(jnp.abs(lg - rl[:, 0].astype(jnp.float32))
                             .max()))
    tok = jnp.argmax(rl[:, 0].astype(jnp.float32), -1)[:, None] \
        .astype(jnp.int32)
st = eng.paged_stats()
assert st["slot_tokens"] == [16] * B, st              # extended per step
assert st["pages_in_use"] == B * 2, st                # 16 tok = 2 pages
eng.free_slot(0)
assert eng.paged_stats()["pages_in_use"] == B * 2 - 2
print(f"paged worst={worst:.2e}")
sys.exit(0 if worst < 5e-4 else 1)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_paged_kv_lossless_and_accounted():
    """Paged engine contract: block-table adoption is lossless and slot
    page counts track seed / extend / free exactly."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", PAGED_WORKER], env=env,
                       capture_output=True, text=True, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    assert r.returncode == 0


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_lossless_ring_buffer_long_mode():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", LONGMODE_WORKER], env=env,
                       capture_output=True, text=True, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    assert r.returncode == 0


# ----------------------------------------------------------------------------
# one-step look-ahead (DESIGN.md §9): each decode step is dispatched before
# the host reads the previous step's ids, and the served tokens stay the
# greedy continuation
# ----------------------------------------------------------------------------
AHEAD_WORKER = r"""
import json
import jax, jax.numpy as jnp, numpy as np
jnp.bfloat16 = jnp.float32   # fp32 => the engine's tokens are exact
import repro.core.engine as E
from repro.configs.base import Family, ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.obs import trace as tr_ev
from repro.serving import (ContinuousBatchingScheduler, EngineBackend,
                           Request, SamplerConfig, SchedulerConfig)

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_params(cfg, jax.random.PRNGKey(0)))
n = len(jax.devices())
mesh = make_mesh((n,), ("data",))
fwd = jax.jit(lambda t: M.forward(cfg, params, t)[0])


def engine(n_mb=1, **kw):
    return E.InterleavedEngine(cfg, mesh, E.UniformPlan(n, 2, 4 // n - 1, 1),
                               n_mb=n_mb, mb=1, max_len=32, **kw)


def greedy(prompt, n_new):
    seq, out = list(prompt), []
    for _ in range(n_new):
        t = int(jnp.argmax(fwd(jnp.asarray([seq], jnp.int32))
                           [0, -1, :cfg.vocab_size]))
        out.append(t)
        seq.append(t)
    return out


def prompt(i):
    return np.random.default_rng(i).integers(1, 256, 8).astype(np.int32)


def counts(tr):
    c = [e[5] for e in tr.events() if e[0] == tr_ev.BACKEND_AHEAD]
    return c[-1] if c else None


def serve(be, max_new):
    with tr_ev.tracing() as tr:
        sched = ContinuousBatchingScheduler(be, SchedulerConfig())
        done = sched.serve([Request(i, prompt(i), m, arrival_s=0.0)
                            for i, m in enumerate(max_new)])
    done = sorted(done, key=lambda r: r.rid)
    return {"max_new": max_new, "tokens": [list(r.output) for r in done],
            "greedy": [greedy(prompt(r.rid), r.max_new_tokens)
                       for r in done],
            "counts": counts(tr)}


def backend(eng, n_slots=1):
    return EngineBackend(cfg, params, engine=eng, n_slots=n_slots,
                         max_len=32, sampler=SamplerConfig())


out = {"engine": serve(backend(engine()), [1, 2, 5]),
       "paged_engine": serve(backend(engine(paged=True, page_size=8)),
                             [1, 2, 5])}
# a step in flight when the tier boundary moves, and one dropped by the
# next admission
be = backend(engine(retier_headroom=1))
with tr_ev.tracing() as tr:
    first = be.start_batch([Request(0, prompt(0), 5)])
    toks = first + [be.decode_active([0], [0])[0]]
    freed = be._apply_retier(0, 1)
    toks += [be.decode_active([0], [0])[0], be.decode_active([0], [0])[0]]
    be.start_batch([Request(1, prompt(1), 5)])
out["retier"] = {"tokens": toks, "greedy": greedy(prompt(0), 4),
                 "freed": freed, "counts": counts(tr)}
if n == 1:
    out["no_engine"] = serve(backend(None), [1, 2, 5])
else:
    # the bursty layout: two slots in one epoch; the freed slot rides as
    # padding while the other's steps go on being dispatched ahead
    out["two_slots"] = serve(backend(engine(n_mb=2), n_slots=2), [2, 5, 1])
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module", params=[1, 2], ids=["1dev", "2dev"])
def ahead_run(request):
    from conftest import _run_worker
    r = _run_worker(AHEAD_WORKER, devices=request.param)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.subprocess
def test_lookahead_serves_the_greedy_forward_tokens(ahead_run):
    assert {"engine", "paged_engine"} <= set(ahead_run)
    for name, run in ahead_run.items():
        assert run["tokens"] == run["greedy"], name


@pytest.mark.subprocess
def test_lookahead_dispatches_all_but_each_first_step_ahead(ahead_run):
    """Back to back, one request at a time: a request decodes max_new - 1
    steps, each but its first dispatched before the previous step's ids
    were read, and none is dispatched past the request's last token."""
    for name in ("engine", "paged_engine", "no_engine"):
        if name not in ahead_run:
            continue
        max_new = ahead_run[name]["max_new"]
        steps = sum(m - 1 for m in max_new)
        assert ahead_run[name]["counts"] == {
            "steps": steps, "ahead": steps - sum(m >= 2 for m in max_new),
            "discarded": 0}, name
    if "two_slots" in ahead_run:
        # epochs (2, 5) and (1): 4 steps, the last 3 ahead
        assert ahead_run["two_slots"]["counts"] == {
            "steps": 4, "ahead": 3, "discarded": 0}


@pytest.mark.subprocess
def test_lookahead_survives_a_retier_and_counts_a_dropped_step(ahead_run):
    """A retier between steps first reads the step in flight; the next
    admission drops the step nobody will read, and counts it."""
    run = ahead_run["retier"]
    assert run["freed"] > 0
    assert run["tokens"] == run["greedy"]
    assert run["counts"] == {"steps": 4, "ahead": 3, "discarded": 1}

"""The engine path under the flight recorder, on 2 virtual CPU devices
with the engine tests' dense configuration: the program's spans nest
from the scheduler step down to the fetch and step program calls, the
step program's parts are named scopes the engine maps its compiled ops
to, and the scopes leave the compiled program as it was."""
import json

import pytest
from conftest import _run_worker

WORKER = r"""
import collections, contextlib, json, re
import jax, jax.numpy as jnp, numpy as np
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
params = M.init_params(cfg, jax.random.PRNGKey(0))
mesh = make_mesh((2,), ("data",))
eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(2, 2, 1, 1), n_mb=1,
                          mb=1, max_len=32, fetch_mode="step")


def serve(n):
    be = EngineBackend(cfg, params, engine=eng, n_slots=1, max_len=32,
                       sampler=SamplerConfig())
    sched = ContinuousBatchingScheduler(be, SchedulerConfig())
    reqs = [Request(i, np.random.default_rng(i).integers(
        1, 256, 8).astype(np.int32), 4, arrival_s=0.0) for i in range(n)]
    return sched.serve(reqs)


out = {}
# untraced: the hot path never reaches the tracer
calls = []
for meth in ("_push", "span", "clock_sync", "now"):
    setattr(tr_ev.Tracer, meth + "_orig", getattr(tr_ev.Tracer, meth))
    setattr(tr_ev.Tracer, meth,
            (lambda m: lambda *a, **k: calls.append(m))(meth))
done = serve(1)
out["untraced_calls"] = len(calls)
out["untraced_tokens"] = [list(r.output) for r in done]
for meth in ("_push", "span", "clock_sync", "now"):
    setattr(tr_ev.Tracer, meth, getattr(tr_ev.Tracer, meth + "_orig"))

with tr_ev.tracing() as tr:
    done = serve(2)
out["tokens"] = [list(r.output) for r in done]
out["spans"] = [(e[0], e[2], e[2] + e[3]) for e in tr.events()
                if e[1] == "X" and e[4] == tr_ev.TRACK_PIPELINE]
out["scopes"] = [e[5] for e in tr.events() if e[0] == tr_ev.ENGINE_SCOPES]

# the step program compiled with and without its named scopes
st = eng.init_state(params)
args = (st["resident"], eng._fetch(st["offload"]), st["shared"],
        st["cache"], st["glob"], jnp.ones((1, 1, 1), jnp.int32),
        eng._kl_dev, eng._win_dev, eng._live_dev)


def opcodes(program):
    text = program.lower(*args).compile().as_text()
    return collections.Counter(re.findall(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.*?\s([a-z][\w\-]*)\(", text,
        re.M)), "lime." in text, text

with_scopes, marked, step_text = opcodes(eng._build_step(1))
out["step_hlo"] = step_text
out["resident_shapes"] = [list(x.shape) for x in
                          jax.tree.leaves(st["resident"])]
E._scope = lambda part: contextlib.contextmanager(lambda: (yield))()
without, unmarked, _ = opcodes(eng._build_step(1))
out["ops_with"], out["ops_without"] = dict(with_scopes), dict(without)
out["marked"], out["unmarked"] = marked, unmarked
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def traced():
    r = _run_worker(WORKER, devices=2)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _holder(span, spans, name):
    return [p for p in spans if p[0] == name and _inside(span, p)]


def test_engine_spans_nest_from_the_scheduler_step_down(traced):
    spans = traced["spans"]
    names = {s[0] for s in spans}
    assert {"sched.step", "engine.prefill", "backend.prefill",
            "engine.init_state", "engine.seed_cache", "engine.decode",
            "engine.dispatch", "engine.fetch", "engine.step",
            "backend.sample", "backend.sync"} <= names
    for child, parent in (("engine.fetch", "engine.dispatch"),
                          ("engine.step", "engine.dispatch"),
                          ("engine.dispatch", "engine.decode"),
                          ("backend.sample", "sched.step"),
                          ("backend.sync", "sched.step"),
                          ("engine.decode", "sched.step"),
                          ("backend.prefill", "engine.prefill"),
                          ("engine.init_state", "engine.prefill"),
                          ("engine.seed_cache", "engine.prefill"),
                          ("engine.prefill", "sched.step")):
        kids = [s for s in spans if s[0] == child]
        assert kids, child
        for s in kids:
            assert len(_holder(s, spans, parent)) == 1, (child, parent, s)
    # every decode step is one fetch and one step program call
    n_dec = sum(s[0] == "engine.decode" for s in spans)
    assert n_dec == sum(s[0] == "engine.fetch" for s in spans) \
        == sum(s[0] == "engine.step" for s in spans) > 0
    # two 4-token requests: 2 admissions, 3 decode steps each
    assert sum(s[0] == "engine.prefill" for s in spans) == 2 and n_dec == 6


def test_engine_scopes_map_step_ops_to_chunk_params(traced):
    """Every mapped op is a `lime.*` part, the layer scan is one, and
    what maps to the chunk's weights (`lime.chunk_params`, the per-layer
    pick) or to `lime.restore` copies no stack of layers: the scan reads
    each layer in place, so the part may compile to no op of its own."""
    from conftest import hlo_results, is_layer_stack, matrix_leaf_shapes
    from repro.core.engine import hlo_scopes
    by_module = {s["module"]: s for s in traced["scopes"]}
    assert set(by_module) == {"jit_step_fn", "jit_fetch_fn"}
    step = by_module["jit_step_fn"]["ops"]
    parts = set(step.values())
    assert "lime.layers" in parts
    assert all(p.startswith("lime.") for p in parts)
    assert set(by_module["jit_fetch_fn"]["ops"].values()) == {"lime.restore"}
    # the step program the test compiled with its scopes is the one the
    # tracer saw: every mapped op, under the same part
    _, ops = hlo_scopes(traced["step_hlo"])
    assert ops == step
    leaves = matrix_leaf_shapes(s[3:] for s in traced["resident_shapes"])
    weight_parts = {"lime.chunk_params", "lime.restore"}
    stacks = [(name, dims) for name, _, shapes in
              hlo_results(traced["step_hlo"])
              if ops.get(name) in weight_parts
              for dims in shapes if is_layer_stack(dims, leaves)]
    assert not stacks, stacks


def test_named_scopes_leave_the_compiled_step_as_it_was(traced):
    assert traced["marked"] and not traced["unmarked"]
    assert traced["ops_with"] == traced["ops_without"]
    assert sum(traced["ops_with"].values()) > 100


def test_tracing_changes_no_token_and_off_makes_no_tracer_call(traced):
    assert traced["untraced_calls"] == 0
    assert traced["untraced_tokens"][0] == traced["tokens"][0]


HLO = """HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()}

%fused_computation.1 (param_0.1: bf16[4]) -> bf16[4] {
  %param_0.1 = bf16[4]{0} parameter(0)
  ROOT %neg.1 = bf16[4]{0} negate(%param_0.1), metadata={op_name="jit(step_fn)/shard_map/while/body/lime.layers/while/body/neg"}
}

ENTRY %main.2 (p: bf16[4]) -> bf16[4] {
  %p = bf16[4]{0} parameter(0)
  %fusion.3 = bf16[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/shard_map/lime.chunk_params/concatenate"}
  %copy.4 = bf16[4]{0} copy(%fusion.3)
  ROOT %add.5 = bf16[4]{0} add(%copy.4, %p), metadata={op_name="jit(step_fn)/shard_map/add"}
}
"""


def test_hlo_scopes_read_the_innermost_part_of_each_instruction():
    from repro.core.engine import hlo_scopes
    module, ops = hlo_scopes(HLO)
    assert module == "jit_step_fn"
    assert ops == {"neg.1": "lime.layers", "fusion.3": "lime.chunk_params"}
    # a program that is one part maps every instruction to it, the
    # copies XLA inserts without metadata included
    _, whole = hlo_scopes(HLO, "restore")
    assert set(whole) == {"param_0.1", "neg.1", "p", "fusion.3", "copy.4",
                          "add.5"}
    assert set(whole.values()) == {"lime.restore"}

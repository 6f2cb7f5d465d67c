"""Heterogeneous ExecutionPlan execution + online retier (DESIGN.md §13).

The unification contract: the engine running an ExecutionPlan with unequal
per-stage splits — including one retiered mid-stream — must be
token-identical to the uniform path at bf16, on both the ref and Pallas
attention impls. Distributed cases re-exec in a subprocess with a forced
host device count (the test_engine.py convention).
"""
import numpy as np
import pytest

WORKER = r"""
import jax, jax.numpy as jnp, numpy as np, sys
import repro.core.engine as E
from repro.core.cost_model import ExecutionPlan, StageAlloc
from repro.configs.base import ModelConfig, Family
from repro.models import model as M
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
key = jax.random.PRNGKey(0)
# unequal per-stage splits (chunks of 3/1/1/1 layers over the same 8-layer
# model the uniform plan runs as 4 x 2-layer chunks; both grids pad)
HET = ExecutionPlan(n_seg=2, stages=[StageAlloc(2, 1), StageAlloc(0, 1),
                                     StageAlloc(2, 0), StageAlloc(0, 1)])
UNI = E.UniformPlan(4, 2, 1, 1)


def decode_tokens(mesh, plan, impl, steps=8, retier=None, headroom=0,
                  pre_demote=0):
    params = M.init_params(cfg, key)
    eng = E.InterleavedEngine(cfg, mesh, plan, n_mb=1, mb=2, max_len=32,
                              impl=impl, retier_headroom=headroom)
    if pre_demote:
        # counter-only retier before any state exists: init_state must
        # build the demoted layout directly
        none_state, freed = eng.retier(None, 0, pre_demote)
        assert none_state is None and freed > 0, freed
    state = eng.init_state(params)
    tok = jax.random.randint(key, (2, 1), 0, cfg.vocab_size)
    out = []
    for t in range(steps):
        lg, state = eng.decode_step(state, tok)
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok)[:, 0].copy())
        if retier and t in retier:
            stage, delta = retier[t]
            state, freed = eng.retier(state, stage, delta)
            assert (freed > 0) == (delta > 0), (delta, freed)
    return np.stack(out)


fails = []
for impl, shape, axes in (("ref", (4, 2), ("data", "model")),
                          ("pallas", (4,), ("data",))):
    # ref on the partial-auto (stage x model) mesh; pallas on the
    # stage-only mesh (Mosaic kernels cannot be partitioned over an auto
    # axis)
    mesh = make_mesh(shape, axes)
    base = decode_tokens(mesh, UNI, impl)
    cases = {
        "hetero": decode_tokens(mesh, HET, impl),
        # demote stage 0's resident slot after step 2, promote after 5 —
        # a mid-stream retier event must change no emitted token
        "retier": decode_tokens(mesh, HET, impl, headroom=1,
                                retier={2: (0, +1), 5: (0, -1)}),
        # demote BEFORE init_state (between-epoch counter-only path)
        "pre_demoted": decode_tokens(mesh, HET, impl, headroom=1,
                                     pre_demote=1),
    }
    for name, got in cases.items():
        ok = (got == base).all()
        print(f"{impl} {name}: tokens {'identical' if ok else 'MISMATCH'}")
        if not ok:
            fails.append((impl, name))
print("HETERO_OK" if not fails else f"FAILS {fails}")
sys.exit(1 if fails else 0)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_hetero_and_retier_token_identical(run_worker):
    """Heterogeneous ExecutionPlan (unequal per-stage k_res/k_off) and
    mid-stream retier events are token-identical to the uniform path at
    bf16, ref + Pallas."""
    r = run_worker(WORKER)
    assert r.returncode == 0 and "HETERO_OK" in r.stdout


# ----------------------------------------------------------------------------
# retier DURING speculative decoding (DESIGN.md §14): a demotion between
# spec rounds must not disturb losslessness — the resident self-draft
# thins, the verify pass still corrects everything
# ----------------------------------------------------------------------------
SPEC_RETIER_WORKER = r"""
import jax, jax.numpy as jnp, numpy as np, sys
import repro.core.engine as E
from repro.core.cost_model import ExecutionPlan, StageAlloc
from repro.configs.base import ModelConfig, Family
from repro.models import model as M
from repro.launch.mesh import make_mesh
from repro.specdec import greedy_verify

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
key = jax.random.PRNGKey(0)
HET = ExecutionPlan(n_seg=2, stages=[StageAlloc(2, 1), StageAlloc(0, 1),
                                     StageAlloc(2, 0), StageAlloc(0, 1)])
STEPS = 12


def make(mesh, impl):
    params = M.init_params(cfg, key)
    eng = E.InterleavedEngine(cfg, mesh, HET, n_mb=1, mb=2, max_len=48,
                              impl=impl, retier_headroom=1)
    return eng, eng.init_state(params)


def greedy(lg):
    return jnp.argmax(lg[:, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)


fails = []
for impl, shape, axes in (("ref", (4, 2), ("data", "model")),
                          ("pallas", (4,), ("data",))):
    mesh = make_mesh(shape, axes)
    tok0 = jax.random.randint(key, (2, 1), 0, cfg.vocab_size)

    # plain autoregressive greedy reference on the SAME hetero plan
    eng, st = make(mesh, impl)
    t, ref = tok0, []
    for _ in range(STEPS):
        lg, st = eng.decode_step(st, t)
        t = greedy(lg)
        ref.append(np.asarray(t)[:, 0].copy())
    ref = np.stack(ref)

    # resident self-spec loop, retiering stage 0 BETWEEN spec rounds:
    # demote after round 2 (the draft loses a resident layer mid-stream),
    # promote it back after round 4
    eng, st = make(mesh, impl)
    t = np.array(tok0, np.int32)
    out = [[], []]
    pos, rounds = 0, 0
    while min(len(o) for o in out) < STEPS:
        cur = jnp.asarray(t)
        drafts = np.zeros((2, 3), np.int32)
        for i in range(3):
            lg, st = eng.draft_step(st, cur)
            cur = greedy(lg)
            drafts[:, i] = np.asarray(cur)[:, 0]
        st = eng.rollback(st, pos)
        lg, st = eng.verify_step(st, jnp.asarray(
            np.concatenate([t, drafts], 1)))
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
        if rounds == 2:
            st, freed = eng.retier(st, 0, +1)
            assert freed > 0, freed
        if rounds == 4:
            st, freed = eng.retier(st, 0, -1)
            assert freed < 0, freed
    got = np.stack([np.asarray(o[:STEPS]) for o in out], 1)
    ok = (got == ref).all()
    print(f"{impl}: retier x spec tokens "
          f"{'identical' if ok else 'MISMATCH'} ({rounds} rounds)")
    if not ok:
        fails.append(impl)
print("SPEC_RETIER_OK" if not fails else f"FAILS {fails}")
sys.exit(1 if fails else 0)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_retier_during_spec_token_identical(run_worker):
    """Mid-stream demotion AND promotion between resident-draft spec
    rounds leave the committed stream token-identical to plain greedy
    decode on the same heterogeneous plan, ref + Pallas."""
    r = run_worker(SPEC_RETIER_WORKER)
    assert r.returncode == 0 and "SPEC_RETIER_OK" in r.stdout


# ----------------------------------------------------------------------------
# plan geometry (no mesh needed)
# ----------------------------------------------------------------------------
def _hetero_plan():
    from repro.core.cost_model import ExecutionPlan, StageAlloc
    return ExecutionPlan(n_seg=2, stages=[StageAlloc(4, 1), StageAlloc(2, 2),
                                          StageAlloc(6, 0),
                                          StageAlloc(0, 3)])


def test_execution_plan_geometry():
    p = _hetero_plan()
    assert p.n_stage == 4 and p.n_chunks == 8
    assert p.k_res_list == (2, 1, 3, 0)
    assert p.k_off_list == (1, 2, 0, 3)
    assert p.k_max == 3
    assert p.n_layers == 2 * (3 + 3 + 3 + 3)
    assert p.layers_total() == 24
    assert not p.is_uniform
    with pytest.raises(AssertionError):
        p.k_res                                        # noqa: B018


def test_uniform_plan_delegates_to_execution_plan():
    from repro.core.cost_model import ExecutionPlan
    from repro.core.engine import UniformPlan
    p = UniformPlan(4, 2, 1, 1)
    assert isinstance(p, ExecutionPlan)
    assert p.is_uniform
    assert (p.k_res, p.k_off, p.k) == (1, 1, 2)
    assert p.n_layers == p.n_chunks * p.k == 16


def test_plan_layout_hetero_and_demoted():
    from repro.core.engine import plan_layout
    p = _hetero_plan()
    res, off = plan_layout(p, headroom=2)
    dead = p.n_layers
    # chunk 0 (seg 0, stage 0): layers 0,1 resident + 2 streamed
    assert list(res[0, 0]) == [0, 1, dead]
    assert list(off[0, 0]) == [dead, dead, 2, dead, dead]
    # chunk 3 (stage 3): all streamed
    assert list(res[0, 3]) == [dead] * 3
    assert list(off[0, 3]) == [dead, dead, 9, 10, 11]
    # demote stage 0's last resident slot: its layer id moves into the
    # LAST headroom slot (order-preserving: right before the streamed tail)
    res_d, off_d = plan_layout(p, headroom=2, k_res_live=[1, 1, 3, 0])
    assert list(res_d[0, 0]) == [0, dead, dead]
    assert list(off_d[0, 0]) == [dead, 1, 2, dead, dead]


def test_split_layer_stack_hetero_roundtrip():
    import jax.numpy as jnp
    from repro.core.engine import split_layer_stack
    p = _hetero_plan()
    L = p.layers_total()
    stacked = {"w": jnp.arange(L * 3.0).reshape(L, 3)}
    res, off = split_layer_stack(stacked, p, headroom=1)
    H = 1
    flat = 0
    for c in range(p.n_chunks):
        s, d = c // p.n_stage, c % p.n_stage
        kr, ko = p.k_res_list[d], p.k_off_list[d]
        chunk = np.concatenate([np.asarray(res["w"][s, d, :kr]),
                                np.asarray(off["w"][s, d, H:H + ko])], 0)
        want = np.arange(flat * 3.0, (flat + kr + ko) * 3.0).reshape(-1, 3)
        np.testing.assert_array_equal(chunk, want)
        # padding slots are zero (identity layers)
        np.testing.assert_array_equal(np.asarray(res["w"][s, d, kr:]), 0.0)
        np.testing.assert_array_equal(np.asarray(off["w"][s, d, :H]), 0.0)
        np.testing.assert_array_equal(
            np.asarray(off["w"][s, d, H + ko:]), 0.0)
        flat += kr + ko


# ----------------------------------------------------------------------------
# plan_for regression (ISSUE 5 S1): layer counts that don't factor cleanly
# ----------------------------------------------------------------------------
def test_plan_for_covers_and_fits_budget():
    """The 2-segment fallback used to size k_res from floor-divided
    off_layers, claiming up to ~170x more resident bytes than the stage
    budget holds. Every emitted plan must cover cfg.n_layers AND keep
    n_seg * k_res resident layers inside the per-stage weight budget."""
    from repro.configs.base import Family, ModelConfig
    from repro.core.engine import plan_for
    for n_layers in range(1, 41):
        cfg = ModelConfig(name="t", family=Family.DENSE, n_layers=n_layers,
                          d_model=512, n_heads=8, n_kv_heads=8, d_ff=2048,
                          vocab_size=1024, head_dim=64)
        l_bytes = cfg.layer_params() * 2
        for n_stage in (2, 3, 4, 5, 8, 16):
            for frac, hbm in ((0.002, 5e7), (0.01, 2e8), (0.05, 1e9),
                              (0.3, 1e9), (0.6, 16e9)):
                plan = plan_for(cfg, n_stage, hbm_frac_for_weights=frac,
                                hbm_bytes=hbm)
                ctx = (n_layers, n_stage, frac, hbm, plan)
                assert plan.n_layers >= n_layers, ctx
                assert plan.k_res + plan.k_off == plan.k, ctx
                if plan.k_off:                # offloading: budget binds
                    assert plan.n_seg * plan.k_res * l_bytes \
                        <= hbm * frac + 1e-6, ctx

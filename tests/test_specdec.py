"""Speculative decoding (DESIGN.md §11): acceptance-rejection losslessness,
draft providers, multi-query kernels, verify/rollback through every decode
path, and serving integration."""
import numpy as np
import pytest

from repro.serving.sampling import SamplerConfig
from repro.specdec import (NgramDraft, SpecConfig, greedy_verify,
                           rejection_verify, target_probs)


# ----------------------------------------------------------------------------
# acceptance-rejection sampler
# ----------------------------------------------------------------------------
def test_greedy_verify_prefix_correction_bonus():
    V = 8
    lg = np.full((4, V), -10.0)
    lg[0, 3] = lg[1, 5] = lg[2, 1] = lg[3, 7] = 0.0   # argmax per position
    # full acceptance -> bonus appended
    assert greedy_verify(lg, [3, 5, 1], V) == [3, 5, 1, 7]
    # first mismatch commits the correction and stops
    assert greedy_verify(lg, [3, 2, 1], V) == [3, 5]
    assert greedy_verify(lg, [0, 5, 1], V) == [3]


def test_greedy_verify_ignores_padded_vocab():
    lg = np.zeros((2, 8))
    lg[:, 6] = 5.0        # real-vocab argmax
    lg[:, 7] = 99.0       # padding column must not win
    assert greedy_verify(lg, [6], real_vocab=7) == [6, 6]


def _hist(tokens, V):
    h = np.zeros(V)
    for t in tokens:
        h[t] += 1
    return h / len(tokens)


@pytest.mark.parametrize("point_mass", [True, False])
def test_rejection_verify_matches_target_distribution(point_mass):
    """The first committed token of a 1-draft round is exactly
    p-distributed, whatever the proposal: the statistical half of the
    losslessness contract."""
    rng = np.random.default_rng(0)
    V = 6
    p = np.array([[0.35, 0.05, 0.2, 0.1, 0.25, 0.05],
                  [1 / V] * V])            # bonus row (unused on reject)
    q = np.array([[0.1, 0.4, 0.1, 0.2, 0.1, 0.1]])
    n = 40_000
    out = []
    for _ in range(n):
        d = rng.choice(V, p=q[0])
        committed = rejection_verify(
            rng, p, [d] if not point_mass else [int(np.argmax(q[0]))],
            None if point_mass else q)
        out.append(committed[0])
    emp = _hist(out, V)
    # 3-sigma-ish band for n=40k multinomial cells
    assert np.abs(emp - p[0]).max() < 0.01, (emp, p[0])


def test_rejection_verify_full_acceptance_bonus_distribution():
    """Proposal == target: every draft accepted, the bonus token is drawn
    from the last row."""
    rng = np.random.default_rng(1)
    V = 4
    p = np.array([[0.25, 0.25, 0.25, 0.25],
                  [0.7, 0.1, 0.1, 0.1]])
    out = []
    for _ in range(20_000):
        d = rng.choice(V, p=p[0])
        committed = rejection_verify(rng, p, [d], p[:1])
        assert committed[0] == d          # q == p: acceptance is certain
        assert len(committed) == 2
        out.append(committed[1])
    emp = _hist(out, V)
    assert np.abs(emp - p[1]).max() < 0.015, emp


def test_target_probs_is_filtered_softmax():
    import jax.numpy as jnp
    lg = jnp.asarray([[1.0, 2.0, 3.0, 0.5, -1.0, 99.0]])
    # padding column (index 5) is cut by real_vocab
    p = target_probs(lg, SamplerConfig(temperature=1.0), 5)
    ref = np.exp([1.0, 2.0, 3.0, 0.5, -1.0])
    ref /= ref.sum()
    assert np.allclose(p[0], ref, atol=1e-6)
    assert abs(p[0].sum() - 1.0) < 1e-9
    # top_k=2 keeps exactly the two largest
    p2 = target_probs(lg, SamplerConfig(temperature=1.0, top_k=2), 5)
    assert (p2[0] > 0).sum() == 2 and p2[0, 2] > p2[0, 1] > 0


# ----------------------------------------------------------------------------
# draft providers
# ----------------------------------------------------------------------------
def test_ngram_draft_continues_repeated_pattern():
    d = NgramDraft(max_ngram=3)
    d.reset([1, 2, 3, 4, 9, 9, 1, 2, 3])
    toks, probs = d.propose(3)
    assert probs is None                  # point-mass draft
    assert list(toks[:2]) == [4, 9]       # continuation of the earlier match
    d.observe([4])
    toks, _ = d.propose(2)
    assert list(toks[:1]) == [9]          # match shifted by the new token


def test_ngram_draft_fallback_repeats_last():
    d = NgramDraft()
    d.reset([7])
    toks, _ = d.propose(4)
    assert list(toks) == [7, 7, 7, 7]


def test_small_model_draft_propose_is_snapshot(smoke_model):
    """propose() must not advance the committed cache: two proposals from
    the same state are identical, and observe() actually moves it.
    (The full cross-provider contract lives in test_draft_conformance.py;
    this pins the shift-by-one behaviour of the greedy model draft.)"""
    from repro.specdec import SmallModelDraft

    cfg, params = smoke_model
    d = SmallModelDraft(cfg, params, max_len=32)
    d.reset([3, 1, 4, 1, 5])
    a, _ = d.propose(3)
    b, _ = d.propose(3)
    assert list(a) == list(b)
    d.observe([int(a[0])])
    c, _ = d.propose(3)
    # after observing the first proposed token, the remaining proposal
    # shifts by one (greedy draft is deterministic)
    assert list(c[:2]) == list(a[1:])


# ----------------------------------------------------------------------------
# multi-query kernels (bit-wise contracts)
# ----------------------------------------------------------------------------
def _paged_case(key, B=2, Q=3, KV=2, G=2, dh=16, ps=8, P=12, dtype=None):
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Q, KV * G, dh), dtype)
    kp = jax.random.normal(k2, (P, ps, KV, dh), dtype)
    vp = jax.random.normal(k3, (P, ps, KV, dh), dtype)
    bt = jnp.array([[5, 2, -1], [7, 0, 3]], jnp.int32)
    ctx = jnp.array([14, 19], jnp.int32)      # incl. the Q new positions
    return q, kp, vp, bt, ctx


@pytest.mark.parametrize("window", [None, 6])
def test_mq_paged_kernel_bitwise_vs_blocked_ref_bf16(window):
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention import multiquery as mq
    q, kp, vp, bt, ctx = _paged_case(jax.random.PRNGKey(0))
    out_k = mq.mq_paged_decode_attention(q, kp, vp, bt, ctx, window=window)
    out_r = mq.mq_paged_decode_attention_ref(q, kp, vp, bt, ctx,
                                             window=window)
    assert out_k.dtype == jnp.bfloat16
    assert bool((out_k.view(jnp.uint16) == out_r.view(jnp.uint16)).all())


@pytest.mark.parametrize("dtype_name", ["bfloat16", "float32"])
def test_mq_paged_qlen1_reduces_to_paged_kernel(dtype_name):
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention import multiquery as mq
    from repro.kernels.decode_attention import paged as pg
    dtype = getattr(jnp, dtype_name)
    q, kp, vp, bt, ctx = _paged_case(jax.random.PRNGKey(1), Q=1,
                                     dtype=dtype)
    a = mq.mq_paged_decode_attention(q, kp, vp, bt, ctx)
    b = pg.paged_decode_attention(q, kp, vp, bt, ctx)
    bits = jnp.uint16 if dtype == jnp.bfloat16 else jnp.uint32
    assert bool((a.view(bits) == b.view(bits)).all())


def test_mq_contiguous_qlen1_reduces_to_decode_kernel():
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention import multiquery as mq
    from repro.kernels.decode_attention import ops as da_ops
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    B, KV, G, dh, S_c = 2, 2, 2, 16, 24
    q = jax.random.normal(k1, (B, 1, KV * G, dh), jnp.bfloat16)
    kc = jax.random.normal(k2, (B, S_c, KV, dh), jnp.bfloat16)
    vc = jax.random.normal(k3, (B, S_c, KV, dh), jnp.bfloat16)
    pos_ids = jnp.where(jnp.arange(S_c) < 14, jnp.arange(S_c), -1)
    a = mq.mq_decode_attention(q, kc, vc, pos_ids, jnp.int32(13))
    b = da_ops.decode_attention(q, kc, vc, pos_ids, jnp.int32(13))
    assert bool((a.view(jnp.uint16) == b.view(jnp.uint16)).all())


def test_mq_contiguous_matches_einsum_ref():
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention import multiquery as mq
    from repro.models.attention import mq_decode_attention_ref
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    B, Q, KV, G, dh, S_c = 2, 3, 2, 2, 16, 24
    q = jax.random.normal(k1, (B, Q, KV * G, dh), jnp.float32)
    kc = jax.random.normal(k2, (B, S_c, KV, dh), jnp.float32)
    vc = jax.random.normal(k3, (B, S_c, KV, dh), jnp.float32)
    pos_ids = jnp.where(jnp.arange(S_c) < 14, jnp.arange(S_c), -1)
    a = mq.mq_decode_attention(q, kc, vc, pos_ids, jnp.int32(11))
    b = mq_decode_attention_ref(q, kc, vc, pos_ids, jnp.int32(11),
                                window=None)
    assert float(jnp.abs(a - b).max()) < 1e-5


# ----------------------------------------------------------------------------
# model.verify_step: multi-token scoring == sequential decode + rollback
# ----------------------------------------------------------------------------
def test_verify_step_equals_sequential_decode_and_rolls_back(tiny_dense_cfg):
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    cfg = tiny_dense_cfg
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 5), 0, cfg.vocab_size)
    cache = M.init_cache(cfg, 2, 24)
    logits, cache = jax.jit(functools.partial(M.prefill, cfg))(
        params, toks, cache)

    seq_logits, fed = [], []
    c1 = dict(cache)
    cur = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None] \
        .astype(jnp.int32)
    fed.append(cur)
    for _ in range(3):
        lg, c1 = M.decode_step(cfg, params, c1, cur)
        seq_logits.append(lg[:, 0])
        cur = jnp.argmax(lg[:, 0, :cfg.vocab_size], -1)[:, None] \
            .astype(jnp.int32)
        fed.append(cur)

    vt = jnp.concatenate(fed[:3], axis=1)
    vl, c2 = M.verify_step(cfg, params, dict(cache), vt)
    sl = jnp.stack(seq_logits, 1)
    assert float(jnp.abs(vl.astype(jnp.float32)
                         - sl.astype(jnp.float32)).max()) < 1e-5
    assert int(c2["pos"]) == int(cache["pos"]) + 3

    # rollback: commit 1 of 3 by resetting pos; the next sequential step
    # must exactly reproduce the sequential path (stale future entries
    # are masked by pos_ids > pos)
    c2 = dict(c2)
    c2["pos"] = cache["pos"] + 1
    lg_a, _ = M.decode_step(cfg, params, c2, fed[1])
    assert float(jnp.abs(lg_a[:, 0].astype(jnp.float32)
                         - seq_logits[1].astype(jnp.float32)).max()) < 1e-6


def test_verify_step_rejects_recurrent_families():
    import jax

    from repro.configs.base import AttnKind, Family, ModelConfig
    from repro.models import model as M
    cfg = ModelConfig(name="s", family=Family.SSM, n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=0, d_ff=64, vocab_size=64,
                      head_dim=8, attn_kind=AttnKind.NONE,
                      ssm_state_size=16)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cache = M.init_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError):
        M.verify_step(cfg, params, cache,
                      np.zeros((1, 3), np.int32))


# ----------------------------------------------------------------------------
# paged KV rollback: block-table truncation
# ----------------------------------------------------------------------------
def test_block_table_truncate_frees_only_rejected_pages():
    from repro.kvcache import PagedKVConfig, PagedKVManager, PagePool
    mgr = PagedKVManager(PagePool(PagedKVConfig(page_size=4,
                                                device_pages=8)))
    assert mgr.admit(0, 10)               # 3 pages
    assert mgr.extend(0, 15)              # 4 pages (spec round drafts 5)
    assert mgr.pages_of(0) == 4
    dropped = mgr.truncate(0, 11)         # commit 1 of 5
    assert dropped == 1 and mgr.pages_of(0) == 3
    assert mgr.tokens_of(0) == 11
    assert mgr.pool.free_pages() == 5
    # partial page shared by committed + rejected slots stays allocated
    assert mgr.truncate(0, 9) == 0 and mgr.pages_of(0) == 3
    assert mgr.truncate(0, 8) == 1 and mgr.pages_of(0) == 2


def test_paged_decode_verify_commit_lossless_vs_dense(tiny_dense_cfg):
    """Spec decode over PagedDecodeCache (verify + truncating commit)
    emits token-for-token the dense autoregressive sequence."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kvcache.paged_decode import PagedDecodeCache
    from repro.models import model as M
    cfg = tiny_dense_cfg
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                              cfg.vocab_size)
    cache = M.init_cache(cfg, 2, 32)
    logits, cache = jax.jit(functools.partial(M.prefill, cfg))(
        params, toks, cache)
    first = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)

    # dense AR reference
    c1 = dict(cache)
    cur = first[:, None].astype(jnp.int32)
    want = [[int(first[b])] for b in range(2)]
    for _ in range(6):
        lg, c1 = M.decode_step(cfg, params, c1, cur)
        cur = jnp.argmax(lg[:, 0, :cfg.vocab_size], -1)[:, None] \
            .astype(jnp.int32)
        for b in range(2):
            want[b].append(int(cur[b, 0]))

    # paged spec decode: garbage drafts, greedy verification
    pc = PagedDecodeCache(cfg, 2, 32, page_size=4)
    pc.seed(cache)
    got = [[int(first[b])] for b in range(2)]
    cur = np.array(first)[:, None].astype(np.int32)
    rng = np.random.default_rng(0)
    freed_any = False
    while min(len(g) for g in got) < 7:
        k = 3
        draft = rng.integers(0, cfg.vocab_size, (2, k)).astype(np.int32)
        mat = np.concatenate([cur, draft], axis=1)
        lg = np.asarray(pc.verify(params, mat), np.float32)
        after_verify = pc.pages_in_use
        committed = [greedy_verify(lg[b], draft[b], cfg.vocab_size)
                     for b in range(2)]
        c = min(len(x) for x in committed)
        pc.commit(c)
        freed_any |= pc.pages_in_use < after_verify
        for b in range(2):
            got[b].extend(committed[b][:c])
            cur[b, 0] = committed[b][c - 1]
    got = [g[:7] for g in got]
    assert got == want, (got, want)
    assert freed_any                      # rollback actually freed pages


# ----------------------------------------------------------------------------
# serving integration (sim_backend: the conftest E3 fleet factory)
# ----------------------------------------------------------------------------
def test_sim_spec_exact_counts_and_counters(sim_backend):
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)
    arr = make_arrivals("bursty", 8, seed=0, burst_size=4, gap_s=4.0,
                        prompt_len=64, max_new_tokens=19)
    sched = ContinuousBatchingScheduler(
        sim_backend(4, spec=SpecConfig(k=4, acceptance=0.6, seed=0)),
        SchedulerConfig())
    done = sched.serve(requests_from_arrivals(arr))
    assert all(r.done and r.generated == 19 for r in done)
    rep = summarize(done, pattern="bursty", backend="sim",
                    stats=sched.stats)
    assert rep.spec_rounds > 0 and rep.spec_drafted > 0
    assert 0.0 < rep.spec_acceptance_rate < 1.0
    assert rep.spec_accepted <= rep.spec_drafted
    assert np.isfinite(rep.decode_tok_s_p50)


def test_sim_spec_beats_autoregressive_throughput(sim_backend):
    """The bench_specdec acceptance invariant, in-suite."""
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)
    out = {}
    for name, spec in (("ar", None),
                       ("spec", SpecConfig(k=4, acceptance=0.6, seed=0))):
        arr = make_arrivals("sporadic", 4, seed=0, gap_s=4.0,
                            prompt_len=64, max_new_tokens=24)
        sched = ContinuousBatchingScheduler(sim_backend(1, spec=spec),
                                            SchedulerConfig())
        done = sched.serve(requests_from_arrivals(arr))
        out[name] = summarize(done, pattern="sporadic", backend="sim",
                              stats=sched.stats)
    assert out["spec"].throughput_tok_s > out["ar"].throughput_tok_s


def test_sim_resident_spec_acceptance_and_depth_follow_tier(sim_backend):
    """draft='resident' in the simulator: acceptance scales with the
    plan's resident fraction and the DepthController shrinks k with it
    (DESIGN.md §14). E3/llama2-13b allocates fully resident, so the base
    plan sits at the configured acceptance; a fully demoted plan drops to
    the clipped floor and k collapses to 1."""
    import dataclasses

    from repro.core.cost_model import ExecutionPlan

    full = sim_backend(1, spec=SpecConfig(k=6, draft="resident",
                                          acceptance=0.9, seed=0))
    assert full._res_frac0 == pytest.approx(1.0)
    assert full._spec_acceptance() == pytest.approx(0.9)
    assert full._spec_k() == 6          # 0.9/(1-0.9) = 9, clipped to k

    base = full.plan
    stages = [dataclasses.replace(
        st, resident_total=0,
        off_full_seg=st.off_full_seg + st.resident_total // base.n_seg)
        for st in base.stages]
    thin = sim_backend(1, spec=SpecConfig(k=6, draft="resident",
                                          acceptance=0.9, seed=0),
                       plan=ExecutionPlan(n_seg=base.n_seg, stages=stages))
    assert thin._res_frac0 == pytest.approx(0.0)
    assert thin._spec_acceptance() == pytest.approx(0.02)   # clip floor
    assert thin._spec_k() == 1


def test_controller_external_drafts_mode(tiny_dense_cfg):
    """The engine backend drafts on-device: the controller must build no
    host providers, treat begin/observe as no-ops, and refuse propose."""
    from repro.specdec import SpecDecodeController
    ctl = SpecDecodeController(SpecConfig(k=3, draft="resident"),
                               SamplerConfig(), tiny_dense_cfg, 2,
                               external_drafts=True)
    assert ctl.drafts is None
    ctl.begin(0, [1, 2, 3])
    ctl.observe(0, [4])
    with pytest.raises(AssertionError):
        ctl.propose(0, 3)


@pytest.mark.parametrize("draft", ["ngram", "resident"])
@pytest.mark.parametrize("paged", [False, True])
def test_engine_backend_spec_lossless_single_device(paged, draft,
                                                    smoke_model):
    """Greedy spec serving == autoregressive serving, token for token,
    through the dense and paged single-device paths, for both the n-gram
    and the resident-tier self-draft (DESIGN.md §14)."""
    from repro.serving import (ContinuousBatchingScheduler, EngineBackend,
                               Request, SchedulerConfig)
    cfg, params = smoke_model

    def run(spec):
        be = EngineBackend(cfg, params, n_slots=2, max_len=48, paged=paged,
                           page_size=8, spec=spec)
        reqs = [Request(0, None, max_new_tokens=12, prompt_len=6),
                Request(1, None, max_new_tokens=9, prompt_len=4)]
        done = ContinuousBatchingScheduler(be, SchedulerConfig()).serve(
            reqs)
        return {r.rid: list(r.output) for r in done}, be

    base, _ = run(None)
    spec_out, be = run(SpecConfig(k=3, draft=draft))
    assert base == spec_out
    assert be.spec_stats["spec_rounds"] > 0


def test_engine_backend_spec_model_draft_accepts(smoke_model):
    """A draft that shares the target's weights accepts most tokens —
    the accept path (not just rejection) is exercised end to end."""
    from repro.serving import (ContinuousBatchingScheduler, EngineBackend,
                               Request, SchedulerConfig)
    cfg, params = smoke_model

    def run(spec):
        be = EngineBackend(cfg, params, n_slots=1, max_len=48, spec=spec)
        reqs = [Request(0, None, max_new_tokens=12, prompt_len=6)]
        done = ContinuousBatchingScheduler(be, SchedulerConfig()).serve(
            reqs)
        return {r.rid: list(r.output) for r in done}, be

    base, _ = run(None)
    out, be = run(SpecConfig(k=3, draft="model", draft_arch="gemma3-1b"))
    assert base == out
    assert be.spec_stats["spec_accepted"] > 0


def test_engine_backend_spec_stochastic_counts(smoke_model):
    """temperature > 0: the rejection sampler drives serving to exact
    per-request token counts (distribution-level losslessness is
    test_rejection_verify_matches_target_distribution)."""
    from repro.serving import (ContinuousBatchingScheduler, EngineBackend,
                               Request, SchedulerConfig)
    from repro.serving.sampling import SamplerConfig as SC
    cfg, params = smoke_model
    be = EngineBackend(cfg, params, n_slots=2, max_len=48,
                       sampler=SC(temperature=0.8, top_p=0.95),
                       spec=SpecConfig(k=3, draft="ngram", seed=7))
    reqs = [Request(0, None, max_new_tokens=10, prompt_len=6),
            Request(1, None, max_new_tokens=7, prompt_len=4)]
    done = ContinuousBatchingScheduler(be, SchedulerConfig()).serve(reqs)
    by = {r.rid: r for r in done}
    assert by[0].generated == 10 and len(by[0].output) == 10
    assert by[1].generated == 7 and len(by[1].output) == 7
    assert all(0 <= t < cfg.vocab_size
               for r in done for t in r.output)


# ----------------------------------------------------------------------------
# the interleaved engine: one pipeline round verifies k tokens
# ----------------------------------------------------------------------------
ENGINE_WORKER = r"""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, Family
import repro.core.engine as E
from repro.models import model as M
from repro.launch.mesh import make_mesh
from repro.serving import (ContinuousBatchingScheduler, Request,
                           SchedulerConfig, EngineBackend)
from repro.specdec import SpecConfig

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
params = M.init_params(cfg, jax.random.PRNGKey(0))

# ref on the partial-auto (stage x model) mesh; pallas on the stage-only
# mesh (Mosaic kernels cannot be partitioned over an auto axis)
for impl, shape, axes in (("ref", (4, 2), ("data", "model")),
                          ("pallas", (4,), ("data",))):
    mesh = make_mesh(shape, axes)
    def run(spec):
        eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(4, 2, 0, 1),
                                  n_mb=2, mb=1, max_len=48, impl=impl)
        be = EngineBackend(cfg, params, engine=eng, n_slots=2, max_len=48,
                           spec=spec)
        reqs = [Request(0, None, max_new_tokens=10, prompt_len=6),
                Request(1, None, max_new_tokens=8, prompt_len=4)]
        done = ContinuousBatchingScheduler(be, SchedulerConfig()).serve(reqs)
        return {r.rid: list(r.output) for r in done}, be
    base, _ = run(None)
    spec_out, be = run(SpecConfig(k=3, draft="ngram"))
    stats = be.spec_stats
    ok = base == spec_out and stats["spec_rounds"] > 0
    print(f"{impl}: spec==AR {base == spec_out} stats={stats}")
    assert ok, (impl, base, spec_out)
print("ENGINE_SPEC_OK")
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_spec_decode_lossless_ref_and_pallas(run_worker):
    """temperature=0 spec decoding through the InterleavedEngine equals
    autoregressive decoding token-for-token, on both the ref and Pallas
    attention paths (subprocess: needs >= 4 host devices)."""
    r = run_worker(ENGINE_WORKER)
    assert r.returncode == 0 and "ENGINE_SPEC_OK" in r.stdout

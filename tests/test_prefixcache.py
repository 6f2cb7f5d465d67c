"""Radix prefix-cache subsystem (DESIGN.md §12): tree match/insert/evict
invariants, COW admission through the paged manager, scheduler integration
(hits, chunked prefill, leak-freedom), and the engine-tier losslessness
contract (prefix-hit decode token-identical to cold; chunked prefill
bitwise-equal to monolithic)."""
import pytest

from repro.kvcache import BlockTable, PagedKVConfig, PagedKVManager, PagePool
from repro.kvcache.pool import DEVICE, HOST
from repro.prefixcache import RadixPrefixCache


def _pool(dev=16, host=8, ps=4, page_bytes=8.0):
    return PagePool(PagedKVConfig(page_size=ps, device_pages=dev,
                                  host_pages=host, page_bytes=page_bytes))


def _table(pool, tokens):
    t = BlockTable(pool.page_size)
    pool.extend_table(t, tokens)
    return t


# ----------------------------------------------------------------------------
# radix tree: match / insert / evict
# ----------------------------------------------------------------------------
def test_radix_insert_match_page_aligned():
    pool = _pool()
    tree = RadixPrefixCache(pool)
    toks = list(range(100, 110))        # 10 tokens, ps=4 -> 2 full pages
    t = _table(pool, 10)
    assert tree.insert(toks, t.pages) == 2
    assert tree.n_pages == 2
    # full match returns both pages; the partial last page never caches
    pages, n = tree.match(toks)
    assert n == 8 and pages == t.pages[:2]
    # max_pages cap (admission leaves >= 1 token to prefill)
    pages, n = tree.match(toks, max_pages=1)
    assert n == 4 and pages == t.pages[:1]
    # diverging second page: only the first page matches
    other = toks[:4] + [999] * 6
    pages, n = tree.match(other)
    assert n == 4 and pages == t.pages[:1]
    # no match at all
    assert tree.match([7, 7, 7, 7, 7])[1] == 0
    pool.release_table(t)
    assert pool.alloc.used_pages == tree.n_pages == 2


def test_radix_insert_increfs_pages_outlive_table():
    pool = _pool()
    tree = RadixPrefixCache(pool)
    toks = list(range(8))
    t = _table(pool, 8)
    tree.insert(toks, t.pages)
    assert pool.alloc.refcount(t.pages[0]) == 2
    pool.release_table(t)
    assert pool.alloc.used_pages == 2   # the tree still owns them
    pages, n = tree.match(toks, max_pages=1)
    assert n == 4
    tree.release_all()
    assert pool.alloc.used_pages == 0


def test_radix_insert_existing_key_keeps_first_copy():
    pool = _pool()
    tree = RadixPrefixCache(pool)
    toks = list(range(8))
    a, b = _table(pool, 8), _table(pool, 8)
    assert tree.insert(toks, a.pages) == 2
    assert tree.insert(toks, b.pages) == 0      # same keys: first wins
    assert tree.match(toks)[0] == a.pages[:2]
    assert pool.alloc.refcount(b.pages[0]) == 1  # b's copy not adopted
    pool.release_table(a)
    pool.release_table(b)
    tree.release_all()
    assert pool.alloc.used_pages == 0


def test_radix_evict_lru_leaves_and_refcount_pinning():
    pool = _pool(dev=16)
    tree = RadixPrefixCache(pool)
    t1 = _table(pool, 8)                # stream A: 2 pages
    t2 = _table(pool, 8)                # stream B: 2 pages
    a = [1, 1, 1, 1, 2, 2, 2, 2]
    b = [3, 3, 3, 3, 4, 4, 4, 4]
    tree.insert(a, t1.pages)
    tree.insert(b, t2.pages)
    pool.release_table(t1)              # A unpinned
    tree.match(a)                       # A recently used; B is LRU...
    # ...but B is pinned by t2, so eviction must take A's leaf instead
    assert tree.evict(1) == 1
    assert tree.match(a)[1] == 4        # A's leaf gone, root page stays
    assert tree.match(b)[1] == 8        # pinned B untouched
    pool.release_table(t2)
    assert tree.evict(10) == 3          # everything else reclaimable
    assert tree.n_pages == 0 and pool.alloc.used_pages == 0


def test_radix_evict_exposes_parents():
    pool = _pool()
    tree = RadixPrefixCache(pool)
    t = _table(pool, 12)                # 3-page chain
    tree.insert(list(range(12)), t.pages)
    pool.release_table(t)
    assert tree.evict(3) == 3           # leaf, then its parent, then root
    assert tree.n_pages == 0 and pool.alloc.used_pages == 0


# ----------------------------------------------------------------------------
# manager: COW admission over a matched prefix
# ----------------------------------------------------------------------------
def test_admit_with_prefix_shares_and_releases_cleanly():
    pool = _pool(dev=8)
    tree = RadixPrefixCache(pool)
    mgr = PagedKVManager(pool)
    toks = list(range(10))
    donor = _table(pool, 10)
    tree.insert(toks, donor.pages)
    pool.release_table(donor)
    pages, ctok = tree.match(toks, max_pages=(10 - 1) // 4)
    assert ctok == 8
    assert mgr.can_admit_prefix(11, pages)
    moved = mgr.admit_with_prefix(1, pages, ctok, 11)
    assert moved == 0.0                 # all matched pages on-device
    t = mgr.table(1)
    assert t.pages[:2] == pages and t.tokens == 11
    assert pool.alloc.refcount(pages[0]) == 2   # tree + table
    # COW: growth appends fresh pages, never touches shared ones
    assert mgr.extend(1, 13)
    assert t.pages[:2] == pages and len(t.pages) == 4
    mgr.release(1)
    assert pool.alloc.used_pages == tree.n_pages == 2
    tree.release_all()
    assert pool.alloc.used_pages == 0


def test_admit_with_prefix_fetches_host_pages_and_prices_them():
    pool = _pool(dev=8, host=8, page_bytes=100.0)
    tree = RadixPrefixCache(pool)
    mgr = PagedKVManager(pool)
    toks = list(range(8))
    donor = _table(pool, 8)
    tree.insert(toks, donor.pages)
    pool.release_table(donor)
    pool.migrate(tree.match(toks)[0], HOST)     # delegated cached pages
    pages, ctok = tree.match(toks, max_pages=1)
    assert pool.tier_of(pages[0]) == HOST
    moved = mgr.admit_with_prefix(5, pages, ctok, 6)
    assert moved == 100.0                       # the hit paid the fetch
    assert pool.tier_of(pages[0]) == DEVICE
    mgr.release(5)
    tree.release_all()
    assert pool.alloc.used_pages == 0


def test_can_admit_prefix_counts_suffix_only():
    pool = _pool(dev=4)
    tree = RadixPrefixCache(pool)
    mgr = PagedKVManager(pool)
    donor = _table(pool, 12)            # 3 of 4 device pages
    tree.insert(list(range(12)), donor.pages)
    pool.release_table(donor)
    pages, ctok = tree.match(list(range(12)), max_pages=3)
    # cold would need 4 pages (16 tokens) -> impossible; with the prefix
    # only 1 fresh page is needed
    assert not mgr.can_admit(13 + 1)
    assert mgr.can_admit_prefix(13 + 1, pages)
    tree.release_all()


def test_spill_keeps_shared_pages_on_device():
    """Preempt-spill must not migrate pages another owner still shares:
    the co-resident request attends them, and moving them would overstate
    free device capacity (the admission watermark would over-commit)."""
    pool = _pool(dev=8, host=8, page_bytes=10.0)
    tree = RadixPrefixCache(pool)
    mgr = PagedKVManager(pool)
    toks = list(range(12))
    donor = _table(pool, 12)
    tree.insert(toks, donor.pages)
    pool.release_table(donor)
    pages, ctok = tree.match(toks, max_pages=2)
    mgr.admit_with_prefix(1, pages, ctok, 13)       # A: 2 shared + 2 own
    mgr.admit_with_prefix(2, pages, ctok, 13)       # B shares the prefix
    a_own = [p for p in mgr.table(1).pages if p not in pages]
    moved = mgr.preempt(1, "spill")
    assert moved == len(a_own) * 10.0               # only A's own pages
    assert all(pool.tier_of(p) == DEVICE for p in pages)
    assert all(pool.tier_of(p) == HOST for p in a_own)
    assert mgr.resume(1) == len(a_own) * 10.0       # fetch only what left
    assert all(pool.tier_of(p) == DEVICE for p in mgr.table(1).pages)
    mgr.release(1)
    mgr.release(2)
    tree.release_all()
    assert pool.alloc.used_pages == 0


def test_evict_tier_aware_skips_host_pages():
    """A caller starved for device pages gains nothing from dropping
    host-tier cached leaves — tier-restricted eviction skips them (and
    untiered eviction still reclaims everything)."""
    pool = _pool(dev=8, host=8)
    tree = RadixPrefixCache(pool)
    t = _table(pool, 8)
    tree.insert(list(range(8)), t.pages)
    pool.release_table(t)
    host_page = tree.match(list(range(8)))[0][1]    # the leaf
    pool.migrate([host_page], HOST)
    assert tree.evict(1, tier=DEVICE) == 0          # leaf is host-tier,
    assert tree.n_pages == 2                        # its parent shielded
    assert tree.evict(2) == 2                       # untiered: all go
    assert pool.alloc.used_pages == 0


# ----------------------------------------------------------------------------
# scheduler integration over the simulator (sim_backend: conftest factory)
# ----------------------------------------------------------------------------
def _serve_shared(sim_backend, prefix: bool, chunk=None, budget_pages=None,
                  n_req=16, prompt=256, prefix_len=192, max_new=16):
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)

    arr = make_arrivals("shared_prefix", n_req, seed=0, n_templates=2,
                        prefix_len=prefix_len, prompt_len=prompt,
                        max_new_tokens=max_new, rate_rps=2.0)
    budget = (budget_pages * 32) if budget_pages \
        else 6 * (prompt + max_new)
    sched = ContinuousBatchingScheduler(sim_backend(4, prompt=prompt),
                                        SchedulerConfig(
        kv_budget_tokens=budget, kv_policy="paged", page_size=32,
        prefix_cache=prefix, prefill_chunk_tokens=chunk))
    done = sched.serve(requests_from_arrivals(arr))
    rep = summarize(done, pattern="shared_prefix", backend="sim",
                    stats=sched.stats)
    return sched, done, rep


def test_prefix_cache_hits_and_no_leaks(sim_backend):
    sched, done, rep = _serve_shared(sim_backend, True)
    assert all(r.done and r.generated == r.max_new_tokens for r in done
               if not r.rejected)
    assert rep.prefix_hit_rate > 0.5
    assert rep.prefill_tokens_saved > 0
    assert rep.cached_tokens == sched.prefix.n_pages * 32
    # leak-freedom: after every request released, only the radix tree
    # holds pages
    pool = sched.mgr.pool
    assert pool.alloc.used_pages == sched.prefix.n_pages
    sched.prefix.release_all()
    assert pool.alloc.used_pages == 0


def test_prefix_cache_improves_prefill_latency(sim_backend):
    _, _, cold = _serve_shared(sim_backend, False)
    _, _, warm = _serve_shared(sim_backend, True)
    assert warm.ttft_prefill_p50_s < cold.ttft_prefill_p50_s
    assert warm.ttft_p50_s < cold.ttft_p50_s


def test_prefix_cache_requires_paged_policy(sim_backend):
    from repro.serving import ContinuousBatchingScheduler, SchedulerConfig

    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(sim_backend(2), SchedulerConfig(
            kv_policy="reserve", prefix_cache=True))


def test_admission_accounts_cached_pages(sim_backend):
    """The _admits fix: a prefix hit must be admitted where a cold request
    of the same length would not fit — cached pages don't count against
    the free pool."""
    from repro.serving import (ContinuousBatchingScheduler, Request,
                               SchedulerConfig)
    from repro.serving.traffic import template_tokens

    be = sim_backend(2, prompt=96)
    # budget: 5 pages of 32 = 160 tokens; a 96+4=100-token request needs
    # 4 pages cold
    sched = ContinuousBatchingScheduler(be, SchedulerConfig(
        kv_budget_tokens=160, kv_policy="paged", page_size=32,
        prefix_cache=True))
    prompt = template_tokens(0, 96)
    r0 = Request(0, prompt.copy(), max_new_tokens=4)
    done = sched.serve([r0])
    assert done[0].done
    assert sched.prefix.n_pages == 3        # 96/32 pages donated
    # now 3 of 5 pages are cached; a cold 100-token request (4 pages)
    # could only be admitted by evicting — a hit needs just 2 fresh pages
    r1 = Request(1, prompt.copy(), max_new_tokens=4)
    pages, ctok = sched._lookup(r1)
    assert ctok == 64                       # capped below the last token
    assert sched._admits(r1)
    sched._on_admit(r1)
    assert r1.cached_tokens == 64
    assert sched.mgr.table(r1.rid).pages[:2] == pages
    sched.mgr.release(r1.rid)
    sched.prefix.release_all()
    assert sched.mgr.pool.alloc.used_pages == 0


def test_cached_pages_evicted_before_preemption(sim_backend):
    """Pool pressure reclaims unpinned radix pages first: with the tree
    holding most of a tiny pool, a burst must still complete without the
    tree deadlocking admission, and eviction must actually fire."""
    sched, done, rep = _serve_shared(sim_backend, True, budget_pages=22,
                                     n_req=12)
    assert all(r.done and r.generated == r.max_new_tokens for r in done
               if not r.rejected)
    assert sched.prefix.evicted_pages > 0
    pool = sched.mgr.pool
    assert pool.alloc.used_pages == sched.prefix.n_pages


def test_chunked_prefill_same_results_and_mixed_rounds(sim_backend):
    """Chunked prefill completes every request with its exact token count
    and emits first tokens only after the full prompt drained."""
    schedm, donem, repm = _serve_shared(sim_backend, False, chunk=None)
    schedc, donec, repc = _serve_shared(sim_backend, False, chunk=64)
    for done in (donem, donec):
        assert all(r.done and r.generated == r.max_new_tokens
                   for r in done if not r.rejected)
    served = [r for r in donec if not r.rejected]
    assert all(r.first_token_s >= r.admitted_s for r in served)
    # chunking never loses tokens vs monolithic
    assert sum(r.generated for r in donec) == sum(r.generated
                                                  for r in donem)


def test_chunked_prefill_with_prefix_hits(sim_backend):
    sched, done, rep = _serve_shared(sim_backend, True, chunk=64)
    assert all(r.done and r.generated == r.max_new_tokens for r in done
               if not r.rejected)
    assert rep.prefix_hit_rate > 0.5
    pool = sched.mgr.pool
    assert pool.alloc.used_pages == sched.prefix.n_pages


def test_multiturn_traffic_hits_grow_over_turns(sim_backend):
    from repro.serving import (ContinuousBatchingScheduler, SchedulerConfig,
                               make_arrivals, requests_from_arrivals,
                               summarize)

    arr = make_arrivals("multiturn", 9, seed=1, turns=3, prompt_len=64,
                        max_new_tokens=8, rate_rps=1.0)
    sched = ContinuousBatchingScheduler(sim_backend(2, prompt=64),
                                        SchedulerConfig(
        kv_policy="paged", page_size=16, prefix_cache=True))
    done = sched.serve(requests_from_arrivals(arr))
    rep = summarize(done, pattern="multiturn", backend="sim",
                    stats=sched.stats)
    assert all(r.done for r in done if not r.rejected)
    # turn >= 2 re-sends the conversation: its turn-1 prefix must hit
    assert rep.prefix_hit_rate > 0.3
    assert sched.mgr.pool.alloc.used_pages == sched.prefix.n_pages


# ----------------------------------------------------------------------------
# engine tier: losslessness of prefix-hit decode + chunked prefill
# ----------------------------------------------------------------------------
PREFIX_LOSSLESS_WORKER = r"""
import sys
import numpy as np, jax
import jax.numpy as jnp
from repro.configs.registry import get_smoke_config
from repro.models import model as M
from repro.serving import (ContinuousBatchingScheduler, EngineBackend,
                           Request, SchedulerConfig)
from repro.kvcache.paged_decode import PagedDecodeCache

impl = sys.argv[1]
cfg = get_smoke_config("gemma3-1b")
params = M.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(7)
P = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)

# (a) prefix-hit decode token-identical to a cold run of the same prompt
be = EngineBackend(cfg, params, n_slots=1, max_len=64, prefix_cache=True,
                   page_size=8)
be._paged_cache = None  # force construction with chosen impl below
pc, radix = be._prefix_structures()
pc.impl = impl
outs = []
for epoch in range(2):
    r = Request(epoch, P.copy(), max_new_tokens=6)
    done = ContinuousBatchingScheduler(be, SchedulerConfig()).serve([r])
    outs.append(list(done[0].output))
st = be.prefix_stats
assert st["prefix_hits"] >= 1, st
assert outs[0] == outs[1], (impl, outs)
print(f"{impl}: warm==cold tokens OK {outs[0][:4]}...")

# (b) chunked prefill bitwise-equal to monolithic at bf16
last = {}
for chunk in (0, 7, 16):
    pc = PagedDecodeCache(cfg, 1, 64, page_size=8, impl=impl)
    last[chunk] = np.asarray(pc.prefill(params, P[None, :], chunk=chunk),
                             np.float32)
    pc.release()
    assert pc.pool.alloc.used_pages == 0
for chunk in (7, 16):
    assert (last[chunk] == last[0]).all(), (impl, chunk)
print(f"{impl}: chunked==monolithic bitwise OK")
"""


ENGINE_CHUNK_WORKER = r"""
import functools, sys
import jax, jax.numpy as jnp
jnp.bfloat16 = jnp.float32   # fp32 => losslessness must be (near-)exact
import repro.core.engine as E
from repro.configs.base import ModelConfig, Family
from repro.models import model as M
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="d", family=Family.DENSE, n_layers=8, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16)
key = jax.random.PRNGKey(0)
mesh = make_mesh((4, 2), ("data", "model"))
params = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_params(cfg, key))
toks = jax.random.randint(key, (1, 10), 1, cfg.vocab_size)

# reference: the classic dense prefill adopted via seed_cache
eng = E.InterleavedEngine(cfg, mesh, E.UniformPlan(4, 2, 1, 1), n_mb=1,
                          mb=1, max_len=32)
cache = jax.tree.map(
    lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
    M.init_cache(cfg, 1, 32))
ref_logits, cache = jax.jit(functools.partial(M.prefill, cfg))(
    params, toks, cache)
ref_last = ref_logits[:, -1].astype(jnp.float32)

# partial-context prefill rounds through the pipeline itself
state = eng.init_state(params)
lg, state = eng.prefill_partial(state, toks, chunk=4)
got_last = lg[:, -1].astype(jnp.float32)
err = float(jnp.abs(got_last[:, :cfg.vocab_size]
                    - ref_last[:, :cfg.vocab_size]).max())
pos = int(jax.device_get(state["glob"]["pos"]))
print(f"prefill_partial: pos={pos} worst={err:.2e}")
ok = err < 5e-4 and pos == 10

# the built cache must decode equivalently to the seeded one
tok = jnp.argmax(ref_last[:, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
seeded = eng.seed_cache(eng.init_state(params), cache)
for step in range(3):
    lg_a, state = eng.decode_step(state, tok)
    lg_b, seeded = eng.decode_step(seeded, tok)
    err = float(jnp.abs(lg_a.astype(jnp.float32)
                        - lg_b.astype(jnp.float32)).max())
    print(f"decode step {step}: worst={err:.2e}")
    ok = ok and err < 5e-4
    tok = jnp.argmax(lg_b[:, :cfg.vocab_size].astype(jnp.float32),
                     -1)[:, None].astype(jnp.int32)
sys.exit(0 if ok else 1)
"""


@pytest.mark.slow
@pytest.mark.subprocess
def test_engine_prefill_partial_matches_dense_prefill(run_worker):
    """Partial-context prefill rounds through the interleaved pipeline
    (chunked verify steps) build the same cache the classic dense
    prefill + seed_cache adoption does: same last-position logits, same
    subsequent decode."""
    r = run_worker(ENGINE_CHUNK_WORKER)
    assert r.returncode == 0


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_engine_prefix_hit_lossless_and_chunk_bitwise(impl, run_worker):
    """The §12 losslessness contract on real KV: a prefix-hit decode emits
    token-identical output to a cold run of the same prompt, and chunked
    prefill is bitwise-equal to monolithic (bf16), for both the blocked
    jnp reference and the Pallas kernel (interpret on CPU).
    (devices=None: this worker needs the real 1-device CPU.)"""
    r = run_worker(PREFIX_LOSSLESS_WORKER, impl, devices=None)
    assert r.returncode == 0

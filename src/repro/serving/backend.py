"""Execution backends for LIME-Serve (DESIGN.md §9).

One protocol, two substrates:

  EngineBackend  the real thing — prefill on GSPMD params, cache adoption
                 into the InterleavedEngine layout, real sampled tokens,
                 wall-clock time. Batch membership is fixed once the caches
                 are seeded (`can_join_running = False`): the scheduler
                 runs it in epochs.
  SimBackend     the discrete-event InterleavedPipelineSim on a CostEnv —
                 virtual time, per-step micro-batch occupancy, planner/KV
                 protocol effects. Slots are bookkeeping
                 (`can_join_running = True`): continuous batching.

The protocol (duck-typed; SimBackend and EngineBackend are the reference
implementations):

  n_slots            micro-batch slots the substrate co-schedules
  can_join_running   may the scheduler refill freed slots mid-flight?
  now()              current time (wall or virtual, seconds)
  advance_to(t)      idle until t (arrival wait)
  kv_budget_tokens() fleet KV capacity in tokens, or None (unbounded)
  start_batch(reqs)  admit an idle-state batch; returns first token per
                     request (None where the substrate has no real tokens)
  decode_active(slots, more)
                     one decode step; {slot: token-or-None} per live slot.
                     `more`: the slots that still want a token after this
                     one (the scheduler's rule); a backend may dispatch
                     their next step before it returns this one's
  join(slot, req)    mid-flight admission (only if can_join_running)
  release(slot)      slot freed by the scheduler
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.cost_model import CostEnv
from repro.core.pipeline_sim import InterleavedPipelineSim
from repro.obs import trace as tr_ev
from repro.obs.trace import get_tracer


# ============================================================================
# Simulator backend
# ============================================================================
class SimBackend:
    """Discrete-event substrate: prices each decode step by live occupancy.

    Per-request KV accounting feeds the OnlinePlanner: every step passes
    kv_tokens = ceil(Σ_active ctx_i / n_micro_env), the effective
    per-stream token count under the Workload's n_micro scaling — so the
    TS thresholds (paper Eq. 5) fire exactly when the *admitted* KV load
    says they should, not on a fixed token loop.
    """

    can_join_running = True

    def __init__(self, env: CostEnv, plan=None, *, n_slots: int = 0,
                 use_planner: bool = True, use_kv_transfer: bool = True,
                 prompt_tokens: int = 64, spec=None, adapt: bool = False,
                 refit: bool = False, true_env: Optional[CostEnv] = None):
        if plan is None:
            from repro.core.offline_scheduler import allocate
            r = allocate(env, env.work.cfg.n_layers,
                         n_emp=max(prompt_tokens, 1))
            if not r.feasible:
                raise ValueError(f"infeasible allocation: {r.reason}")
            plan = r.plan
        self.env = env
        self.plan = plan
        self.n_slots = n_slots or max(env.work.n_micro, 1)
        self.sim = InterleavedPipelineSim(
            env, plan, use_planner=use_planner,
            use_kv_transfer=use_kv_transfer, prompt_tokens=prompt_tokens,
            true_env=true_env)
        # online re-fit (DESIGN.md §18): observe the sim's fetch/compute
        # telemetry and fold measured drift back into the planned env
        self.refit = None
        if refit:
            from repro.tune.refit import OnlineRefit
            self.refit = OnlineRefit(env)
            self.sim.attach_refit(self.refit)
        self._ctx: Dict[int, int] = {}        # slot -> prompt + generated
        self._kv_pages = None                 # (pages_in_use, page_size)
        # adaptation telemetry (DESIGN.md §13): planner (α, β) moves are
        # reported in whole-layer equivalents; scheduler-driven reclaims
        # (reclaim_kv_pages) force-advance the TS ladder and credit the
        # freed bytes to the admission page pool. `adapt` gates the
        # reclaim hook — with it off (default) admission pressure behaves
        # exactly as the static plan (preempt, never retier).
        self.adapt = adapt
        self._pool = None
        self._adapt = {"retier_events": 0, "layers_demoted": 0,
                       "layers_promoted": 0, "hbm_returned_bytes": 0.0}
        # speculative decoding (DESIGN.md §11): the simulator has no real
        # tokens to verify, so a spec config prices each decode round as a
        # (k+1)-query verify pass and draws per-slot accepted counts from
        # the acceptance-rate model (each draft token independently
        # accepted with prob spec.acceptance, stopping at the first
        # rejection — the geometric shape real rejection sampling has).
        # draft="resident" (DESIGN.md §14) scales that acceptance by the
        # LIVE resident fraction — the plan's resident share minus
        # whatever the TS ladder has demoted — and adapts draft depth per
        # rung through a DepthController, so planner demotions visibly
        # thin the self-draft exactly as they do on the real engine.
        self.spec = spec
        self._depth = None
        if spec is not None:
            from repro.specdec import SpecStats
            self._spec_rng = np.random.default_rng(spec.seed)
            self._spec_stats = SpecStats()
            if spec.draft == "resident":
                total = max(plan.layers_total(), 1)
                self._res_frac0 = min(
                    sum(st.resident_total for st in plan.stages) / total,
                    1.0)
                if spec.adapt_k:
                    from repro.specdec import DepthController
                    self._depth = DepthController(
                        k_max=spec.k, prior=self._spec_acceptance())

    # -- clock -------------------------------------------------------------------
    def now(self) -> float:
        return self.sim.now

    def advance_to(self, t: float) -> None:
        self.sim.advance_to(t)

    # -- capacity ----------------------------------------------------------------
    def kv_budget_tokens(self) -> Optional[int]:
        """Fleet KV capacity in per-request tokens: aggregate memory left
        after weights, divided by the per-token-per-sequence KV rate
        (kv_bytes_per_token_layer covers the whole mb × n_micro set)."""
        cfg = self.env.work.cfg
        w = self.env.work
        per_seq = w.kv_bytes_per_token_layer() \
            / (max(w.mb, 1) * max(w.n_micro, 1))
        rate = cfg.n_layers * per_seq
        if rate <= 0:
            return None                       # attention-free: KV is not a budget
        agg = sum(d.mem_bytes for d in self.env.devices)
        budget = max(agg - cfg.total_params() * 2, agg * 0.03)
        return int(budget // rate)

    def kv_bytes_per_token(self) -> float:
        """Fleet KV bytes one context token costs one sequence (page
        pricing for the paged scheduler's spill/fetch accounting)."""
        cfg = self.env.work.cfg
        w = self.env.work
        return cfg.n_layers * w.kv_bytes_per_token_layer() \
            / (max(w.mb, 1) * max(w.n_micro, 1))

    # -- paged-KV hooks (DESIGN.md §10) ------------------------------------------
    def note_kv_pages(self, pages_in_use: int, page_size: int) -> None:
        """Scheduler callback: current page-granular occupancy. Attaches
        the planner/KV-transfer accounting to *allocated* pages, so the TS
        ladder (paper Eq. 5) fires on what admission actually holds."""
        self._kv_pages = (pages_in_use, page_size)

    def note_slo_pressure(self, pressure: float) -> None:
        """Scheduler callback (DESIGN.md §17): forward SLO pressure
        (1 - health) to the sim's OnlinePlanner so its TS thresholds
        fire early while the serving layer is breaching."""
        if self.sim.planner is not None:
            self.sim.planner.note_slo_pressure(pressure)

    def attach_page_pool(self, pool) -> None:
        """Expose a PagePool to the simulator so Eq. 8 volumes move real
        pages (core/kv_transfer.sync_pool) every step, and to the
        adaptation path so retiered weight bytes grow its device tier."""
        self.sim.attach_page_pool(pool)
        self._pool = pool

    # -- online memory adaptation (DESIGN.md §13) --------------------------------
    def _planner_snapshot(self):
        pl = self.sim.planner
        return [(st.alpha, st.beta) for st in pl.states] if pl else None

    def _note_planner_delta(self, before) -> None:
        """Fold planner (α, β) moves since `before` into the adaptation
        telemetry (whole-layer equivalents: a layer = 1 MHA + 1 MLP).
        Gated on `adapt`: a static run's report keeps the documented
        'zero when --adapt is off' contract even on workloads where the
        sim's own TS ladder fires."""
        pl = self.sim.planner
        if not self.adapt or pl is None or before is None:
            return
        w = self.env.work
        tr = get_tracer()
        factor = max(self.plan.n_seg - 1, 1)
        for dev, ((a0, b0), st) in enumerate(zip(before, pl.states)):
            da, db = st.alpha - a0, st.beta - b0
            if not (da or db):
                continue
            self._adapt["retier_events"] += 1
            self._adapt["layers_demoted"] += max(max(da, db), 0)
            self._adapt["layers_promoted"] += max(-min(da, db), 0)
            self._adapt["hbm_returned_bytes"] += max(
                (da * w.attn_block_bytes + db * w.mlp_block_bytes) * factor,
                0.0)
            if tr is not None:
                tr.instant(tr_ev.RETIER, track=tr_ev.TRACK_KV,
                           args={"dev": dev,
                                 "demoted": max(max(da, db), 0),
                                 "promoted": max(-min(da, db), 0)})

    def _sim_step(self, **kw):
        before = self._planner_snapshot()
        trace = self.sim.step_once(**kw)
        self._note_planner_delta(before)
        tr = get_tracer()
        if tr is not None:
            # StepTrace -> trace events: sim and engine render identically
            # (one "step" span per pipeline round on the "pipeline" track)
            t1 = self.sim.now
            tr.complete(tr_ev.STEP, ts=t1 - trace.latency,
                        dur=trace.latency, track=tr_ev.TRACK_PIPELINE,
                        args={"load_stall": trace.load_stall,
                              "comm_time": trace.comm_time,
                              "kv_moved_bytes": trace.kv_moved_bytes})
            if trace.planner_fired:
                tr.instant(tr_ev.PLANNER_FIRED, ts=t1,
                           track=tr_ev.TRACK_PIPELINE)
        return trace

    def reclaim_kv_pages(self, n_pages: int) -> int:
        """Scheduler pressure hook: force-advance the TS ladder (demote
        blocks ahead of their occupancy thresholds) and return the freed
        bytes as device KV pages. The simulator prices the added
        per-segment load on every subsequent step — adaptation trades
        steady-state load for preemption churn. Returns pages granted."""
        pl = self.sim.planner
        if not self.adapt or pl is None or self._pool is None:
            return 0
        pb = self._pool.cfg.page_bytes
        if pb <= 0:
            return 0
        w = self.env.work
        factor = max(self.plan.n_seg - 1, 1)
        snap = [(st.alpha, st.beta, st.plan_idx) for st in pl.states]
        adapt_snap = dict(self._adapt)
        freed = 0.0
        need = n_pages * pb
        advanced = True
        while freed < need and advanced:
            advanced = False
            for st in pl.states:
                lad = pl.ladders[st.dev_idx]
                if st.plan_idx >= len(lad):
                    continue
                step = lad[st.plan_idx]
                da, db = step.alpha - st.alpha, step.beta - st.beta
                gain = (da * w.attn_block_bytes
                        + db * w.mlp_block_bytes) * factor
                st.alpha, st.beta = step.alpha, step.beta
                st.plan_idx += 1
                advanced = True
                if gain > 0:
                    freed += gain
                    self._adapt["retier_events"] += 1
                    self._adapt["layers_demoted"] += max(max(da, db), 0)
                    self._adapt["hbm_returned_bytes"] += gain
                if freed >= need:
                    break
        pages = int(freed // pb)
        if pages <= 0:
            # nothing granted: roll the ladder (and its telemetry) back —
            # the preemption happens anyway; paying extra per-segment
            # load for zero pages would be pure loss
            for st, (a, b, i) in zip(pl.states, snap):
                st.alpha, st.beta, st.plan_idx = a, b, i
            self._adapt = adapt_snap
            return 0
        self._pool.grow(pages)
        tr = get_tracer()
        if tr is not None:
            tr.instant(tr_ev.RETIER, track=tr_ev.TRACK_KV,
                       args={"forced": True, "pages": pages})
        return pages

    @property
    def adapt_stats(self):
        return dict(self._adapt)

    def charge_transfer(self, nbytes: float) -> None:
        """Preemption spill/fetch traffic: advances the virtual clock."""
        self.sim.charge_transfer(nbytes)

    # -- serving hooks -----------------------------------------------------------
    @staticmethod
    def _prefill_span(req) -> int:
        # a recompute-resumed request re-prefills prompt + generated
        return getattr(req, "prefill_tokens", None) or req.prompt_len

    @staticmethod
    def _prefill_q(req) -> int:
        """Query positions the prefill pass actually computes: the span
        minus whatever the radix prefix cache (or a spill that kept the
        KV) already holds — Eq. 5-8 bytes and hops are priced for the
        uncached suffix only (DESIGN.md §12)."""
        span = SimBackend._prefill_span(req)
        cached = getattr(req, "cached_tokens", 0)
        return max(span - cached, 1)

    def start_batch(self, reqs: Sequence) -> List[Optional[int]]:
        out: List[Optional[int]] = []
        for slot, r in enumerate(reqs):
            self._ctx[slot] = self._prefill_span(r)
        # prefill: one pipeline pass; each micro-batch carries its own
        # uncached-suffix query count (attention still reads the full
        # span, hence ctx = the longest context in the batch)
        self._sim_step(ctx=max((self._prefill_span(r) for r in reqs),
                                   default=1),
                           n_micro=max(len(reqs), 1),
                           kv_tokens=self._planner_tokens(),
                           q_lens=[self._prefill_q(r) for r in reqs] or [1])
        for slot, r in enumerate(reqs):
            self._ctx[slot] += 1
            out.append(None)                  # sim has no real token ids
        return out

    def join(self, slot: int, req) -> Optional[int]:
        # mid-flight admission: the joiner's prefill rides one step at its
        # own prompt span before it starts decoding with the others
        span = self._prefill_span(req)
        self._ctx[slot] = span
        self._sim_step(ctx=max(span, 1), n_micro=1,
                           kv_tokens=self._planner_tokens(),
                           q_len=self._prefill_q(req))
        self._ctx[slot] += 1
        return None

    # -- chunked prefill / mixed rounds (DESIGN.md §12) --------------------------
    def attach_slot(self, slot: int, req, ctx0: int) -> None:
        """Register a slot whose prompt will drain through decode_mixed
        chunks; `ctx0` is the context already in KV (radix prefix hit or
        a spill that kept the pages)."""
        self._ctx[slot] = max(ctx0, 0)

    def decode_mixed(self, work: Dict[int, tuple]):
        """One mixed round: {slot: ("prefill", n_tokens, last_chunk) |
        ("decode",)}. Every stream rides the same weight-stream — the
        chunk's compute and hops scale with its q_len, decode streams
        with 1 (or k+1 under speculation) — so a cold prompt no longer
        stalls live decoders for a monolithic pass. Prefill slots emit
        [None] (their first token) when the last chunk lands, [] before;
        decode slots emit their committed round."""
        if not work:
            return {}
        slots = sorted(work)
        q_lens, out = [], {}
        spec_slots = []
        k = self._spec_k() if self.spec is not None else 0
        for s in slots:
            w = work[s]
            if w[0] == "prefill":
                q_lens.append(max(w[1], 1))
            elif self.spec is not None:
                q_lens.append(k + 1)
                spec_slots.append(s)
            else:
                q_lens.append(1)
        ctx = max(self._ctx[s] + (work[s][1] if work[s][0] == "prefill"
                                  else 1) for s in slots)
        self._sim_step(ctx=ctx, n_micro=len(slots),
                           kv_tokens=self._planner_tokens(), q_lens=q_lens)
        for s in slots:
            w = work[s]
            if w[0] == "prefill":
                self._ctx[s] += w[1]
                if w[2]:                      # last chunk: first token
                    self._ctx[s] += 1
                    out[s] = [None]
                else:
                    out[s] = []
            elif s in spec_slots:
                out[s] = [None] * self._spec_commit(s, k)
            else:
                self._ctx[s] += 1
                out[s] = [None]
        return out

    def _demoted_layers(self) -> int:
        """Whole-layer equivalents the TS ladder currently holds demoted
        (the sim's retier rung; max(α, β) per device, the convention
        _note_planner_delta reports in)."""
        pl = self.sim.planner
        if pl is None:
            return 0
        return sum(max(st.alpha, st.beta) for st in pl.states)

    def _resident_frac(self) -> float:
        """Live resident share: the plan's static fraction minus ladder
        demotions."""
        total = max(self.plan.layers_total(), 1)
        return min(max(self._res_frac0 - self._demoted_layers() / total,
                       0.0), 1.0)

    def _spec_acceptance(self) -> float:
        """Per-token acceptance of the model: flat for ngram/model drafts;
        for the resident self-draft it scales with the live resident
        fraction (a thinner draft stack proposes worse tokens)."""
        if self.spec.draft != "resident":
            return self.spec.acceptance
        return min(max(self.spec.acceptance * self._resident_frac(),
                       0.02), 0.98)

    def _spec_k(self) -> int:
        """Round depth: spec.k, or the DepthController's rung-adapted k
        for the resident draft (rung = ladder-demoted layers)."""
        if self._depth is None:
            return self.spec.k
        self._depth.note_rung(self._demoted_layers(),
                              prior=self._spec_acceptance())
        return self._depth.k()

    def _spec_commit(self, s: int, k: Optional[int] = None) -> int:
        """Draw one slot's committed count from the acceptance model and
        advance its context (shared by decode_active and mixed rounds)."""
        k = self.spec.k if k is None else k
        a = self._spec_acceptance()
        acc = 0
        while acc < k and self._spec_rng.random() < a:
            acc += 1
        committed = acc + 1          # accepted prefix + correction/bonus
        self._ctx[s] += committed
        self._spec_stats.rounds += 1
        self._spec_stats.drafted += k
        self._spec_stats.accepted += acc
        if self._depth is not None:
            self._depth.note_round(k, acc)
        return committed

    def decode_active(self, slots: Sequence[int], more: Sequence[int] = ()):
        if not slots:
            return {}
        ctx = max(self._ctx[s] for s in slots)
        if self.spec is not None:
            return self._decode_active_spec(slots, ctx)
        self._sim_step(ctx=ctx, n_micro=len(slots),
                           kv_tokens=self._planner_tokens())
        for s in slots:
            self._ctx[s] += 1
        return {s: None for s in slots}

    def _decode_active_spec(self, slots: Sequence[int], ctx: int):
        """One speculative round: price a (k+1)-query verify pass, then
        commit 1..k+1 tokens per slot from the acceptance model."""
        k = self._spec_k()
        self._sim_step(ctx=ctx, n_micro=len(slots),
                           kv_tokens=self._planner_tokens(), q_len=k + 1)
        return {s: [None] * self._spec_commit(s, k) for s in slots}

    @property
    def spec_stats(self):
        return self._spec_stats.to_dict() if self.spec is not None else None

    def release(self, slot: int) -> None:
        self._ctx.pop(slot, None)

    def _planner_tokens(self) -> int:
        n_micro_env = max(self.env.work.n_micro, 1)
        if self._kv_pages is not None:
            pages, ps = self._kv_pages        # real page occupancy
            return -(-(pages * ps) // n_micro_env)
        total = sum(self._ctx.values())
        return -(-total // n_micro_env)       # ceil-div


# ============================================================================
# Engine backend (real execution; single-device fallback without an engine)
# ============================================================================
class EngineBackend:
    """Wall-clock substrate over the InterleavedEngine (or the plain
    single-host decode path when engine is None — 1-device smoke runs).

    Epoch batching: cache seeding fixes batch membership, so freed slots
    pad the pipeline until the epoch drains (can_join_running = False).
    Arrival waits don't sleep — advance_to() skews the clock, so a trace
    with long idle gaps benches in real compute time while latency math
    still sees the gaps.
    """

    can_join_running = False

    def __init__(self, cfg, params, *, engine=None, n_slots: int = 0,
                 max_len: int = 512, sampler=None, prompt_seed: int = 0,
                 paged: bool = False, page_size: int = 64, spec=None,
                 prefix_cache: bool = False, prefill_chunk_tokens: int = 0,
                 cache_pages: int = 0, planner=None, refit: bool = False):
        import jax

        from repro.models import model as M
        from repro.serving.sampling import SamplerConfig

        self.cfg = cfg
        self.params = params
        self.engine = engine
        self.max_len = max_len
        # online memory adaptation (DESIGN.md §13): an OnlinePlanner walks
        # its TS ladder on the scheduler's page occupancy (note_kv_pages)
        # and fires retier events on the live engine — demoted resident
        # layers return their HBM to the admission page pool. The
        # scheduler may also force demotions (reclaim_kv_pages) before
        # preempting a request.
        self.planner = planner
        # online re-fit on the real engine (DESIGN.md §18): wall-clock
        # weight-load / stage-compute timings go in via note_load_timing
        # and fold drift back into the planner's CostEnv
        self.refit = None
        if refit and planner is not None:
            from repro.tune.refit import OnlineRefit
            if not isinstance(planner.env.devices, list):
                planner.env.devices = list(planner.env.devices)
            self.refit = OnlineRefit(planner.env, planner)
        self._pool = None                 # admission PagePool (scheduler's)
        self._grants = []                 # reclaim-driven (stage, pages)
        self._reclaim_dry = False         # retier slots too small to grant
        self._adapt = {"retier_events": 0, "layers_demoted": 0,
                       "layers_promoted": 0, "hbm_returned_bytes": 0.0}
        # radix prefix cache over the real paged pool (DESIGN.md §12):
        # prompts matched against cached pages, only the uncached suffix
        # prefilled, finished requests donate their pages back. Rides the
        # single-device paged path (with an engine, chunked prefill is
        # available via prefill_partial; page sharing needs the paged
        # pool, which the engine tier keeps per-slot-dense).
        if prefix_cache and engine is not None:
            raise NotImplementedError(
                "prefix_cache shares real KV pages through the "
                "single-device paged pool; the engine's per-stage cache "
                "layout has no shared pool to fork from")
        self.prefix_cache = prefix_cache
        self.chunk = max(int(prefill_chunk_tokens), 0)
        self._cache_pages = cache_pages   # radix headroom (0 -> one full
                                          # batch's worth of extra pages)
        self._radix = None
        self._slot_tokens = None          # per-slot donatable prompt ids
        self._slot_out = None             # per-slot committed output ids
        self._saved_tokens = 0            # prompt tokens seeded from cache
        if prefix_cache:
            paged = True
        # speculative decoding (DESIGN.md §11): real drafts, real
        # multi-token verification. The shared-pos cache layout (prompts
        # left-padded, one position counter per batch) forces lockstep
        # commits: every live slot advances by the min accepted count and
        # the rest re-verifies next round — lossless either way, since
        # re-verification redraws from the same target conditional.
        self.spec = spec
        self._ctl = None
        self._pos = 0                         # host mirror of cache pos
        # resident self-draft (DESIGN.md §14): with an engine, k tokens
        # are drafted ON the pipeline itself (draft_requests — resident
        # tier only, zero weight streaming) and the host providers are
        # skipped; without one, each slot gets a ResidentDraft over the
        # bottom spec.resident_layers of the target's own stack. Depth
        # adapts per retier rung through a DepthController.
        self._resident_engine = (spec is not None
                                 and spec.draft == "resident"
                                 and engine is not None)
        self._depth = None
        if spec is not None:
            from repro.configs.base import Family
            if cfg.family not in (Family.DENSE, Family.MOE):
                raise ValueError(
                    f"speculative decoding needs pure-KV per-layer state "
                    f"(DENSE/MOE), not {cfg.family}")
            if self._resident_engine and engine.k_res_cap == 0:
                raise ValueError(
                    "draft='resident' needs a resident tier; this "
                    "engine's plan streams every layer (k_res == 0)")
            if spec.draft == "resident" and spec.adapt_k:
                from repro.specdec import DepthController
                self._depth = DepthController(k_max=spec.k,
                                              prior=spec.acceptance)
            # verify windows must not wrap the cache ring: cap rounds at
            # the ACTUAL KV length (sliding-window caches have
            # S_c = window < max_len), not max_len. Past the ring end the
            # plain ring-aware step takes over (decode_active fallback).
            if paged and engine is None:
                self._spec_cap = max_len      # pool slots, no ring
            elif engine is not None:
                self._spec_cap = min(engine.S_c, max_len)
            else:
                self._spec_cap = min(M.kv_cache_len(cfg, max_len), max_len)
        # paged=True routes the single-device path through the paged
        # decode (block-table gather attention, kvcache/paged_decode);
        # with an engine, pass paged=True to the engine itself instead
        # (slot-level page accounting + paged seed_cache adoption).
        self.paged = paged and engine is None
        self.page_size = page_size
        self._paged_cache = None
        self.sampler = sampler if sampler is not None else SamplerConfig()
        # batch_width: what the compiled step expects (fixed); n_slots:
        # what the scheduler may co-schedule (sporadic serves 1 through a
        # wide engine — the spare slots ride as padding)
        self.batch_width = (engine.n_mb * engine.mb) if engine is not None \
            else max(n_slots or 1, 1)
        self.n_slots = min(n_slots, self.batch_width) if n_slots \
            else self.batch_width
        self._key = jax.random.PRNGKey(self.sampler.seed)
        self._prompt_rng_seed = prompt_seed
        self._prefill = jax.jit(functools.partial(M.prefill, cfg))
        self._decode = jax.jit(functools.partial(M.decode_step, cfg)) \
            if engine is None else None
        self._verify = jax.jit(functools.partial(M.verify_step, cfg)) \
            if (engine is None and not self.paged and spec is not None) \
            else None
        self._t0 = time.monotonic()
        self._skew = 0.0
        self._state = None
        self._cur = None                      # (batch_width, 1) last tokens
        # one-step look-ahead (DESIGN.md §9): the decode step dispatched
        # before the host read its predecessor's ids, as (ids, the slots
        # it decodes); the ids stay on the device until read
        self._ahead = None
        self._ahead_n = {"steps": 0, "ahead": 0, "discarded": 0}

    # -- clock -------------------------------------------------------------------
    def now(self) -> float:
        return (time.monotonic() - self._t0) + self._skew

    def advance_to(self, t: float) -> None:
        cur = self.now()
        if t > cur:
            self._skew += t - cur
            tr = get_tracer()
            if tr is not None:
                tr.clock_sync()

    # -- capacity ----------------------------------------------------------------
    def kv_budget_tokens(self) -> Optional[int]:
        # the engine's cache is statically shaped: max_len per slot
        return self.n_slots * self.max_len

    def kv_bytes_per_token(self) -> float:
        return 2.0 * self.cfg.n_layers * self.cfg.n_kv_heads \
            * self.cfg.head_dim * 2.0         # k+v, bf16

    def max_request_tokens(self) -> Optional[int]:
        """Per-slot ceiling: a single request's prompt + max_new must fit
        the statically-shaped cache, regardless of pooled headroom."""
        return self.max_len

    def fits_batch(self, batch: Sequence, req) -> bool:
        """Epoch-composition constraint: prompts are LEFT-padded to the
        batch max, so every co-scheduled request decodes from position
        max(prompt_len) — each one's max_prompt + own max_new must fit
        max_len or its cache writes clamp at the last row (silent
        corruption)."""
        cand = list(batch) + [req]
        mp = max(r.prompt_len for r in cand)
        return all(mp + r.max_new_tokens <= self.max_len for r in cand)

    # -- helpers -----------------------------------------------------------------
    def _materialize_prompt(self, r) -> np.ndarray:
        if r.prompt is not None:
            return np.asarray(r.prompt, np.int32)
        rng = np.random.default_rng(self._prompt_rng_seed + r.rid)
        n = max(r.prompt_len, 1)
        return rng.integers(1, self.cfg.vocab_size, size=n).astype(np.int32)

    def _pad_prompts(self, prompts: List[np.ndarray]):
        import jax.numpy as jnp
        S = max(len(p) for p in prompts)
        toks = np.zeros((len(prompts), S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, S - len(p):] = p          # left-pad
        return jnp.asarray(toks)

    def _sample(self, logits):
        import jax

        from repro.serving.sampling import sample
        self._key, k = jax.random.split(self._key)
        return sample(logits, self.sampler, k, self.cfg.vocab_size)

    # -- online memory adaptation (DESIGN.md §13) --------------------------------
    def attach_page_pool(self, pool) -> None:
        """Scheduler hook: the admission PagePool that retiered weight HBM
        is credited to (grow on demote, shrink on promote)."""
        self._pool = pool

    def _page_bytes(self) -> float:
        pb = self._pool.cfg.page_bytes if self._pool is not None else 0.0
        return pb or self.kv_bytes_per_token() * self.page_size

    def _apply_retier(self, stage: int, delta: int) -> float:
        """Move `delta` slots of `stage` across the tier boundary on the
        live engine state (counter-only between epochs — init_state builds
        the demoted layout). Returns HBM bytes freed (< 0 on promote)."""
        eng = self.engine
        self._drain()
        before = eng.demoted(stage)
        self._state, freed = eng.retier(self._state, stage, delta)
        moved = abs(eng.demoted(stage) - before)
        if moved:
            self._adapt["retier_events"] += 1
            key = "layers_demoted" if freed > 0 else "layers_promoted"
            self._adapt[key] += moved
            self._adapt["hbm_returned_bytes"] += max(freed, 0.0)
            self._sync_depth_rung()
            tr = get_tracer()
            if tr is not None:
                tr.instant(tr_ev.ENGINE_RETIER, track=tr_ev.TRACK_KV,
                           args={"stage": stage, "moved": moved,
                                 "direction": ("demote" if freed > 0
                                               else "promote"),
                                 "freed_bytes": freed})
        return freed

    def _sync_depth_rung(self) -> None:
        """Tell the DepthController the tier boundary moved: the new rung
        (total demoted slots) starts from an acceptance prior scaled by
        the LIVE resident fraction — a demotion shrinks k immediately
        instead of waiting for rejections to pile up (DESIGN.md §14)."""
        if self._depth is None or self.engine is None:
            return
        eng = self.engine
        rung = sum(eng.demoted(d) for d in range(eng.plan.n_stage))
        self._depth.note_rung(
            rung, prior=self.spec.acceptance * eng.resident_fraction())

    def _retier_to(self, stage: int, target_demoted: int) -> None:
        """Planner-driven: demote until `stage` has target_demoted slots
        streamed (whole-layer mapping of the planner's (α, β) blocks)."""
        eng = self.engine
        cap = min(eng.k_res_b[stage], eng.H)
        delta = min(target_demoted, cap) - eng.demoted(stage)
        if delta <= 0:
            return
        freed = self._apply_retier(stage, delta)
        if self._pool is not None and freed > 0:
            self._pool.grow(int(freed // self._page_bytes()))

    def note_kv_pages(self, pages_in_use: int, page_size: int) -> None:
        """Scheduler callback with page-granular KV occupancy: walk the
        planner's TS ladder (paper Eq. 5) on what admission actually
        holds, retier the live pipeline on fired plans, and promote
        pressure-driven demotions back when occupancy leaves headroom."""
        if self.engine is None:
            return
        if self.planner is not None:
            for dev, step in self.planner.on_pages(pages_in_use, page_size):
                if dev < self.engine.plan.n_stage:
                    self._retier_to(dev, max(step.alpha, step.beta))
        self._maybe_promote()

    def note_slo_pressure(self, pressure: float) -> None:
        """Scheduler callback with SLO pressure (DESIGN.md §17): forward
        to the planner so its TS ladder fires early under burn."""
        if self.planner is not None:
            self.planner.note_slo_pressure(pressure)

    def note_load_timing(self, stage: int, nbytes: float,
                         seconds: float) -> None:
        """Wall-clock weight-load observation from the engine's streaming
        path (DESIGN.md §18): feed the online re-fit and let it rebuild
        the planner's ladders if the measured bandwidth has drifted."""
        if self.refit is None:
            return
        now = time.monotonic()
        self.refit.observe_fetch(stage, nbytes, seconds, now=now)
        self.refit.maybe_refit(now)

    def reclaim_kv_pages(self, n_pages: int) -> int:
        """Scheduler pressure hook: before preempting a request, demote
        resident layers and return their HBM as device KV pages. Returns
        pages made available (0 = no retier headroom left)."""
        if self.engine is None or self._pool is None:
            return 0
        pb = self._page_bytes()
        if pb <= 0:
            return 0
        if self._reclaim_dry:
            return 0          # a slot frees < 1 page on this engine: the
        eng = self.engine     # geometry is constant, retrying just churns
        got = 0
        while got < n_pages:
            stage = max(range(eng.plan.n_stage), key=eng.demote_capacity)
            if eng.demote_capacity(stage) <= 0:
                break
            snap = dict(self._adapt)
            pages = int(self._apply_retier(stage, +1) // pb)
            if pages <= 0:
                # one slot frees less than a page: undo the demotion (a
                # grant of nothing would permanently slow the stage) and
                # its telemetry — no HBM was returned
                self._apply_retier(stage, -1)
                self._adapt = snap
                self._reclaim_dry = True
                break
            self._pool.grow(pages)
            self._grants.append((stage, pages))
            got += pages
        return got

    def _planner_demote_target(self, stage: int) -> int:
        """Slots the TS ladder currently demands demoted on `stage`."""
        if self.planner is None or stage >= len(self.planner.states):
            return 0
        st = self.planner.states[stage]
        return max(st.alpha, st.beta)

    def _maybe_promote(self) -> None:
        """Undo reclaim-driven demotions when pressure drops: withdraw the
        granted pages (only free capacity can leave the pool) and promote
        the layers back to residency. Planner-driven demotions stay — the
        TS ladder is monotone in KV growth (paper §IV-D) — so promotion
        stops at the ladder's current demote target even when a reclaim
        grant is still outstanding on that stage (retier() promotes the
        most recent demotion, which may be the planner's)."""
        while self._grants and self._pool is not None:
            stage, pages = self._grants[-1]
            if self.engine.demoted(stage) - 1 \
                    < self._planner_demote_target(stage):
                break                    # would undo a ladder demotion
            if self._pool.free_pages() < pages + 2 * self.n_slots:
                break                    # still too close to the watermark
            self._pool.shrink(pages)
            self._apply_retier(stage, -1)
            self._grants.pop()

    @property
    def adapt_stats(self):
        stats = dict(self._adapt)
        if self.engine is not None:
            stats["layers_streamed_now"] = sum(
                self.engine.demoted(d)
                for d in range(self.engine.plan.n_stage))
        return stats

    # -- radix prefix cache over real KV pages (DESIGN.md §12) -------------------
    def _engine_can_chunk(self) -> bool:
        from repro.configs.base import Family
        return self.cfg.family in (Family.DENSE, Family.MOE) \
            and self.chunk < self.engine.S_c

    def _prefix_structures(self):
        """Persistent pool + paged cache + radix tree (lazily built: they
        outlive epochs — that is the whole point of the cache)."""
        if self._radix is None:
            from repro.kvcache.paged_decode import PagedDecodeCache
            from repro.kvcache.pool import PagePool, PagedKVConfig
            from repro.prefixcache import RadixPrefixCache
            B = self.batch_width
            max_pages = -(-self.max_len // self.page_size)
            extra = self._cache_pages or B * max_pages
            pool = PagePool(PagedKVConfig(
                page_size=self.page_size,
                device_pages=B * max_pages + extra))
            self._paged_cache = PagedDecodeCache(
                self.cfg, B, self.max_len, page_size=self.page_size,
                pool=pool)
            self._radix = RadixPrefixCache(pool)
        return self._paged_cache, self._radix

    def _ensure_room(self, pc, n_new_tokens: int) -> None:
        """Free device pages for the coming growth: unpinned radix pages
        are evicted first — cached prefixes are reclaimable, live tables
        are not (the pool is sized so this always suffices)."""
        need = sum(pc.pool.pages_for(pc.pos + n_new_tokens) - len(t.pages)
                   for t in pc.tables)
        short = need - pc.pool.free_pages()
        if short > 0:
            self._radix.evict(short)

    def _start_batch_prefix(self, reqs, prompts, toks):
        """Seed the epoch from shared pages where the radix tree has them,
        then prefill only the uncached suffix (chunked when configured).
        The shared-pos cache layout forces one matched length for the
        whole batch, so hits need equal-length prompts (shared_prefix
        traffic's common case) and align on the batch-minimum match;
        unequal-length epochs run cold through the dense prefill (their
        left-padded prefixes would key pad tokens — never donated)."""
        from repro.kvcache.allocator import BlockTable
        from repro.models import model as M

        pc, radix = self._prefix_structures()
        B = self.batch_width
        pc.reset_tables()                 # radix increfs keep shared pages
        self._slot_tokens = [None] * B
        self._slot_out = [[] for _ in range(B)]
        ps = self.page_size
        lens = {len(p) for p in prompts}
        if len(lens) != 1:
            cache = M.init_cache(self.cfg, B, self.max_len)
            logits, cache = self._prefill(self.params, toks, cache)
            self._ensure_room(pc, int(cache["pos"]))
            pc.seed(cache)
            self._state = None
            return logits[:, -1]
        L = lens.pop()
        matches = [radix.match(p, max_pages=(L - 1) // ps)
                   for p in prompts]
        m = min(n for _, n in matches)    # shared pos: batch-min match
        self._saved_tokens += m * len(reqs)
        for r in reqs:                    # visibility in serving reports
            r.cached_tokens = max(getattr(r, "cached_tokens", 0), m)
        while len(matches) < B:           # padded replicas ride the last
            matches.append(matches[-1])   # request's match
        if m > 0:
            tables = []
            for pages, _ in matches:
                t = BlockTable(ps)
                for pid in pages[:m // ps]:
                    pc.pool.incref_page(pid)
                t.pages = list(pages[:m // ps])
                t.tokens = m
                tables.append(t)
            pc.adopt_tables(tables, m)
        self._ensure_room(pc, L - pc.pos)
        last = pc.prefill(self.params, np.asarray(toks)[:, pc.pos:],
                          chunk=self.chunk)
        for slot, p in enumerate(prompts):
            self._slot_tokens[slot] = [int(x) for x in p]
        self._state = None
        return last

    @property
    def prefix_stats(self):
        if self._radix is None:
            return None
        r = self._radix
        return {"prefix_lookups": r.lookups, "prefix_hits": r.hits,
                "cached_tokens": r.cached_tokens(),
                "prefix_pages": r.n_pages,
                "prefill_tokens_saved": self._saved_tokens}

    # -- serving hooks -----------------------------------------------------------
    def _note_hbm(self) -> None:
        """`hbm.bytes_in_use` counter (tracing on only): bytes in use and
        the peak so far on the fullest device, where the backend reports
        them."""
        tr = get_tracer()
        if tr is None:
            return
        import jax
        devs = self.engine.mesh.devices.flat if self.engine is not None \
            else jax.local_devices()[:1]
        stats = [d.memory_stats() for d in devs]
        if not all(stats):
            return
        tr.counter(tr_ev.HBM_BYTES_IN_USE, track=tr_ev.TRACK_PIPELINE,
                   bytes_in_use=max(m.get("bytes_in_use", 0) for m in stats),
                   peak_bytes=max(m.get("peak_bytes_in_use", 0)
                                  for m in stats))

    def start_batch(self, reqs: Sequence) -> List[Optional[int]]:
        tr = get_tracer()
        if self._ahead is not None:           # a step nobody will read
            self._ahead = None
            self._ahead_n["discarded"] += 1
            if tr is not None:
                self._note_ahead(tr)
        if tr is None:
            return self._start_batch(reqs, None)
        args = {"batch": len(reqs)}
        with tr.span(tr_ev.ENGINE_PREFILL, track=tr_ev.TRACK_PIPELINE,
                     args=args):
            self._note_hbm()
            return self._start_batch(reqs, args)

    def _start_batch(self, reqs, trace_args) -> List[Optional[int]]:
        import jax.numpy as jnp

        from repro.models import model as M

        pipe = tr_ev.TRACK_PIPELINE
        prompts = [self._materialize_prompt(r) for r in reqs]
        toks = self._pad_prompts(prompts)
        if trace_args is not None:
            trace_args["span"] = int(toks.shape[1])
        if toks.shape[0] < self.batch_width:  # pad batch with replicas
            toks = jnp.concatenate(
                [toks, jnp.tile(toks[-1:], (self.batch_width - toks.shape[0],
                                            1))], 0)
        if self.engine is not None:
            # the last epoch's engine state holds a split copy of every
            # weight: drop it before init_state builds the next one
            self._state = None
        if self.prefix_cache:
            last = self._start_batch_prefix(reqs, prompts, toks)
        elif self.engine is not None and self.chunk \
                and self._engine_can_chunk():
            # partial-context prefill rounds through the interleaved
            # pipeline itself (DESIGN.md §12) — no separate prefill
            # program on replicated params
            state = self.engine.init_state(self.params)
            lg, self._state = self.engine.prefill_partial(
                state, toks, chunk=self.chunk)
            last = lg[:, -1]
        else:
            with tr_ev.span(tr_ev.BACKEND_PREFILL, track=pipe):
                cache = M.init_cache(self.cfg, toks.shape[0], self.max_len)
                logits, cache = self._prefill(self.params, toks, cache)
                last = logits[:, -1]
            self._note_hbm()
            if self.engine is not None:
                state = self.engine.init_state(self.params)
                self._note_hbm()
                self._state = self.engine.seed_cache(state, cache)
                self._note_hbm()
            elif self.paged:
                from repro.kvcache.paged_decode import PagedDecodeCache
                if self._paged_cache is not None:
                    self._paged_cache.release()
                self._paged_cache = PagedDecodeCache(
                    self.cfg, toks.shape[0], self.max_len,
                    page_size=self.page_size)
                self._paged_cache.seed(cache)
                self._state = None
            else:
                self._state = cache
        with tr_ev.span(tr_ev.BACKEND_SAMPLE, track=pipe):
            tok = self._sample(last)
        self._note_hbm()
        if self.prefix_cache:
            for slot in range(len(reqs)):
                self._slot_out[slot].append(int(tok[slot]))
        self._cur = tok[:, None]
        if self.spec is not None:
            from repro.specdec import SpecDecodeController
            if self._ctl is None:
                self._ctl = SpecDecodeController(
                    self.spec, self.sampler, self.cfg, self.batch_width,
                    target_params=self.params,
                    external_drafts=self._resident_engine)
            self._pos = int(toks.shape[1])    # left-padded prompt span
            for slot, p in enumerate(prompts):
                # drafts see the real (unpadded) prompt + first token
                self._ctl.begin(slot, list(int(t) for t in p)
                                + [int(tok[slot])])
        with tr_ev.span(tr_ev.BACKEND_SYNC, track=pipe):
            first = [int(tok[slot]) for slot in range(len(reqs))]
        self._note_hbm()
        return first

    def decode_active(self, slots: Sequence[int], more: Sequence[int] = ()):
        """One decode step for `slots`: {slot: token}. `more`, the slots
        the scheduler says still want a token after this one, get their
        next step dispatched before this step's ids are read, so the
        host's read and its work up to the next call run under the
        device's next step (DESIGN.md §9)."""
        # speculative round when a draft fits before the cache/ring end
        # (the last position is reserved for the committed-token write)
        if self.spec is not None:
            if self._depth is not None:
                self._sync_depth_rung()
            k_cap = self.spec.k if self._depth is None else self._depth.k()
            k = min(k_cap, self._spec_cap - self._pos - 1)
            if slots and k >= 1:
                return self._decode_active_spec(slots, k)
        tr = get_tracer()
        if tr is None:
            return self._decode_once(slots, more)
        with tr.span(tr_ev.ENGINE_DECODE, track=tr_ev.TRACK_PIPELINE,
                     args={"slots": len(slots)}):
            out = self._decode_once(slots, more)
            self._note_ahead(tr)
            return out

    def _note_ahead(self, tr) -> None:
        tr.counter(tr_ev.BACKEND_AHEAD, track=tr_ev.TRACK_PIPELINE,
                   **self._ahead_n)

    def _decode_once(self, slots: Sequence[int], more: Sequence[int]):
        if self._ahead is None:
            tok = self._dispatch(slots)
        else:
            tok, live = self._ahead
            self._ahead = None
            if not live.issuperset(slots):
                raise RuntimeError(
                    f"decode step for slots {sorted(slots)}, but the step "
                    f"in flight decodes {sorted(live)}")
        # speculative decoding proposes from the host's copy of the ids,
        # so with it the next step waits for this one's read
        if more and self.spec is None:
            self._ahead = (self._dispatch(more), frozenset(more))
            self._ahead_n["ahead"] += 1
        with tr_ev.span(tr_ev.BACKEND_SYNC, track=tr_ev.TRACK_PIPELINE):
            ids = np.asarray(tok)
        if self.prefix_cache:
            for s in slots:
                self._slot_out[s].append(int(ids[s]))
        if self.spec is not None:             # keep drafts in sync on the
            for s in slots:                   # non-spec fallback step
                self._ctl.observe(s, [int(ids[s])])
        return {s: int(ids[s]) for s in slots}

    def _dispatch(self, slots: Sequence[int]):
        """Dispatch one decode step for `slots` and its sampling. Returns
        the (batch_width,) sampled ids, on the device with their copy to
        the host started; the next step's input is built from them there."""
        import jax.numpy as jnp
        active = np.zeros(self.batch_width, bool)
        active[list(slots)] = True
        active = jnp.asarray(active)
        if self.engine is not None:
            lg, self._state = self.engine.decode_requests(
                self._state, self._cur, active)
        elif self.paged:
            if self.prefix_cache:
                self._ensure_room(self._paged_cache, 1)
            lg = self._paged_cache.step(self.params, self._cur)[:, 0]
        else:
            lg, self._state = self._decode(self.params, self._state,
                                           self._cur)
            if lg.ndim == 3:
                lg = lg[:, 0]
        with tr_ev.span(tr_ev.BACKEND_SAMPLE, track=tr_ev.TRACK_PIPELINE):
            tok = self._sample(lg)
        # one copy of the whole array, started now: read after the next
        # step is dispatched, an eager per-slot index would queue behind it
        tok.copy_to_host_async()
        if self.spec is not None:
            self._pos += 1
        # freed slots keep replaying their last token as pipeline padding
        self._cur = jnp.where(active[:, None], tok[:, None], self._cur)
        self._ahead_n["steps"] += 1
        return tok

    def _drain(self) -> None:
        """Read the ids of a step in flight (the next decode_active still
        returns them), so what changes the state between steps acts after
        every step the host has dispatched is accounted for."""
        if self._ahead is not None:
            tok, live = self._ahead
            self._ahead = (np.asarray(tok), live)

    def _decode_active_spec(self, slots: Sequence[int], k: int):
        """One speculative round: propose k per live slot, verify all of
        them in ONE multi-token pass (one engine pipeline round — one
        weight-stream), commit the lockstep-min accepted prefix, roll the
        rejected suffix back (pos reset / table truncation)."""
        import jax.numpy as jnp
        tr = get_tracer()
        t0 = tr.now() if tr is not None else 0.0
        cur = np.array(self._cur, np.int32)             # (B, 1) host copy
        mat = np.tile(cur, (1, 1 + k))                  # padding: replicas
        active = np.zeros(self.batch_width, bool)
        active[list(slots)] = True
        proposals = {}
        if self._resident_engine:
            # self-draft on the pipeline: k resident-only steps (zero
            # weight streaming) batched across ALL live slots, then the
            # drafted positions roll back before the full verify pass
            draft = self._draft_resident(active, k)
            for s in slots:
                proposals[s] = (draft[s], None)         # greedy point-mass
                mat[s, 1:] = draft[s]
        else:
            for s in slots:
                toks, qp = self._ctl.propose(s, k)
                proposals[s] = (toks, qp)
                mat[s, 1:] = toks
        if self.engine is not None:
            lg, self._state = self.engine.verify_requests(
                self._state, jnp.asarray(mat), jnp.asarray(active))
        elif self.paged:
            if self.prefix_cache:
                self._ensure_room(self._paged_cache, 1 + k)
            lg = self._paged_cache.verify(self.params, mat)
        else:
            lg, self._state = self._verify(self.params, self._state,
                                           jnp.asarray(mat))
        lg = np.asarray(lg, np.float32)                 # (B, k+1, PV)
        committed = {s: self._ctl.verify(lg[s], *proposals[s])
                     for s in slots}
        # shared-pos lockstep: every live slot advances by the same count;
        # tokens past the min re-verify next round (greedy re-derives them
        # exactly; stochastic redraws from the same target conditional)
        c = min(len(v) for v in committed.values())
        for s in slots:
            # accepted AND committed drafts only (out = accepted drafts +
            # one correction/bonus; truncated tokens re-draft next round)
            self._ctl.note_round(k, min(c, len(committed[s]) - 1))
        if self._depth is not None:
            self._depth.note_round(
                k * len(slots),
                sum(min(c, len(committed[s]) - 1) for s in slots))
        committed = {s: v[:c] for s, v in committed.items()}
        new_pos = self._pos + c
        if self.engine is not None:
            self._state = self.engine.rollback(self._state, new_pos)
            self.engine.note_committed(new_pos, active)
        elif self.paged:
            self._paged_cache.commit(c)
        else:
            self._state = dict(self._state)
            self._state["pos"] = jnp.asarray(new_pos, jnp.int32)
        self._pos = new_pos
        for s in slots:
            self._ctl.observe(s, committed[s])
            cur[s, 0] = committed[s][-1]
            if self.prefix_cache:
                # spec commit boundary (DESIGN.md §12): several tokens
                # landed at once — donate freshly-completed pages so
                # concurrent same-prefix traffic hits mid-flight
                self._slot_out[s].extend(int(t) for t in committed[s])
                self._donate_slot(s)
        self._cur = jnp.asarray(cur)
        if tr is not None:
            tr.complete(tr_ev.ENGINE_VERIFY, ts=t0, dur=tr.now() - t0,
                        track=tr_ev.TRACK_PIPELINE,
                        args={"k": k, "committed": c,
                              "slots": len(slots)})
        return committed

    def _draft_resident(self, active: np.ndarray, k: int) -> np.ndarray:
        """Propose k greedy tokens per slot via the engine's resident-only
        step (DESIGN.md §14): the draft rides the live tier boundary and
        the real slot caches, then rolls back to self._pos so the verify
        pass overwrites every drafted position. Returns (B, k) int32."""
        import jax.numpy as jnp
        tr = get_tracer()
        t0 = tr.now() if tr is not None else 0.0
        eng = self.engine
        act = jnp.asarray(active)
        st = self._state
        cur = jnp.asarray(np.array(self._cur, np.int32))
        out = np.empty((self.batch_width, k), np.int32)
        for i in range(k):
            lg, st = eng.draft_requests(st, cur, act)
            cur = jnp.argmax(lg[:, :self.cfg.vocab_size],
                             -1)[:, None].astype(jnp.int32)
            out[:, i] = np.asarray(cur)[:, 0]
        self._state = eng.rollback(st, self._pos)
        if tr is not None:
            tr.complete(tr_ev.ENGINE_DRAFT, ts=t0, dur=tr.now() - t0,
                        track=tr_ev.TRACK_PIPELINE, args={"k": k})
        return out

    @property
    def spec_stats(self):
        return self._ctl.stats.to_dict() if self._ctl is not None else None

    def join(self, slot: int, req) -> Optional[int]:
        raise NotImplementedError(
            "engine batches are fixed at cache-seed time")

    def _donate_slot(self, slot: int) -> None:
        """Insert `slot`'s committed pages (prompt + sampled output so
        far) into the radix tree. Slots whose prompt rode left-padding
        have _slot_tokens None — their early positions hold pad KV, so
        they never donate."""
        if self._radix is None or self._slot_tokens is None \
                or self._slot_tokens[slot] is None:
            return
        toks = self._slot_tokens[slot] + self._slot_out[slot]
        table = self._paged_cache.tables[slot]
        self._radix.insert(toks, table.pages,
                           n_tokens=min(len(toks), table.tokens))

    def release(self, slot: int) -> None:
        # the slot keeps padding the fixed batch until the epoch drains
        # (see decode_active); with a paged engine its pages are freed now
        if self.prefix_cache:
            # insert on finish: the request's committed pages become
            # future prefix hits (the table itself lives until the next
            # epoch's reset_tables — the tree's increfs carry them on)
            self._donate_slot(slot)
        if self.engine is not None and getattr(self.engine, "paged", False):
            self.engine.free_slot(slot)

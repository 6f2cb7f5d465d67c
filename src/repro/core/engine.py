"""TPU-native LIME: the interleaved pipeline as a JAX shard_map program.

This is implementation (B) of DESIGN.md §2 — the paper's mechanism mapped to
a TPU pod slice:

  Jetson device        -> pipeline stage (one slice of the mesh's stage axis)
  SSD weight offload   -> offloaded layers *sharded across all stages* on
                          their largest divisible weight dim (the pod's
                          aggregate HBM is "the SSD"); restored by an
                          all_to_all — per slot (fetch_mode="slot",
                          paper-literal per-segment streaming) or once per
                          decode step in a two-axis-manual region
                          (fetch_mode="step", optimized; EXPERIMENTS §Perf H1)
  SSD read bandwidth   -> ICI all-to-all bandwidth
  Ethernet activation  -> lax.ppermute ring between stages
  interleaved prefetch -> the restore for the *next* unit of work is issued
                          before the current one's compute consumes its
                          weights, so XLA's async collectives overlap it with
                          compute — the paper's overlap claim, structural.

Layer placement (one ExecutionPlan everywhere — DESIGN.md §13): the L
layers are cut into C = n_seg·n_stage contiguous chunks; chunk c runs on
stage c mod n_stage during segment c // n_stage and holds that stage's
k_d = k_res_d + k_off_d layers (per-stage splits may differ — the offline
scheduler's heterogeneous allocation executes directly; a uniform plan is
the degenerate case). Within a chunk the first k_res_d layers are
resident, the last k_off_d stream in per segment — "positions consistent
across segments" (paper §IV-A). Chunks are padded to the caps and dead
slots masked in the scan, so ONE compiled step serves every stage; the
resident/streamed boundary is a dynamic input, which is what lets
retier() move layers between tiers at runtime without recompiling.

Decode schedule: micro-batch m computes chunk c at slot τ = m + c
(sporadic: n_mb = 1; bursty: n_mb = n_stage). The slot loop is a lax.scan,
so HLO size is O(1) in pipeline depth; fill/drain bubbles are masked
commits, not control flow.

Losslessness is the contract: engine output ≡ single-device decode_step
(test_engine.py asserts equality within bf16 tolerance).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import Family, ModelConfig
from repro.core.cost_model import ExecutionPlan, StageAlloc  # noqa: F401
from repro.kvcache import BlockTable, PagePool, PagedKVConfig
from repro.models import model as M
from repro.models import spec as pspec
from repro.obs import trace as tr_ev
from repro.obs.trace import get_tracer


# named scopes of the step program's parts (`lime.<part>`): metadata only,
# so the compiled code is the same with them or without
SCOPE_PREFIX = "lime."
_SCOPE_RE = re.compile(r"(?:^|/)" + re.escape(SCOPE_PREFIX) + r"(\w+)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$", re.M)
_OP_NAME_RE = re.compile(r"op_name=\"([^\"]*)\"")


def _scope(part: str):
    return jax.named_scope(SCOPE_PREFIX + part)


def hlo_scopes(hlo_text: str, whole: Optional[str] = None):
    """(module name, {HLO instruction name: innermost `lime.*` part}) of a
    compiled program's text, read from each instruction's op_name
    metadata; instructions outside every part are left out. With
    `whole`, the program is that one part, and every instruction maps to
    it (the copies XLA inserts carry no metadata)."""
    head = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    ops = {}
    for name, rest in _INSTR_RE.findall(hlo_text):
        if whole is not None:
            ops[name] = SCOPE_PREFIX + whole
            continue
        op_name = _OP_NAME_RE.search(rest)
        parts = _SCOPE_RE.findall(op_name.group(1)) if op_name else []
        if parts:
            ops[name] = SCOPE_PREFIX + parts[-1]
    return (head.group(1) if head else ""), ops


# cache entries stacked on the layer dim (everything else — pos, pos_ids —
# is global; classifying by KEY, not shape, avoids the S_c == n_layers trap)
PER_LAYER_CACHE_KEYS = frozenset({"k", "v", "rwkv_state", "last_tm",
                                  "last_cm", "conv_state", "ssm_state",
                                  "xk", "xv"})


# ============================================================================
# ExecutionPlan (core/cost_model.py) is THE plan object; UniformPlan is the
# degenerate homogeneous-stage constructor kept for the historical API.
# ============================================================================
def UniformPlan(n_stage: int, n_seg: int, k_res: int,
                k_off: int) -> ExecutionPlan:
    """Homogeneous-stage plan (every stage k_res resident + k_off streamed
    per chunk). Delegates to ExecutionPlan.uniform — the engine, simulator
    and offline scheduler all consume the same object."""
    return ExecutionPlan.uniform(n_stage, n_seg, k_res, k_off)


def plan_for(cfg: ModelConfig, n_stage: int, *, hbm_frac_for_weights: float,
             hbm_bytes: float = 16e9) -> ExecutionPlan:
    """Pick (n_seg, k_res, k_off) so resident weights fit the per-stage HBM
    budget. Layers that don't divide evenly fall through to the 2-segment
    fallback, whose chunk is padded (padded slots are zero/identity
    layers); k_res + k_off == ceil(L / n_chunks) by construction, so the
    plan always covers cfg.n_layers AND keeps resident bytes (n_seg ·
    k_res · l_bytes per stage) inside the budget (regression:
    test_plan_for_covers_and_fits_budget)."""
    budget = hbm_bytes * hbm_frac_for_weights
    l_bytes = cfg.layer_params() * 2
    total_per_stage = cfg.n_layers / n_stage * l_bytes
    if total_per_stage <= budget:
        # everything resident: degenerate single-segment pipeline
        k = math.ceil(cfg.n_layers / n_stage)
        return UniformPlan(n_stage, 1, k, 0)
    res_layers = int(budget // l_bytes) * n_stage
    off_layers = cfg.n_layers - res_layers
    for n_seg in range(2, max(3, cfg.n_layers // n_stage + 1)):
        c = n_seg * n_stage
        if cfg.n_layers % c:
            continue
        k = cfg.n_layers // c
        k_off = max(math.ceil(off_layers / c), 1)
        if k_off < k:
            return UniformPlan(n_stage, n_seg, k - k_off, k_off)
    # fallback: 2 segments; resident share sized by the BUDGET (the old
    # fallback derived k_res from floor-divided off_layers, which
    # under-counts the streamed remainder when layer counts don't factor
    # cleanly and could claim far more resident bytes than the stage holds)
    c = 2 * n_stage
    k = math.ceil(cfg.n_layers / c)
    k_res = max(min(int(budget // l_bytes) // 2, k - 1), 0)
    return UniformPlan(n_stage, 2, k_res, k - k_res)


# ============================================================================
# Param / cache reshaping (host-side, once at engine build)
# ============================================================================
def _pad_layers(leaf, L_target: int):
    L = leaf.shape[0]
    if L == L_target:
        return leaf
    pad = [(0, L_target - L)] + [(0, 0)] * (leaf.ndim - 1)
    return jnp.pad(leaf, pad)


def stage_shard_dim(per_layer_shape, n_stage: int):
    """Which weight dim the offload store shards over the stage axis ("the
    SSD" distribution). Largest dim divisible by n_stage wins, so the
    all_to_all moves big contiguous slabs; None -> leaf too small / odd
    shaped, kept replicated across stages (its bytes are noise)."""
    best, best_sz = None, 0
    for i, d in enumerate(per_layer_shape):
        if d % n_stage == 0 and d > best_sz:
            best, best_sz = i, d
    return best


def plan_layout(plan: ExecutionPlan, headroom: int = 0, k_res_live=None):
    """Index maps from the flat (execution-order) layer stack into the
    padded per-stage grid.

    Returns (res_ids, off_ids): int32 arrays of shapes
    (n_seg, n_stage, k_res_cap) and (n_seg, n_stage, headroom + k_off_cap)
    whose entries are flat layer indices, or the sentinel `plan.n_layers`
    (one past the real stack — a guaranteed-zero identity row) for dead
    padding slots. Chunk c = s·n_stage + d holds the k_d = k_res_d +
    k_off_d layers at its cumulative offset: residents first, then the
    streamed tail — same execution order as the flat stack, whatever each
    stage's split.

    `k_res_live` (per-stage, <= build-time k_res) applies the retier
    layout: a demoted resident slot j moves its layer id into off-store
    headroom slot `headroom - (k_res_d - j)`, i.e. demotions fill the
    headroom right-to-left so the streamed tier preserves layer order
    (demoted residents run immediately before the originally-streamed
    tail)."""
    kr, ko = plan.k_res_list, plan.k_off_list
    n_seg, S = plan.n_seg, plan.n_stage
    kr_cap = max(kr) if kr else 0
    ko_cap = headroom + (max(ko) if ko else 0)
    live = list(kr) if k_res_live is None else [int(x) for x in k_res_live]
    assert all(0 <= lv <= k and k - lv <= headroom
               for lv, k in zip(live, kr)), (live, kr, headroom)
    dead = plan.n_layers
    res_ids = np.full((n_seg, S, max(kr_cap, 1)), dead, np.int32)
    off_ids = np.full((n_seg, S, max(ko_cap, 1)), dead, np.int32)
    flat = 0
    for c in range(n_seg * S):
        s, d = c // S, c % S
        for j in range(kr[d]):
            if j < live[d]:
                res_ids[s, d, j] = flat + j
            else:
                off_ids[s, d, headroom - (kr[d] - j)] = flat + j
        for j in range(ko[d]):
            off_ids[s, d, headroom + j] = flat + kr[d] + j
        flat += kr[d] + ko[d]
    return res_ids[:, :, :kr_cap], off_ids[:, :, :ko_cap]


def split_layer_stack(stacked, plan: ExecutionPlan, *, headroom: int = 0,
                      k_res_live=None):
    """(L, ...) pytree -> (resident, offloaded).

    resident:  (n_seg, n_stage, k_res_cap, *dims) — stage-sharded on dim 1.
    offloaded: (n_seg, n_stage, headroom + k_off_cap, *dims) — stage-sharded
               on weight dim `stage_shard_dim(dims) + 3` (or replicated when
               None), so streamed layers stay 'model'-sharded on their other
               dims under GSPMD the whole time — one chip never materializes
               a full MoE layer (kimi-k2: 34 GB/layer).

    Stages whose chunk is smaller than the cap get zero rows — identity
    layers through the residual stream, masked dead in the slot body. A
    uniform plan with headroom 0 reproduces the historical reshape split
    exactly.
    """
    res_ids, off_ids = plan_layout(plan, headroom, k_res_live)

    def do(leaf):
        leaf = _pad_layers(leaf, plan.n_layers + 1)   # +1: the identity row
        return leaf[res_ids], leaf[off_ids]
    pairs = jax.tree.map(do, stacked)
    res = jax.tree.map(lambda p: p[0], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    off = jax.tree.map(lambda p: p[1], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    return res, off


# ============================================================================
# The engine
# ============================================================================
class InterleavedEngine:
    """LIME decode engine over a mesh axis (default: 'data' doubles as the
    pipeline-stage axis; remaining mesh axes — 'model', 'pod' — stay under
    GSPMD auto-sharding, giving tensor parallelism inside each stage)."""

    def __init__(self, cfg: ModelConfig, mesh: Mesh, plan: ExecutionPlan, *,
                 stage_axis: str = "data", n_mb: int = 1, mb: int = 1,
                 max_len: int = 256, long_mode: bool = False,
                 prefetch: bool = True, impl: str = "ref",
                 enc_len: int = 0, fetch_mode: str = "step",
                 paged: bool = False, page_size: int = 64,
                 retier_headroom: int = 0):
        """fetch_mode:
        'slot' — paper-literal per-segment streaming: an all_to_all inside
                 every pipeline slot re-fetches the active chunk's layers.
                 Simple, but each stage re-pulls the same chunk n_stage
                 times per step, and the in-scan collective forces the
                 partitioner to un-shard auto ('model') dims of the slab
                 (§Perf baseline).
        'step' — one two-axis-manual all_to_all per decode step restores
                 every stage's streamed layers for all segments into a
                 double buffer the slot scan indexes; each streamed byte
                 moves once per step and stays 'model'-sharded end to end
                 (§Perf optimized; the beyond-paper variant)."""
        assert mesh.shape[stage_axis] == plan.n_stage, \
            (mesh.shape, plan.n_stage)
        assert fetch_mode in ("slot", "step")
        if impl == "pallas" and tuple(mesh.axis_names) != (stage_axis,):
            raise ValueError(
                f"impl='pallas' needs a stage-only mesh, not "
                f"{dict(mesh.shape)}: the step's shard_map leaves the "
                f"other axes auto, and Mosaic kernels cannot be "
                f"partitioned there")
        self.cfg, self.mesh, self.plan = cfg, mesh, plan
        self.axis = stage_axis
        self.n_mb, self.mb = n_mb, mb
        self.max_len = max_len
        self.long_mode = long_mode
        self.prefetch = prefetch
        self.impl = impl
        self.enc_len = enc_len          # ENCDEC: encoder runs outside
        # per-stage tier geometry (DESIGN.md §13): every stage's chunk is
        # padded to the caps so ONE compiled step serves heterogeneous
        # splits; dead slots are zero/identity layers masked in the scan.
        # retier_headroom adds per-stage streamed-store slots so resident
        # layers can demote into the streamed tier at runtime without
        # recompiling (the tier boundary `k_res_live` is a dynamic input).
        self.k_res_b = plan.k_res_list
        self.k_off_b = plan.k_off_list
        self.k_res_cap = max(self.k_res_b) if self.k_res_b else 0
        self.H = max(int(retier_headroom), 0)
        self.k_off_cap = self.H + (max(self.k_off_b) if self.k_off_b else 0)
        self.K = self.k_res_cap + self.k_off_cap
        self.k_res_live = list(self.k_res_b)      # host-side tier boundary
        self.fetch_mode = fetch_mode if self.k_off_cap else "slot"
        self.S_c = M.kv_cache_len(cfg, max_len, long_mode)
        # paged KV accounting (DESIGN.md §10): the statically-shaped
        # per-slot cache is carved into page_size-token pages owned by a
        # PagePool; slots hold block tables instead of implicit worst-case
        # reservations, so the serving layer sees page-granular occupancy
        # and seed_cache adoption moves real pages (see seed_cache).
        self.paged = paged and self.S_c > 0 and cfg.n_kv_heads > 0
        self.page_size = page_size
        if self.paged:
            self.pages_per_slot = -(-self.S_c // page_size)
            page_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                          * page_size * 2.0)            # k+v, bf16
            self.page_pool = PagePool(PagedKVConfig(
                page_size=page_size,
                device_pages=(n_mb * mb) * self.pages_per_slot,
                page_bytes=page_bytes))
            self.slot_tables = [BlockTable(page_size)
                                for _ in range(n_mb * mb)]
            self._paged_pos = 0        # host mirror of glob["pos"]
        self._refresh_tier_inputs()
        self._fetch = self._build_fetch() if self.fetch_mode == "step" \
            else None
        # compiled steps by query length: 1 = autoregressive decode,
        # q_len > 1 = speculative-decoding verification (DESIGN.md §11),
        # built lazily on first use
        self._steps: Dict[Any, Any] = {1: self._build_step(1)}
        self._step = self._steps[1]
        self._scoped = set()       # programs with an engine.scopes event

    # -- tier boundary (retier) inputs -----------------------------------------
    def _refresh_tier_inputs(self) -> None:
        """(Re)build the layout-dependent step inputs from the live tier
        boundary: the gather maps for state construction, the per-slot
        window table (a layer's window moves with it across tiers), and
        the dynamic `k_res_live` array the compiled step masks against."""
        self._res_ids, self._off_ids = plan_layout(self.plan, self.H,
                                                   self.k_res_live)
        self._cache_ids = np.concatenate([self._res_ids, self._off_ids],
                                         axis=2)        # (n_seg, n_stage, K)
        wins = M.layer_windows(self.cfg, self.plan.n_layers + 1,
                               self.long_mode)
        tab = jnp.asarray(wins)[jnp.asarray(self._cache_ids)]
        tab = jnp.transpose(tab, (1, 0, 2))             # (n_stage, n_seg, K)
        # real-layer mask: grid-overhang slots (ceil-rounded residents,
        # plan capacity past cfg.n_layers) hold zero rows like the dead
        # sentinel does — mask them structurally too, don't rely on
        # zero-weight layers being numerical no-ops
        real = np.transpose(self._cache_ids < self.cfg.n_layers, (1, 0, 2))
        sh = NamedSharding(self.mesh, P(self.axis))
        self._win_dev = jax.device_put(tab.astype(jnp.int32), sh)
        self._live_dev = jax.device_put(jnp.asarray(real), sh)
        self._kl_dev = jax.device_put(
            jnp.asarray(self.k_res_live, jnp.int32), sh)

    def _gather_layer_cache(self, v):
        """Model-layout (L, B, ...) cache leaf -> per-stage grid
        (n_seg, n_stage, K, n_mb, mb, ...), routing each layer's rows to
        its CURRENT slot (resident or streamed/demoted)."""
        x = _pad_layers(v, self.plan.n_layers + 1)
        x = x[self._cache_ids]            # (n_seg, n_stage, K, B, ...)
        shp = x.shape[4:]
        return x.reshape(self.plan.n_seg, self.plan.n_stage, self.K,
                         self.n_mb, self.mb, *shp)

    # -- state construction ----------------------------------------------------
    def init_state(self, params) -> Dict[str, Any]:
        """params: the model's usual pytree (layers stacked on L). Returns the
        engine state with resident/offloaded splits + per-stage caches.
        Respects the live tier boundary: layers demoted by earlier retier
        calls land in the streamed store."""
        assert "dense_layers" not in params, \
            "engine expects a homogeneous stack; fold dense layers via " \
            "configs with first_dense_layers=0 or pad (see tests)"
        with tr_ev.span(tr_ev.ENGINE_INIT_STATE, track=tr_ev.TRACK_PIPELINE):
            return self._init_state(params)

    def _init_state(self, params) -> Dict[str, Any]:
        cfg, plan = self.cfg, self.plan
        res, off = split_layer_stack(params["layers"], plan,
                                     headroom=self.H,
                                     k_res_live=self.k_res_live)
        cache = M.init_cache(cfg, self.n_mb * self.mb, self.max_len,
                             self.long_mode,
                             enc_out=(jnp.zeros((self.n_mb * self.mb,
                                                 self.enc_len, cfg.d_model),
                                                jnp.bfloat16)
                                      if self.enc_len else None))
        per_layer = {}
        glob = {"pos": cache["pos"]}
        for k, v in cache.items():
            if k == "pos":
                continue
            if k in PER_LAYER_CACHE_KEYS:
                per_layer[k] = self._gather_layer_cache(v)
            else:
                glob[k] = v                      # pos_ids etc. (global)
        others = {k: v for k, v in params.items() if k != "layers"}
        state = {
            "resident": res, "offload": off, "shared": others,
            "cache": per_layer, "glob": glob,
        }
        return jax.device_put(state, self.state_shardings())

    def _model_part(self, dim_size: int, logical_axis) -> Optional[str]:
        """'model' when the rules shard this logical axis there and the dim
        divides (auto-axis at-rest sharding — GSPMD keeps it)."""
        if logical_axis is None or "model" not in self.mesh.shape:
            return None
        from repro.sharding import rules as R
        axes = tuple(a for a in R.RULES.get(logical_axis, ())
                     if a == "model")
        if axes and dim_size % self.mesh.shape["model"] == 0:
            return "model"
        return None

    def _off_pspec(self, per_layer_shape, per_layer_axes=None) -> P:
        sdim = stage_shard_dim(per_layer_shape, self.plan.n_stage)
        parts: list = [None] * (3 + len(per_layer_shape))
        if per_layer_axes is not None:
            for i, (d, la) in enumerate(zip(per_layer_shape, per_layer_axes)):
                mp = self._model_part(d, la)
                if mp and i != sdim:
                    parts[3 + i] = mp
        if sdim is not None:
            parts[3 + sdim] = self.axis
        return P(*parts)

    def _res_pspec(self, per_layer_shape, per_layer_axes=None) -> P:
        parts: list = [None, self.axis] + [None] * (1 + len(per_layer_shape))
        if per_layer_axes is not None:
            for i, (d, la) in enumerate(zip(per_layer_shape, per_layer_axes)):
                mp = self._model_part(d, la)
                if mp:
                    parts[3 + i] = mp
        return P(*parts)

    def _shared_pspec(self, spec: pspec.ParamSpec) -> P:
        parts = [self._model_part(d, la)
                 for d, la in zip(spec.shape, spec.axes)]
        return P(*parts)

    def _cache_pspec(self, shape) -> P:
        """(n_seg, n_stage, k, n_mb, mb, d5, ...): stage on dim 1; the big
        per-layer dim (KV seq / heads / d_model) over 'model' when it
        divides; mb over 'pod' when present (bursty replicas per pod)."""
        parts: list = [None, self.axis] + [None] * (len(shape) - 2)
        if "pod" in self.mesh.shape and len(shape) > 4 \
                and shape[4] % self.mesh.shape["pod"] == 0 and shape[4] > 1:
            parts[4] = "pod"
        if "model" in self.mesh.shape and len(shape) > 5 \
                and shape[5] % self.mesh.shape["model"] == 0:
            parts[5] = "model"
        return P(*parts)

    def state_shardings(self):
        mesh, ax = self.mesh, self.axis
        specs = M.build_param_specs(self.cfg)

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        is_spec = pspec.is_spec
        res_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, self._res_pspec(s.shape[1:],
                                                          s.axes[1:])),
            specs["layers"], is_leaf=is_spec)
        off_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, self._off_pspec(s.shape[1:],
                                                          s.axes[1:])),
            specs["layers"], is_leaf=is_spec)
        cs = M.cache_specs(self.cfg, self.n_mb * self.mb, self.max_len,
                           self.long_mode, self.enc_len)
        cache_sh = {}
        for k in self._cache_keys():
            shape = (self.plan.n_seg, self.plan.n_stage, self.K,
                     self.n_mb, self.mb) + cs[k].shape[2:]
            cache_sh[k] = NamedSharding(mesh, self._cache_pspec(shape))
        shared_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, self._shared_pspec(s)),
            {k: v for k, v in specs.items() if k != "layers"},
            is_leaf=is_spec)
        return {"resident": res_sh, "offload": off_sh, "shared": shared_sh,
                "cache": cache_sh,
                "glob": {k: ns() for k in self._glob_keys()}}

    # prototypes for tree-mapping shardings without materialized params
    def _tree_proto(self):
        specs = M.build_param_specs(self.cfg)
        shapes = pspec.shapes(specs["layers"])
        return shapes, shapes

    def _shared_proto(self):
        specs = M.build_param_specs(self.cfg)
        return pspec.shapes({k: v for k, v in specs.items()
                             if k != "layers"})

    def _cache_keys(self):
        cs = M.cache_specs(self.cfg, 1, self.max_len, self.long_mode,
                           self.enc_len)
        return [k for k in cs if k in PER_LAYER_CACHE_KEYS]

    def _glob_keys(self):
        cs = M.cache_specs(self.cfg, 1, self.max_len, self.long_mode,
                           self.enc_len)
        return [k for k in cs if k not in PER_LAYER_CACHE_KEYS]

    # -- step-granular weight restore (fetch_mode="step") ------------------------
    def _fetched_pspec(self, per_layer_shape, per_layer_axes) -> P:
        """(n_stage, n_seg, k_off_cap, *dims): stage dim manual, model dims
        kept — except the stage-store dim, which arrives fully merged."""
        sdim = stage_shard_dim(per_layer_shape, self.plan.n_stage)
        parts: list = [self.axis, None, None] + [None] * len(per_layer_shape)
        for i, (d, la) in enumerate(zip(per_layer_shape, per_layer_axes)):
            mp = self._model_part(d, la)
            if mp and i != sdim:
                parts[3 + i] = mp
        return P(*parts)

    def _build_fetch(self):
        """shard_map with BOTH stage and model axes manual: the all_to_all
        then never forces the partitioner to materialize un-sharded slabs
        (the failure mode of in-scan fetches — EXPERIMENTS.md §Perf)."""
        plan = self.plan
        n_stage = plan.n_stage
        ax = self.axis
        mesh = self.mesh
        specs = M.build_param_specs(self.cfg)["layers"]
        # manual over EVERY mesh axis: the fetch touches only weights (pod
        # never shards them)
        manual = set(mesh.axis_names)

        def off_in_pspec(s):
            sdim = stage_shard_dim(s.shape[1:], n_stage)
            parts: list = [None] * (3 + len(s.shape[1:]))
            if sdim is not None:
                parts[3 + sdim] = ax
            for i, (d, la) in enumerate(zip(s.shape[1:], s.axes[1:])):
                mp = self._model_part(d, la)
                if mp and i != sdim:
                    parts[3 + i] = mp
            return P(*parts)

        in_specs = jax.tree.map(off_in_pspec, specs, is_leaf=pspec.is_spec)
        out_specs = jax.tree.map(
            lambda s: self._fetched_pspec(s.shape[1:], s.axes[1:]),
            specs, is_leaf=pspec.is_spec)
        sdims = jax.tree.map(
            lambda s: stage_shard_dim(s.shape[1:], n_stage), specs,
            is_leaf=pspec.is_spec)

        @_scope("restore")
        def fetch_fn(off):
            def one(leaf, sdim):
                # leaf local: (n_seg, n_stage, k_off, *local_dims)
                contrib = jnp.moveaxis(leaf, 1, 0)  # (n_stage, n_seg, ...)
                if sdim is None:
                    d = jax.lax.axis_index(ax)
                    own = jax.lax.dynamic_index_in_dim(contrib, d, 0, False)
                    return own[None]
                got = jax.lax.all_to_all(contrib, ax, split_axis=0,
                                         concat_axis=2 + sdim)
                shp = list(got.shape)
                merged = shp[:2 + sdim] + [shp[2 + sdim] * shp[3 + sdim]] \
                    + shp[4 + sdim:]
                return got.reshape(merged)[None]
            return jax.tree.map(one, off, sdims)

        return jax.jit(shard_map(fetch_fn, mesh=mesh, in_specs=(in_specs,),
                                 out_specs=out_specs, axis_names=manual,
                                 check_vma=False))

    # -- the SPMD step -----------------------------------------------------------
    def _build_step(self, q_len: int = 1, resident_only: bool = False):
        """q_len = 1: one autoregressive token (the historical step).
        q_len > 1: a speculative verification round — every micro-batch
        carries q_len query positions through the same slot schedule, so
        one pipeline traversal (one weight-stream) scores all of them;
        logits come back per position (DESIGN.md §11).
        resident_only (q_len must be 1): the self-draft step (DESIGN.md
        §14) — the same slot schedule with the streamed tier skipped
        entirely: no offload input, no weight fetch, the per-chunk layer
        scan runs only the k_res_cap resident rows (masked at the LIVE
        `kl` boundary, so retier needs no recompile), and the final norm
        + LM head act as the early-exit draft head. K/V writes land in
        resident rows only; the verify round overwrites every row at the
        drafted positions before reading them, so drafts leak nothing."""
        assert not (resident_only and q_len != 1), (q_len,)
        res_only = resident_only
        cfg, plan = self.cfg, self.plan
        n_stage, n_seg = plan.n_stage, plan.n_seg
        k_res_cap, k_off_cap, H, K = (self.k_res_cap, self.k_off_cap,
                                      self.H, self.K)
        KC = k_res_cap if res_only else K      # layer rows the scan runs
        # per-stage build-time tiers, baked as constants the traced stage
        # id selects from; the LIVE boundary arrives as the kl input
        KR_B = jnp.asarray(self.k_res_b, jnp.int32)
        KO_B = jnp.asarray(self.k_off_b, jnp.int32)
        C = plan.n_chunks
        n_mb, mb = self.n_mb, self.mb
        n_slots = C + n_mb - 1
        ax = self.axis
        impl = self.impl
        PV = M.round_up(cfg.vocab_size, 256)
        prefetch = self.prefetch

        layer_specs = M.build_param_specs(cfg)["layers"]
        layer_shapes = pspec.shapes(layer_specs)
        is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)
        # one layer's 'model' layout in the resident store: a streamed
        # layer is read in that layout too, so every row of a chunk
        # partitions (and rounds) the same whichever store holds it
        res_layouts = jax.tree.map(
            lambda s: P(*self._res_pspec(s.shape[1:], s.axes[1:])[3:]),
            layer_specs, is_leaf=pspec.is_spec)
        stage_dims = jax.tree.map(
            lambda s: stage_shard_dim(s.shape[1:], n_stage), layer_shapes,
            is_leaf=is_sds)

        @_scope("restore")
        def fetch_chunk_weights(off_local, tau, d):
            """all_to_all restore of each stage's streamed layers for the
            chunk it runs at slot `tau`. Stage-sharded leaves arrive via an
            untiled all_to_all on their stage dim; replicated leaves are a
            local gather. 'model'-sharded dims stay sharded throughout
            (GSPMD auto axes).
            """
            if k_off_cap == 0:
                return None
            e = jnp.arange(n_stage)
            m_e = (tau - e) % n_stage if n_mb > 1 else jnp.zeros_like(e)
            c_e = tau - m_e
            s_e = jnp.clip(c_e // n_stage, 0, n_seg - 1)
            s_d = jnp.clip((tau - ((tau - d) % n_stage if n_mb > 1 else 0))
                           // n_stage, 0, n_seg - 1)

            def one(leaf, sdim):
                if sdim is None:
                    # replicated store: local pick of (my segment, my stage)
                    seg = jax.lax.dynamic_index_in_dim(leaf, s_d, 0, False)
                    return jax.lax.dynamic_index_in_dim(seg, d, 0, False)
                contrib = leaf[s_e, e]        # (n_stage, k_off, *dims_local)
                # untiled all_to_all: axis0 consumed, new n_stage axis at
                # the stage-sharded dim; merge it back to full width.
                got = jax.lax.all_to_all(contrib, ax, split_axis=0,
                                         concat_axis=1 + sdim)
                # got: (k_off, ..., n_stage, dim/n_stage, ...) at 1+sdim
                shp = list(got.shape)
                merged = shp[:1 + sdim] \
                    + [shp[1 + sdim] * shp[2 + sdim]] + shp[3 + sdim:]
                return got.reshape(merged)
            return jax.tree.map(one, off_local, stage_dims)

        @_scope("ring_shift")
        def ring_shift(x):
            """Hand the activation to the next stage."""
            return jax.lax.ppermute(
                x, ax, [(i, (i + 1) % n_stage) for i in range(n_stage)])

        step_mode = self.fetch_mode == "step"

        def step_fn(resident, offload, shared, cache, glob, tokens,
                    kl, win_tab, real_tab):
            """One autoregressive token for all n_mb micro-batches.
            tokens: (n_mb, mb, 1) int32 (replicated). Locals per stage:
            resident (n_seg, 1, k_res_cap, ...); cache (n_seg, 1, K, n_mb,
            mb, ...); offload: fetch_mode='slot' -> the sharded store,
            'step' -> the per-stage restored buffer (1, n_seg, k_off_cap,
            ...).
            kl: (1,) int32 — the stage's LIVE resident count (the dynamic
            tier boundary; retier changes it without recompiling).
            win_tab: (1, n_seg, K) int32 — per-slot attention windows for
            the stage's CURRENT layout (a layer's window moves with it).
            real_tab: (1, n_seg, K) bool — slot holds a real model layer
            (False on dead padding AND grid overhang past cfg.n_layers)."""
            d = jax.lax.axis_index(ax)

            def layer_params(store, lead):
                """One layer of every leaf of `store`, at index `lead`, read
                in place by the layer scan, in the resident store's dtype
                and 'model' layout: nothing assembles the active chunk,
                since a sliced or concatenated stack that feeds a scan is
                materialized."""
                def one(w, r, layout):
                    rest = w.shape[len(lead):]
                    row = jax.lax.dynamic_slice(
                        w, tuple(lead) + (0,) * len(rest),
                        (1,) * len(lead) + rest).reshape(rest)
                    row = row.astype(r.dtype)
                    if all(p is None for p in layout):
                        return row
                    return jax.lax.with_sharding_constraint(
                        row, NamedSharding(self.mesh, layout))
                with _scope("chunk_params"):
                    return jax.tree.map(one, store, resident, res_layouts)
            # dead-slot mask (DESIGN.md §13): resident slots past the live
            # boundary, unfilled headroom, and cap padding are identity —
            # zero weights make them so numerically, the mask makes it
            # structural (and exact for every family)
            m_dem = KR_B[d] - kl[0]
            jidx = jnp.arange(KC)
            if res_only:
                # only resident rows below the LIVE boundary run: demoted
                # layers sit in the streamed store the draft never touches
                live_d = jidx < kl[0]
            else:
                live_d = (jidx < kl[0]) \
                    | ((jidx >= k_res_cap + H - m_dem)
                       & (jidx < k_res_cap + H + KO_B[d]))
            win_d = win_tab[0][:, :KC]          # (n_seg, KC)
            real_d = real_tab[0][:, :KC]        # (n_seg, KC) bool
            pos = glob["pos"]
            pos_ids = glob.get("pos_ids")
            slot = jnp.int32(0)
            q_slots = None
            if pos_ids is not None:
                S_c = pos_ids.shape[0]
                slot = pos % S_c
                if q_len == 1:
                    pos_ids = jax.lax.dynamic_update_slice(
                        pos_ids, pos[None].astype(pos_ids.dtype), (slot,))
                else:
                    qpos = pos + jnp.arange(q_len)
                    q_slots = qpos % S_c
                    # contiguous update: the verify window never wraps
                    # (backend caps pos + q_len)
                    pos_ids = jax.lax.dynamic_update_slice(
                        pos_ids, qpos.astype(pos_ids.dtype), (slot,))

            x0 = jnp.zeros((mb, q_len, cfg.d_model), jnp.bfloat16)
            logits0 = jnp.zeros((n_mb, mb, q_len, PV), jnp.float32)
            fetched0 = None if (step_mode or res_only) else \
                fetch_chunk_weights(offload, jnp.int32(0), d)

            def slot_body(carry, tau):
                x, logits_buf, cache_l, fetched = carry
                # my active (chunk, micro-batch) at this slot
                m_d = ((tau - d) % n_stage) if n_mb > 1 else jnp.int32(0)
                m_d = jnp.where(n_mb > 1, m_d, 0)
                c_d = tau - m_d
                valid = (c_d >= 0) & (c_d < C) & (m_d < n_mb) \
                    & (c_d % n_stage == d)
                s_d = jnp.clip(c_d // n_stage, 0, n_seg - 1)

                # interleave: issue next slot's weight fetch BEFORE compute
                if res_only:
                    # self-draft: zero weight streaming — the whole point
                    nxt = cur = None
                elif step_mode:
                    # the restored buffer (1, n_seg, k_off_cap, ...), read
                    # in place at [0, s_d, j] by the streamed scan
                    nxt, cur = None, offload
                else:
                    nxt = fetch_chunk_weights(offload, tau + 1, d) \
                        if prefetch else None
                    cur = fetched if prefetch else \
                        fetch_chunk_weights(offload, tau, d)

                # entering micro-batches embed their token at chunk 0
                m_c = jnp.clip(m_d, 0, n_mb - 1)
                tok_m = jnp.take(tokens, m_c, axis=0)
                x_in = jnp.where((c_d == 0)[..., None, None],
                                 M.embed(shared, tok_m).astype(jnp.bfloat16),
                                 x)

                with _scope("cache_read"):
                    cache_chunk = {kk: jax.lax.dynamic_index_in_dim(
                        v[:, 0], s_d, 0, keepdims=False) for kk, v in
                        cache_l.items()}      # (k, n_mb, mb, ...)
                    cache_mb = {kk: jax.lax.dynamic_index_in_dim(
                        v, m_c, 1, keepdims=False)
                        for kk, v in cache_chunk.items()}   # (k, mb, ...)

                moe_mesh = self.mesh if (cfg.family == Family.MOE
                                         and "model" in self.mesh.shape) \
                    else None
                inner = M._decode_body(cfg, moe_mesh, impl,
                                       cfg.family == Family.MOE, pos, slot,
                                       pos_ids, enc_len=self.enc_len,
                                       moe_mode="auto", q_slots=q_slots)

                window = jax.lax.dynamic_index_in_dim(win_d, s_d, 0, False)
                live = live_d & jax.lax.dynamic_index_in_dim(real_d, s_d, 0,
                                                             False)

                def tier_body(lo, store, lead):
                    """The scan body over rows lo + j of the chunk: the
                    layer at `lead(j)` of its tier's store, and the
                    chunk's row lo + j of the masks and the cache."""
                    def body(carry, j):
                        # dead slots are identity: activation (and MoE aux)
                        # pass through untouched; their cache writes land
                        # in rows nothing ever reads
                        x_prev, aux_prev = carry
                        row = lo + j
                        xs_l = {kk: jax.lax.dynamic_index_in_dim(
                            v, row, 0, False) for kk, v in cache_mb.items()}
                        xs_l["window"], xs_l["live"] = (
                            jax.lax.dynamic_index_in_dim(v, row, 0, False)
                            for v in (window, live))
                        xs_l["p"] = layer_params(store, lead(j))
                        (x_new, aux_new), ys_l = inner(carry, xs_l)
                        alive = xs_l["live"]
                        return (jnp.where(alive, x_new, x_prev),
                                jnp.where(alive, aux_new, aux_prev)), ys_l
                    return body

                # the chunk's rows run as two scans of one body, each
                # reading its layers in place from the store that holds
                # them: resident rows [0, k_res_cap) from the resident
                # store, then streamed rows (headroom + streamed tail)
                # [k_res_cap, K) from the restored buffer (step mode) or
                # the fetched chunk (slot mode)
                zero = jnp.int32(0)
                tiers = [(0, k_res_cap, resident, lambda j: (s_d, zero, j))]
                if cur is not None:
                    tiers.append((k_res_cap, k_off_cap, cur,
                                  (lambda j: (zero, s_d, j)) if step_mode
                                  else (lambda j: (j,))))
                carry = (x_in, jnp.float32(0.))
                ys = []                               # (first row, ys)
                with _scope("layers"):
                    for lo, n, store, lead in tiers:
                        if n:
                            carry, ys_t = jax.lax.scan(
                                tier_body(lo, store, lead), carry,
                                jnp.arange(n, dtype=jnp.int32))
                            ys.append((lo, ys_t))
                x_out = carry[0]

                # commit cache only when valid, each scan's rows in place
                # (a resident-only draft leaves the streamed rows untouched)
                def commit(old, kk):
                    cur_s = jax.lax.dynamic_index_in_dim(old[:, 0], s_d, 0,
                                                         False)
                    prev = jax.lax.dynamic_index_in_dim(cur_s, m_c, 1, False)
                    upd = prev
                    for lo, ys_t in ys:
                        new = ys_t[kk]
                        new = jnp.where(valid, new.astype(old.dtype),
                                        prev[lo:lo + new.shape[0]])
                        upd = jax.lax.dynamic_update_slice_in_dim(
                            upd, new, lo, axis=0)
                    cur_s = jax.lax.dynamic_update_index_in_dim(
                        cur_s, upd, m_c, 1)
                    return jax.lax.dynamic_update_index_in_dim(
                        old, cur_s[None], s_d, 0)
                cache_l = dict(cache_l)      # keep read-only keys (xk/xv)
                with _scope("cache_commit"):
                    cache_l.update({kk: commit(cache_l[kk], kk)
                                    for kk in ys[0][1]})

                # last chunk: unembed and stash logits
                with _scope("unembed"):
                    is_last = valid & (c_d == C - 1)
                    xn = M.rms_norm(x_out, shared["final_norm"],
                                    cfg.norm_eps)
                    lg = M.unembed(shared, xn).astype(jnp.float32)
                    logits_buf = jnp.where(
                        is_last,
                        jax.lax.dynamic_update_index_in_dim(
                            logits_buf, lg, jnp.clip(m_d, 0, n_mb - 1), 0),
                        logits_buf)

                # hand activation to the next stage (ring)
                x_next = ring_shift(x_out)
                dbg = (jnp.abs(x_out.astype(jnp.float32)).sum(),
                       c_d, valid.astype(jnp.int32))
                return (x_next, logits_buf, cache_l,
                        nxt if prefetch else fetched), dbg

            carry0 = (x0, logits0, cache, fetched0)
            (xf, logits_buf, cache_f, _), dbg = jax.lax.scan(
                slot_body, carry0, jnp.arange(n_slots, dtype=jnp.int32))

            logits = jax.lax.psum(logits_buf, ax) / 1.0  # only last stage wrote
            new_glob = dict(glob)
            new_glob["pos"] = pos + q_len
            if pos_ids is not None:
                new_glob["pos_ids"] = pos_ids
            dbg_out = jnp.stack([dbg[0],
                                 dbg[1].astype(jnp.float32),
                                 dbg[2].astype(jnp.float32)], -1)[None]
            return logits, cache_f, new_glob, dbg_out

        proto = self._tree_proto()[0]
        out_specs = (P(), {kk: P(None, ax) for kk in self._cache_keys()},
                     {kk: P() for kk in self._glob_keys()}, P(ax))
        if res_only:
            # no offload leg at all: the draft program never sees the
            # streamed store, so XLA cannot schedule a fetch for it
            def draft_fn(resident, shared, cache, glob, tokens, kl,
                         win_tab, real_tab):
                return step_fn(resident, None, shared, cache, glob, tokens,
                               kl, win_tab, real_tab)
            in_specs = (jax.tree.map(lambda _: P(None, ax), proto,
                                     is_leaf=is_sds),
                        jax.tree.map(lambda _: P(), self._shared_proto()),
                        {kk: P(None, ax) for kk in self._cache_keys()},
                        {kk: P() for kk in self._glob_keys()},
                        P(), P(ax), P(ax), P(ax))
            fn = shard_map(draft_fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, axis_names={ax},
                           check_vma=False)
            return jax.jit(fn, donate_argnums=(2,))
        if step_mode:
            off_in = jax.tree.map(lambda _: P(ax), proto, is_leaf=is_sds)
        else:
            off_in = jax.tree.map(lambda s: self._off_pspec(s.shape[1:]),
                                  proto, is_leaf=is_sds)
        in_specs = (jax.tree.map(lambda _: P(None, ax), proto,
                                 is_leaf=is_sds),
                    off_in,
                    jax.tree.map(lambda _: P(), self._shared_proto()),
                    {kk: P(None, ax) for kk in self._cache_keys()},
                    {kk: P() for kk in self._glob_keys()},
                    P(), P(ax), P(ax), P(ax))
        fn = shard_map(step_fn, mesh=self.mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names={ax},
                       check_vma=False)
        # donate the KV/state caches: the slot scan's functional update
        # would otherwise double-buffer them (kimi-k2: +4.2 GB/chip peak)
        return jax.jit(fn, donate_argnums=(3,))

    def _note_scopes(self, key, program, *args, whole=None) -> None:
        """With a tracer installed, one `engine.scopes` event per compiled
        program: its module name and each HLO instruction's `lime.*`
        part, from the compiled text (a compile-cache hit after the first
        call). `whole` names the part that is the whole program."""
        tr = get_tracer()
        if tr is None or key in self._scoped:
            return
        self._scoped.add(key)
        module, ops = hlo_scopes(program.lower(*args).compile().as_text(),
                                 whole)
        tr.instant(tr_ev.ENGINE_SCOPES, track=tr_ev.TRACK_ENGINE,
                   args={"module": module, "program": str(key), "ops": ops})

    # -- paged slot accounting (DESIGN.md §10) -----------------------------------
    def _paged_seed_slots(self, ctx: int) -> None:
        """(Re)build every slot's block table to hold `ctx` tokens."""
        for t in self.slot_tables:
            self.page_pool.release_table(t)
        for t in self.slot_tables:
            self.page_pool.extend_table(t, min(ctx, self.S_c))

    def _through_pages(self, x: np.ndarray, ctx: int) -> np.ndarray:
        """Round-trip a model-layout (L, B, S_c, ...) K or V stack through
        the page pool: scatter each slot's first `ctx` rows into its block
        table's pages, then gather them back. Page placement is whatever
        the free list handed out (LIFO — non-contiguous after any realloc),
        so adoption actually exercises the table indirection; the result is
        bit-identical by construction (pure data movement)."""
        from repro.kvcache.layout import gather_from_pages, scatter_to_pages
        x = np.asarray(x)
        ctx = min(ctx, self.S_c)
        pool_shape = (x.shape[0], self.page_pool.alloc.n_pages,
                      self.page_size) + x.shape[3:]
        pool_buf = scatter_to_pages(np.zeros(pool_shape, x.dtype), x,
                                    self.slot_tables, ctx)
        return gather_from_pages(x.copy(), pool_buf, self.slot_tables, ctx)

    def extend_slot(self, slot: int, n_tokens: Optional[int] = None) -> None:
        """Page-granular growth for one slot (serving calls this per
        decode step for live slots). Raises OutOfPages when the pool is
        dry — cannot happen while every slot's table is capped at
        pages_per_slot, which extend_to guarantees via S_c clamping."""
        t = self.slot_tables[slot]
        target = t.tokens + 1 if n_tokens is None else n_tokens
        self.page_pool.extend_table(t, min(target, self.S_c))

    def free_slot(self, slot: int) -> None:
        """Release a completed request's pages (serving release hook)."""
        self.page_pool.release_table(self.slot_tables[slot])

    def paged_stats(self) -> Dict[str, int]:
        return {"pages_in_use": self.page_pool.pages_in_use(),
                "page_size": self.page_size,
                "slot_tokens": [t.tokens for t in self.slot_tables]}

    def seed_cache(self, state, cache) -> Dict[str, Any]:
        """Adopt a model-layout cache (e.g. produced by M.prefill on
        replicated params) into the engine's per-stage layout.

        Paged mode: adoption is rewritten over block tables — each slot's
        K/V tokens are scattered into its table's pool pages and gathered
        back before the per-stage reshape, so the table indirection (not a
        contiguous memcpy) is what carries the bytes, and slot occupancy
        is page-granular from the first decode step."""
        with tr_ev.span(tr_ev.ENGINE_SEED_CACHE, track=tr_ev.TRACK_PIPELINE):
            return self._seed_cache(state, cache)

    def _seed_cache(self, state, cache) -> Dict[str, Any]:
        paged_ctx = int(cache["pos"]) if self.paged else 0
        if self.paged:
            self._paged_pos = paged_ctx
            self._paged_seed_slots(paged_ctx)
        new_cache = {}
        glob = dict(state["glob"])
        for kk, v in cache.items():
            if kk in PER_LAYER_CACHE_KEYS:
                if self.paged and kk in ("k", "v"):
                    v = jnp.asarray(self._through_pages(v, paged_ctx),
                                    v.dtype)
                new_cache[kk] = self._gather_layer_cache(v)
            else:
                glob[kk] = v
        out = dict(state)
        sh = self.state_shardings()
        out["cache"] = jax.device_put(new_cache, sh["cache"])
        out["glob"] = glob
        return out

    # -- public API ---------------------------------------------------------------
    def decode_step(self, state, tokens):
        """tokens: (n_mb * mb, 1) int32 -> (logits (n_mb*mb, PV), state)."""
        t = tokens.reshape(self.n_mb, self.mb, 1)
        off = state["offload"]
        pipe = tr_ev.TRACK_PIPELINE
        if self.fetch_mode == "step":
            self._note_scopes("fetch", self._fetch, off, whole="restore")
            with tr_ev.span(tr_ev.ENGINE_FETCH, track=pipe):
                off = self._fetch(off)
        args = (state["resident"], off, state["shared"], state["cache"],
                state["glob"], t, self._kl_dev, self._win_dev,
                self._live_dev)
        self._note_scopes(1, self._steps[1], *args)
        with tr_ev.span(tr_ev.ENGINE_STEP, track=pipe):
            logits, cache, glob, dbg = self._step(*args)
        new_state = dict(state)
        new_state["cache"] = cache
        new_state["glob"] = glob
        self.last_debug = dbg       # (n_stage, n_slots, [xnorm, chunk, valid])
        return logits.reshape(self.n_mb * self.mb, -1), new_state

    def decode_requests(self, state, tokens, active):
        """Serving entry point (DESIGN.md §9): one decode step for a batch
        of slot-resident requests where only some slots are live.

        tokens: (n_mb*mb, 1) int32; active: (n_mb*mb,) bool. Inactive slots
        ride the pipeline as padding — their tokens are zeroed so the step
        stays deterministic regardless of stale slot contents, their cache
        writes land in slots the scheduler has already released, and their
        logits must be ignored by the caller. This keeps one compiled step
        for every occupancy level (recompiling per occupancy would defeat
        continuous batching).
        """
        with tr_ev.span(tr_ev.ENGINE_DISPATCH, track=tr_ev.TRACK_PIPELINE):
            return self._decode_requests(state, tokens, active)

    def _decode_requests(self, state, tokens, active):
        if self.paged:
            # page-granular occupancy: live slots grow one token (a new
            # page every page_size steps); released slots hold nothing.
            # pos is tracked host-side (seeded in seed_cache, +1 per
            # step) — a device_get here would sync the async dispatch
            # pipeline every decode step.
            self._paged_pos += 1
            for slot, live in enumerate(np.asarray(active, bool)):
                if live:
                    self.extend_slot(slot, self._paged_pos)
        active = jnp.asarray(active, bool)
        toks = jnp.where(active[:, None], tokens.astype(jnp.int32), 0)
        return self.decode_step(state, toks)

    # -- speculative verification (DESIGN.md §11) --------------------------------
    def verify_step(self, state, tokens):
        """Score q_len query positions per slot in ONE pipeline round —
        one weight-stream validates q_len tokens. tokens: (n_mb*mb,
        q_len) int32, column 0 the last committed token, the rest
        drafted. Returns (logits (n_mb*mb, q_len, PV), state) with pos
        advanced by q_len and all q_len K/V written; the caller commits
        an accepted prefix via rollback() (stale entries carry pos_ids >
        pos and are masked out of every later read)."""
        if self.cfg.family not in (Family.DENSE, Family.MOE):
            raise NotImplementedError(
                f"speculative verification needs pure-KV per-layer state "
                f"(DENSE/MOE), not {self.cfg.family}")
        q_len = tokens.shape[1]
        assert 1 <= q_len < max(self.S_c, 2), (q_len, self.S_c)
        if q_len not in self._steps:
            self._steps[q_len] = self._build_step(q_len)
        t = tokens.reshape(self.n_mb, self.mb, q_len)
        off = state["offload"]
        if self.fetch_mode == "step":
            self._note_scopes("fetch", self._fetch, off, whole="restore")
            off = self._fetch(off)
        args = (state["resident"], off, state["shared"], state["cache"],
                state["glob"], t, self._kl_dev, self._win_dev,
                self._live_dev)
        self._note_scopes(q_len, self._steps[q_len], *args)
        logits, cache, glob, dbg = self._steps[q_len](*args)
        new_state = dict(state)
        new_state["cache"] = cache
        new_state["glob"] = glob
        self.last_debug = dbg
        return logits.reshape(self.n_mb * self.mb, q_len, -1), new_state

    def verify_requests(self, state, tokens, active):
        """Slot-masked verify_step (serving entry): inactive slots ride
        as padding with zeroed tokens, their logits must be ignored.
        Paged slot accounting is the caller's job (note_committed) —
        unlike decode_requests, the tokens actually kept are only known
        after acceptance."""
        tr = get_tracer()
        if tr is not None:
            tr.instant(tr_ev.ENGINE_VERIFY, track=tr_ev.TRACK_ENGINE,
                       args={"q_len": int(tokens.shape[1])})
        active = jnp.asarray(active, bool)
        toks = jnp.where(active[:, None], tokens.astype(jnp.int32), 0)
        return self.verify_step(state, toks)

    # -- resident-tier self-draft (DESIGN.md §14) --------------------------------
    def draft_step(self, state, tokens):
        """One decode step through ONLY the live resident tier: the same
        slot schedule as decode_step with zero weight streaming (no
        offload input at all), the final norm + LM head as the early-exit
        draft head. tokens: (n_mb*mb, 1) int32 -> (logits, state) with pos
        advanced by 1.

        Snapshot-and-advance contract: k draft steps write resident-row
        K/V at positions pos..pos+k-1, then rollback(state, pos) +
        verify_step overwrite every row (resident AND streamed) at those
        positions before attention reads them — drafting leaks nothing
        into the verified stream, and never touches paged accounting
        (note_committed after acceptance is what grows block tables)."""
        if self.cfg.family not in (Family.DENSE, Family.MOE):
            raise NotImplementedError(
                f"resident self-draft needs pure-KV per-layer state "
                f"(DENSE/MOE), not {self.cfg.family}")
        if self.k_res_cap == 0:
            raise ValueError(
                "resident self-draft needs a resident tier (plan has "
                "k_res == 0 on every stage)")
        if "draft" not in self._steps:
            self._steps["draft"] = self._build_step(1, resident_only=True)
        t = tokens.reshape(self.n_mb, self.mb, 1)
        args = (state["resident"], state["shared"], state["cache"],
                state["glob"], t, self._kl_dev, self._win_dev,
                self._live_dev)
        self._note_scopes("draft", self._steps["draft"], *args)
        logits, cache, glob, dbg = self._steps["draft"](*args)
        new_state = dict(state)
        new_state["cache"] = cache
        new_state["glob"] = glob
        self.last_debug = dbg
        return logits.reshape(self.n_mb * self.mb, -1), new_state

    def draft_requests(self, state, tokens, active):
        """Slot-masked draft_step (serving entry): inactive slots ride as
        padding with zeroed tokens. Deliberately NO paged extend — drafted
        positions own no pages until verification commits them."""
        tr = get_tracer()
        if tr is not None:
            tr.instant(tr_ev.ENGINE_DRAFT, track=tr_ev.TRACK_ENGINE)
        active = jnp.asarray(active, bool)
        toks = jnp.where(active[:, None], tokens.astype(jnp.int32), 0)
        return self.draft_step(state, toks)

    def prefill_partial(self, state, tokens, *, chunk: int = 0):
        """Partial-context prefill through the interleaved pipeline
        (DESIGN.md §12): run `tokens` ((n_mb*mb, T) prompt positions
        starting at the state's current pos — 0 for a cold prompt, the
        cached span for a prefix hit) as ceil(T/chunk) multi-query rounds
        of the verify step, each one pipeline traversal (one
        weight-stream) scoring `chunk` positions. No separate prefill
        program on replicated params is needed — the pipeline itself
        builds the cache. Returns (last round's logits (n_mb*mb, q, PV),
        state) with pos advanced by T; the final position's row seeds the
        first sampled token."""
        if self.cfg.family not in (Family.DENSE, Family.MOE):
            raise NotImplementedError(
                "partial-context prefill rides the multi-query verify "
                "step (pure-KV families only)")
        tokens = jnp.asarray(tokens, jnp.int32)
        T = int(tokens.shape[1])
        chunk = T if chunk <= 0 else min(chunk, T)
        assert chunk < max(self.S_c, 2), (chunk, self.S_c)
        tr = get_tracer()
        logits = None
        for off in range(0, T, chunk):
            if tr is not None:
                tr.instant(tr_ev.ENGINE_PREFILL, track=tr_ev.TRACK_ENGINE,
                           args={"offset": off,
                                 "chunk": min(chunk, T - off)})
            logits, state = self.verify_step(state,
                                             tokens[:, off:off + chunk])
        if self.paged:
            # slot tables rebuilt at the prefilled span (the serving
            # layer's page-granular occupancy view; release-then-extend
            # so a later epoch's shorter prompt doesn't try to shrink)
            pos = int(jax.device_get(state["glob"]["pos"]))
            self._paged_pos = pos
            self._paged_seed_slots(pos)
        return logits, state

    def rollback(self, state, pos: int):
        """Reset the decode position to `pos` (commit an accepted prefix
        of a verify round, rejecting the suffix). Purely a pos reset:
        rejected positions' cache entries hold pos_ids > pos, so they
        are invisible to attention and overwritten when decode reaches
        their position again."""
        new_state = dict(state)
        glob = dict(state["glob"])
        glob["pos"] = jnp.asarray(pos, glob["pos"].dtype)
        new_state["glob"] = glob
        return new_state

    def note_committed(self, pos: int, active) -> None:
        """Paged bookkeeping after a spec round: live slots grow to the
        committed context (several tokens per round, unlike the +1 of
        decode_requests); rejected-candidate pages were never allocated
        — the engine's dense per-slot cache only accounts committed
        tokens."""
        if not self.paged:
            return
        self._paged_pos = pos
        for slot, live in enumerate(np.asarray(active, bool)):
            if live:
                self.extend_slot(slot, pos)

    # -- online memory adaptation (DESIGN.md §13) --------------------------------
    def demoted(self, stage: int) -> int:
        """Resident slots of `stage` currently demoted into the streamed
        tier."""
        return self.k_res_b[stage] - self.k_res_live[stage]

    def demote_capacity(self, stage: int) -> int:
        """How many more resident slots `stage` can demote (bounded by its
        build-time residents and the streamed-store headroom)."""
        return min(self.k_res_b[stage], self.H) - self.demoted(stage)

    def slot_hbm_bytes(self) -> float:
        """HBM one demoted resident slot returns: the slot holds one layer
        per segment, and the streamed tier keeps a one-layer load buffer —
        Eq. 7's (#Seg − 1) factor (n_seg == 1 degenerates to the single
        copy)."""
        return max(self.plan.n_seg - 1, 1) * self.cfg.layer_params() * 2.0

    def resident_layer_ids(self) -> List[int]:
        """Flat ids of real model layers currently in the resident tier
        (the live boundary: demoted layers are excluded)."""
        ids = np.unique(self._res_ids[self._res_ids < self.cfg.n_layers])
        return [int(i) for i in ids]

    def resident_fraction(self) -> float:
        """Live resident share of the real layer stack — the draft-quality
        signal the depth controller's rung priors scale with."""
        return len(self.resident_layer_ids()) / max(self.cfg.n_layers, 1)

    def retier_stats(self) -> Dict[str, Any]:
        return {"k_res_build": list(self.k_res_b),
                "k_res_live": list(self.k_res_live),
                "demoted": [self.demoted(d)
                            for d in range(self.plan.n_stage)]}

    def retier(self, state, stage: int, delta: int):
        """Move `delta` resident layer slots of `stage` across the tier
        boundary on the LIVE pipeline (positive: demote resident ->
        streamed, negative: promote back). No recompilation: the compiled
        step's shapes are fixed at the caps; the boundary is the dynamic
        `k_res_live` input, and demotions fill the streamed store's
        headroom right-to-left so layer execution order is preserved.

        Per unit move: the slot's weights are copied into (or back from)
        the streamed store, and its KV/state cache rows move to the slot
        the layer now occupies — so a mid-stream retier changes no emitted
        token (test_engine_hetero). The vacated HBM (slot_hbm_bytes() per
        demotion) is returned to the caller for crediting to the serving
        KV page pool; on the statically-shaped TPU mapping this is an
        accounting transfer, priced for real by the simulator.

        With state=None only the tier counters move (between serving
        epochs, before init_state materializes a state — init_state then
        builds the demoted layout directly).

        Returns (new_state, freed_bytes); freed_bytes < 0 on promotion.
        """
        if delta == 0:
            return state, 0.0
        assert self.H > 0 or delta < 0, \
            "retier needs retier_headroom > 0 at engine build"
        live = state is not None
        res = state["resident"] if live else None
        off = state["offload"] if live else None
        cache = dict(state["cache"]) if live else None
        kr_b = self.k_res_b[stage]
        freed = 0.0
        moves = 0
        for _ in range(abs(delta)):
            if delta > 0:
                if self.k_res_live[stage] <= 0 \
                        or self.demote_capacity(stage) <= 0:
                    break
                j = self.k_res_live[stage] - 1
                h = self.H - (kr_b - j)
                if live:
                    w_mv = jax.tree.map(lambda r: r[:, stage, j], res)
                    off = jax.tree.map(
                        lambda o, wv: o.at[:, stage, h]
                        .set(wv.astype(o.dtype)), off, w_mv)
                    cache = {kk: v.at[:, stage, self.k_res_cap + h]
                             .set(v[:, stage, j]) for kk, v in cache.items()}
                self.k_res_live[stage] = j
                freed += self.slot_hbm_bytes()
            else:
                if self.k_res_live[stage] >= kr_b:
                    break
                j = self.k_res_live[stage]
                h = self.H - (kr_b - j)
                if live:
                    w_mv = jax.tree.map(lambda o: o[:, stage, h], off)
                    res = jax.tree.map(
                        lambda r, wv: r.at[:, stage, j]
                        .set(wv.astype(r.dtype)), res, w_mv)
                    cache = {kk: v.at[:, stage, j]
                             .set(v[:, stage, self.k_res_cap + h])
                             for kk, v in cache.items()}
                self.k_res_live[stage] = j + 1
                freed -= self.slot_hbm_bytes()
            moves += 1
        if not moves:
            return state, 0.0
        self._refresh_tier_inputs()
        if not live:
            return None, freed
        sh = self.state_shardings()
        new_state = dict(state)
        new_state["resident"] = jax.device_put(res, sh["resident"])
        new_state["offload"] = jax.device_put(off, sh["offload"])
        new_state["cache"] = jax.device_put(cache, sh["cache"])
        return new_state, freed

    def lower_step(self):
        """For the dry-run: lower the full serve_step (restore + pipeline)
        without materializing state."""
        shapes = self._abstract_state()
        t = jax.ShapeDtypeStruct((self.n_mb, self.mb, 1), jnp.int32)
        kl = jax.ShapeDtypeStruct((self.plan.n_stage,), jnp.int32)
        win = jax.ShapeDtypeStruct(
            (self.plan.n_stage, self.plan.n_seg, self.K), jnp.int32)
        real = jax.ShapeDtypeStruct(
            (self.plan.n_stage, self.plan.n_seg, self.K), jnp.bool_)
        if self.fetch_mode == "step":
            def full(res, off, shared, cache, glob, tokens, kl_in, win_in,
                     real_in):
                w = self._fetch(off)
                return self._step(res, w, shared, cache, glob, tokens,
                                  kl_in, win_in, real_in)
            return jax.jit(full, donate_argnums=(3,)).lower(
                shapes["resident"], shapes["offload"], shapes["shared"],
                shapes["cache"], shapes["glob"], t, kl, win, real)
        return self._step.lower(
            shapes["resident"], shapes["offload"], shapes["shared"],
            shapes["cache"], shapes["glob"], t, kl, win, real)

    def _abstract_state(self):
        cfg, plan = self.cfg, self.plan
        specs = M.build_param_specs(cfg)
        sh = self.state_shardings()

        def res_shape(s):
            per = (plan.n_seg, plan.n_stage, self.k_res_cap) + s.shape[1:]
            return jax.ShapeDtypeStruct(per, s.dtype)

        def off_shape(s):
            return jax.ShapeDtypeStruct(
                (plan.n_seg, plan.n_stage, self.k_off_cap) + s.shape[1:],
                s.dtype)

        layer_shapes = pspec.shapes(specs["layers"])
        res = jax.tree.map(res_shape, layer_shapes,
                           is_leaf=lambda x: isinstance(
                               x, jax.ShapeDtypeStruct))
        off = jax.tree.map(off_shape, layer_shapes,
                           is_leaf=lambda x: isinstance(
                               x, jax.ShapeDtypeStruct))
        shared = pspec.shapes({k: v for k, v in specs.items()
                               if k != "layers"})
        cs = M.cache_specs(cfg, self.n_mb * self.mb, self.max_len,
                           self.long_mode, self.enc_len)
        cache = {}
        glob = {}
        for kk, v in cs.items():
            shp = v.shape
            if kk in PER_LAYER_CACHE_KEYS:
                per = (plan.n_seg, plan.n_stage, self.K, self.n_mb,
                       self.mb) + shp[2:]
                cache[kk] = jax.ShapeDtypeStruct(per, v.dtype)
            else:
                glob[kk] = jax.ShapeDtypeStruct(shp, v.dtype)

        def with_sh(tree, shtree):
            return jax.tree.map(
                lambda s, n: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                  sharding=n),
                tree, shtree,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return {"resident": with_sh(res, sh["resident"]),
                "offload": with_sh(off, sh["offload"]),
                "shared": with_sh(shared, sh["shared"]),
                "cache": {kk: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=sh["cache"][kk])
                    for kk, v in cache.items()},
                "glob": {kk: jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for kk, v in glob.items()}}

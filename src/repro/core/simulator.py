"""Trace-driven HMS / DRAM-cache simulator (Track A, paper-faithful).

The simulator consumes preprocessed traces (`traces.preprocess`) and models,
per §III of the paper:

  * a direct-mapped DRAM cache (configurable 64..1024 B lines) over SCM,
  * AMIL vs TAD tag organizations and their probe-traffic costs,
  * the Configurable Tag Cache with LRU ways + per-sector valid bits,
  * the two-level SCM-aware bypass policy (penalty EMA filter, then victim
    DRAM-affinity comparison with probabilistic decay),
  * per-page activation counters,
  * prior-work policies (BEAR_i, RedCache_i, McCache_i) and ablations,
  * HMS shared-bus vs separate-bus organizations, SCM-only, infinite HBM,
    and the oversubscribed-HBM Unified-Memory baseline with TBN-style
    chunked migration over a PCIe/NVLink-class host link.

Runtime is a bottleneck (roofline-style) model: the max of channel-bus
occupancy, per-rank bank occupancy (activation/recovery amortized over the
MSHR run), host-link occupancy, serialized fault handling, and a compute
floor.  Counters are float64 (traces are ~10^6 requests and fp32
accumulators would lose increments); the engine entry points trace, compile
and run under ``jax.enable_x64(True)`` (the scan packs int64 words), so the
rest of the process — the model stack, the kernels — stays 32-bit.

Engine architecture (compile-once, batched, shard-parallel)
-----------------------------------------------------------
The paper's headline results are design-space *sweeps*, so the engine is
split so a sweep costs one compile and one short device loop:

  * **Static structure** — the policy's Python-level branching and every
    array shape (trace length, shard count/depth, DRAM-cache slots, CTC
    geometry) — forms an ``_EngineKey`` into a module-level jit cache.
    Slot/set allocations are bucketed to powers of two so nearby footprints
    share a compiled engine.
  * **Runtime scalars** — enabled CTC ways/sets are traced arguments;
    device timings, ``ema_weight``, ``n_levels``, ``bear_fill_prob``,
    thresholds and tag-layout costs shape the host-side request stream and
    counter reduction.  Sweeping any of them never re-traces.
  * Everything per-request-pure is hoisted out of the sequential scan and
    evaluated on the host in numpy: SCM penalty scores, the penalty EMA /
    running maxima, activation-counter values (segmented prefix sums in
    ``preprocess``), the xorshift dice test, and per-column activation
    shares.  The device scan is integer-only: it carries the genuinely
    stateful arrays (packed DRAM-cache words + CTC state) and emits one
    packed decision word per request, from which the host reduces every
    counter in float64.  Counters are therefore bit-identical on every
    backend, including a TPU, whose float64 is emulated.
  * **Shard parallelism** — the carried state partitions by address: a
    cache slot belongs to exactly one row group, and a power-of-two shard
    factor S dividing the CTC set count makes ``row_group % S`` a function
    of the CTC set index too.  ``traces.shard_plan`` stable-partitions the
    trace into S state-disjoint shards and remaps slots / row groups to
    shard-local indices; the engine gathers the precomputed per-request
    stream into ``(S, depth)`` shard layout, ``vmap``s the lean scan over
    shards (padded steps are gated no-ops), and scatters the decision flags
    back to trace order for the unchanged counter reduction.  The device
    loop shrinks from N sequential steps to max-shard-depth (~N/S) steps,
    exactly — parity with the sequential formulation is bit-for-bit because
    every slot and CTC set still sees its original request subsequence in
    order.  ``S`` is chosen per engine key (capped by ``REPRO_SHARDS`` /
    :func:`set_max_shards`, shard depth, and the CTC set counts of every
    config sharing the compile); S=1 reproduces the PR 2 sequential engine.
  * **Temporal splitting** — when spatial lanes run out (zipf traces whose
    hottest CTC set bounds the LPT depth at low S), each shard's stream is
    further cut into T *temporal segments* run as extra vmap lanes, each
    seeded from a guessed boundary carry and made exact by the fixed-point
    stitch in ``repro.core.tsplit``: re-run segments with guesses replaced
    by the carries their predecessors actually produced (composed through
    per-segment touched-slot masks) until the boundaries stop changing,
    which happens in 1-2 extra rounds because cache state forgets its seed
    quickly.  At the fixed point every emitted flag equals the sequential
    scan's, so counters stay bit-for-bit across every (S, T).  The
    (S, T) shape is chosen by ``repro.core.costmodel`` per engine key;
    a bounded-round guard falls back to the exact T=1 engine.
  * ``simulate_many`` vmaps the compiled engine over a batch of runtime
    parameter sets sharing one static structure, so Fig. 18-style CTC
    sweeps and policy ablations cost one compile + one device loop over
    ``configs x shards``.
  * The **Unified-Memory baseline** (oversubscribed HBM + page migration,
    and the HMS overflow path) lives in ``repro.um`` — the same
    compile-once treatment for the paging scan: bucketed page/frame
    allocations key a jit cache, capacity / chunk / link mode are traced
    scalars, batches vmap over UM configs, and fault/migration counters
    are segment-summed per phase.  ``simulate_many`` prefetches every UM
    point a config batch needs through one batched call, deduped by spec.

The seed formulation survives in ``_reference`` (and ``um/_reference`` for
the paging scan) and golden-parity tests pin both engines to it
counter-for-counter.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import time
import types
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import bypass as bp
from . import costmodel
from . import ctc as ctc_mod
from . import tsplit
from .x64 import x64_scoped
from .timing import (
    COLUMN_BYTES,
    COLUMNS_PER_ROW,
    POLICIES_WITH_CTC,
    UM_PAGE_BYTES,
    HMSConfig,
)
from .traces import Trace, geometry_key, preprocess, shard_depth, shard_plan

# Module (not symbol) import: repro.um imports repro.core.timing/traces,
# which are fully initialized before repro.core.__init__ reaches this
# module, and the sys.modules fallback keeps the reverse edge safe when
# repro.um is imported first.  Attributes are only touched at call time.
from repro import um as _um

# Resilience layer (module imports only: the package does all its
# repro.core imports lazily, so this edge is order-safe too).
from repro.resilience import guard as _guard
from repro.resilience import sweepckpt as _sweepckpt
from repro.resilience import validate as _rvalidate

_COUNTERS = (
    # bus traffic, in 32B columns
    "demand_dram_rd", "demand_dram_wr", "demand_scm_rd", "demand_scm_wr",
    "probe_cols", "meta_wr_cols",
    "fill_scm_rd", "fill_dram_wr", "wb_dram_rd", "wb_scm_wr",
    # bank busy cycles (pre bank-parallelism division)
    "dram_busy", "scm_busy",
    # fractional activation-event counts (for energy)
    "dram_acts", "scm_acts", "scm_wr_acts",
    # policy events
    "hit_r", "hit_w", "miss_r", "miss_w",
    "bypass_l1", "bypass_l2", "fills", "dirty_evicts", "aff_decs",
    "ctc_hit", "ctc_miss",
)

_RNG_SEED = 0x9E3779B9


@dataclasses.dataclass
class SimResult:
    name: str
    config: HMSConfig
    runtime_cycles: float
    terms: Dict[str, float]           # bottleneck terms, cycles
    counters: Dict[str, float]
    traffic_bytes: Dict[str, float]   # per-category bus traffic
    hit_rate_read: float
    hit_rate_write: float
    ctc_hit_rate: float
    bypass_l1_frac: float             # fraction of bypasses decided at level 1
    energy_pj: Dict[str, float]
    power_w: float
    # Phase attribution (scenario traces): counters[k] ==
    # float(np.sum(phase_counters[k])) bit-for-bit, because the totals are
    # *computed* as that sum.  Empty/None for unphased traces.  When the
    # UM paging model ran (hbm organization, or an HMS footprint overflow)
    # both dicts additionally carry um_faults / um_migrated /
    # um_writebacks / um_remote_cols with the same exact-sum guarantee.
    phase_names: tuple = ()
    phase_counters: Dict[str, np.ndarray] | None = None

    @property
    def total_traffic(self) -> float:
        return float(sum(self.traffic_bytes.values()))

    def phase_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase derived metrics: request count, hit rates, bypass rate,
        CTC hit rate, and DRAM/SCM bus traffic in bytes."""
        if not self.phase_counters:
            return {}
        out: Dict[str, Dict[str, float]] = {}
        for i, name in enumerate(self.phase_names):
            c = {k: float(v[i]) for k, v in self.phase_counters.items()}
            dram_cols, scm_cols = _bus_cols(c)
            tot_r = c["hit_r"] + c["miss_r"]
            tot_w = c["hit_w"] + c["miss_w"]
            tot_ctc = c["ctc_hit"] + c["ctc_miss"]
            misses = c["miss_r"] + c["miss_w"]
            # single-tier organizations track no hit/miss events; every
            # request is exactly one demand access there
            requests = tot_r + tot_w
            if requests == 0.0:
                requests = (c["demand_dram_rd"] + c["demand_dram_wr"]
                            + c["demand_scm_rd"] + c["demand_scm_wr"])
            out[name] = {
                "requests": requests,
                "hit_rate_read": c["hit_r"] / tot_r if tot_r else 0.0,
                "hit_rate_write": c["hit_w"] / tot_w if tot_w else 0.0,
                "bypass_rate": (c["bypass_l1"] + c["bypass_l2"]) / misses
                if misses else 0.0,
                "ctc_hit_rate": c["ctc_hit"] / tot_ctc if tot_ctc else 1.0,
                "fills": c["fills"],
                "dram_bytes": dram_cols * COLUMN_BYTES,
                "scm_bytes": scm_cols * COLUMN_BYTES,
                "scm_write_cols": c["demand_scm_wr"] + c["wb_scm_wr"],
            }
            if "um_faults" in c:
                # UM paging attribution (oversubscribed runs): exact by
                # construction — the whole-trace totals are these sums
                out[name].update({
                    "um_faults": c["um_faults"],
                    "um_migrated_pages": c["um_migrated"],
                    "um_writeback_pages": c["um_writebacks"],
                    "um_remote_cols": c["um_remote_cols"],
                    "um_link_bytes": (c["um_migrated"] + c["um_writebacks"])
                    * UM_PAGE_BYTES + c["um_remote_cols"] * COLUMN_BYTES,
                })
        return out


# ---------------------------------------------------------------------------
# Static structure: the jit-cache key.
# ---------------------------------------------------------------------------

def _bucket(n: int) -> int:
    """Next power of two — state arrays are allocated at bucketed sizes so
    configs with nearby geometry share one compiled engine (indices never
    reach the slack, so counters are unaffected)."""
    return 1 << max(0, int(n) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class _EngineKey:
    policy: str
    n: int                  # trace length
    shards: int             # shard-parallel width S (1 = sequential scan)
    depth: int              # padded per-shard scan length
    lines_alloc: int        # per-shard DRAM-cache slot allocation (bucketed)
    ctc_sets_alloc: int     # per-shard CTC set allocation (bucketed)
    ctc_ways_alloc: int
    ctc_sectors: int
    phases: int = 1         # counter segments (scenario phase count)
    t_segments: int = 1     # temporal segments T (1 = no splitting)
    replay: int = 0         # replay-prefix steps per segment (T > 1 only)


_USES_CTC = POLICIES_WITH_CTC

# The scan-step cost constants and shard/segment caps live in
# ``repro.core.costmodel`` (one model for both engines); these delegations
# keep the long-standing public override points on this module.


def set_max_shards(cap: int) -> int:
    """Set the shard-count cap (1 = sequential engine); returns the old cap.
    Benchmarks use this to measure shard speedup against the S=1 scan.
    Delegates to :func:`repro.core.costmodel.set_max_shards`."""
    return costmodel.set_max_shards(cap)


def set_forced_shards(n: int | None) -> int | None:
    """Pin the shard count, bypassing the cost model (any count is valid —
    set bins just go empty past the partition-domain size).  Tests use this
    so shard-parallel coverage doesn't depend on host-tuned cost constants.
    ``None`` restores automatic selection; returns the previous value.
    Delegates to :func:`repro.core.costmodel.set_forced_shards`."""
    return costmodel.set_forced_shards(n)


def _engine_key(trace: Trace, cfg: HMSConfig) -> _EngineKey:
    return group_engine_key(trace, [cfg])


def _runtime_params(cfg: HMSConfig,
                    n_sets_local: int = -1) -> Dict[str, np.ndarray]:
    """The config scalars the device scan reads: sweeping these re-uses the
    compiled scan.  ``n_sets_local`` is the *shard-local* CTC set count from
    the shard plan (the sets of one config partition across its shards).
    Every other config field acts on the host side
    (:func:`_request_stream`, :func:`_reduce_counters`)."""
    return {
        "ctc_ways": np.int32(cfg.ctc_ways),
        "ctc_sets": np.int32(cfg.ctc_sets if n_sets_local < 0
                             else n_sets_local),
    }


def _timings(cfg: HMSConfig):
    """DRAM / SCM timings as float32 scalars — the precision every policy
    score and busy-cycle share is evaluated in (exact small integers)."""
    def f32(t):
        return types.SimpleNamespace(
            rcd=np.float32(t.rcd), wr=np.float32(t.wr), rp=np.float32(t.rp))
    return f32(cfg.dram_timing), f32(cfg.scm_timing)


# ---------------------------------------------------------------------------
# Dice stream: the seed engine steps one xorshift32 per request from a fixed
# seed, so the whole stream is trace-position-only.  Generated by a jitted
# device scan (the seed's interpreted per-element Python loop was O(N) host
# work on every first use of a trace length); lengths are bucketed to powers
# of two so the generator compiles a handful of times, and slices are cached
# per exact length.
# ---------------------------------------------------------------------------

_DICE_F32: Dict[int, np.ndarray] = {}


@functools.lru_cache(maxsize=None)
def _dice_chain(m: int) -> np.ndarray:
    def gen():
        def step(s, _):
            s = bp.xorshift32(s)
            return s, s
        _, chain = jax.lax.scan(
            step, jnp.asarray(_RNG_SEED, jnp.uint32), None,
            length=m, unroll=64)
        return chain
    return np.asarray(jax.jit(gen, static_argnums=())())


def _dice(n: int) -> np.ndarray:
    if n not in _DICE_F32:
        chain = _dice_chain(_bucket(max(1, n)))[:n]
        _DICE_F32[n] = (chain.astype(np.float32)
                        * np.float32(1.0 / 4294967296.0))
    return _DICE_F32[n]


# ---------------------------------------------------------------------------
# Host side: the per-request-pure policy stream and the counter reduction.
#
# Only the stateful scan runs on the device, and it is integer-only: packed
# slot / request words in, packed decision flags out.  Every float the model
# computes — penalty and affinity scores, the penalty EMA and running
# maxima, discretized levels, busy-cycle shares and the float64 counter
# sums — is evaluated here in numpy, so counters are bit-identical on every
# backend (a TPU has no float64 unit: XLA emulates it with float32 pairs,
# which is not IEEE binary64).
# ---------------------------------------------------------------------------

def _score_key(cfg: HMSConfig, pre) -> tuple:
    """Everything :func:`_score_stream` reads besides the trace: configs
    with equal keys have equal score streams.  Keep the two in step.
    ``pre`` enters by identity, so a key is valid only while the call that
    holds ``pre`` runs."""
    dram, scm = _timings(cfg)
    return (dram.rcd, dram.wr, scm.rcd, scm.wr, float(cfg.ema_weight),
            cfg.n_levels, bool(cfg.use_activation_counter), id(pre))


def _score_stream(trace: Trace, cfg: HMSConfig,
                  pre) -> Dict[str, np.ndarray]:
    """The config's per-request bypass scores: the affinity level, the
    level-1 filter ``pass1`` and the victim-decay dice test ``dec_ok``."""
    dram, scm = _timings(cfg)
    ncols = pre["run_ncols"]
    page_act = pre["page_act"]
    dice = _dice(trace.n)

    pen = bp.scm_penalty_score(ncols, pre["run_haswrite"], dram, scm, xp=np)
    pen64 = pen.astype(np.float64)
    pen_max = np.maximum.accumulate(pen64)
    # the EMA is a sequential float64 recurrence: evaluated in Python floats
    # (IEEE binary64, same operation order as the reference scan)
    w = float(cfg.ema_weight)
    pen_ema = np.fromiter(
        itertools.accumulate(pen64.tolist(),
                             lambda a, v: bp.ema_update(a, v, w),
                             initial=0.0),
        np.float64, count=trace.n + 1)[1:]
    lv = cfg.n_levels
    req_lvl = bp.discretize(pen, pen_max, lv, xp=np)
    avg_lvl = bp.discretize(pen_ema, pen_max, lv, xp=np)
    aff = bp.affinity_score(pen, page_act, cfg.use_activation_counter, xp=np)
    aff_max = np.maximum.accumulate(aff.astype(np.float64))
    req_aff_lvl = bp.discretize(aff, aff_max, lv, xp=np)
    pass1 = req_lvl > avg_lvl
    dec_ok = dice < bp.p_dec(page_act, pre["max_act"], xp=np)
    return {"req_aff_lvl": req_aff_lvl, "pass1": pass1, "dec_ok": dec_ok}


def _pack_stream(trace: Trace, cfg: HMSConfig, pre,
                 score: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The config's per-request policy inputs from its score stream: the
    packed scan word minus its shard-local row group (``meta``, int64; see
    :func:`_make_engine` for the layout) plus the arrays
    :func:`_reduce_counters` reads.  ``score`` may be shared with other
    configs, so nothing here writes into it."""
    policy = cfg.policy
    page_act = pre["page_act"]
    is_write = pre["is_write"]
    excluded = pre["amil_excluded"] & (cfg.tag_layout == "amil")
    pass1 = score["pass1"]

    # fill candidacy before the (stateful) accept decision
    if policy in ("hms", "no_second_level"):
        cand = ~excluded & pass1
    elif policy in ("no_bypass", "no_bypass_no_ctc", "always_cache"):
        cand = ~excluded
    elif policy == "bear":
        cand = _dice(trace.n) < np.float32(cfg.bear_fill_prob)
    elif policy == "redcache":
        cand = page_act >= np.int32(cfg.redcache_threshold)
    elif policy == "mccache":
        cand = ~is_write
    else:
        raise _rvalidate.unknown_policy_error(policy)

    meta = (is_write.astype(np.int64)
            | (score["dec_ok"].astype(np.int64) << 1)
            | (cand.astype(np.int64) << 2)
            | (pre["sector"].astype(np.int64) << 3)
            | (score["req_aff_lvl"].astype(np.int64) << 8)
            | (pre["tag"].astype(np.int64) << 40))
    return {"meta": meta, "is_write": is_write, "excluded": excluded,
            "pass1": pass1, "ncols": pre["run_ncols"]}


def _request_stream(trace: Trace, cfg: HMSConfig,
                    pre) -> Dict[str, np.ndarray]:
    """One config's policy inputs, scored and packed (:func:`_pack_stream`)."""
    return _pack_stream(trace, cfg, pre, _score_stream(trace, cfg, pre))


def _shared_request_streams(trace: Trace, cfgs: Sequence[HMSConfig], pres,
                            keys) -> List[Dict[str, np.ndarray]]:
    """The policy inputs of every config of one engine call, computing one
    score stream per distinct score key (``keys[i]`` is
    ``_score_key(cfgs[i], pres[i])``) and packing each config from it."""
    scores = {}
    for c, p, k in zip(cfgs, pres, keys):
        if k not in scores:
            scores[k] = _score_stream(trace, c, p)
    return [_pack_stream(trace, c, p, scores[k])
            for c, p, k in zip(cfgs, pres, keys)]


def _reduce_counters(trace: Trace, cfg: HMSConfig, rs: Dict[str, np.ndarray],
                     y: np.ndarray) -> Dict[str, np.ndarray]:
    """Counters from the scan's trace-order decision words ``y``.

    Phased traces reduce every counter per phase (``(P,)`` float64); the
    whole-trace totals are then *defined* as the sum of the per-phase
    vector, so phase attribution is exact by construction.  Unphased traces
    reduce to float64 scalars."""
    policy = cfg.policy
    use_ctc = policy in _USES_CTC
    ideal_probe = policy in ("bear", "redcache", "mccache")
    two_level = policy in ("hms", "no_second_level")
    dram, scm = _timings(cfg)
    amil = cfg.tag_layout == "amil"
    probe_cost = np.float32(1.0 if amil else float(cfg.lines_per_row))
    meta_wr_cost = np.float32(1.0 if amil else 0.0)
    cpl = np.float32(cfg.columns_per_line)
    ncols = rs["ncols"]
    is_write = rs["is_write"]

    hit = (y & 1) != 0
    c_hit = (y & 2) != 0
    do_fill = (y & 4) != 0
    rejected = (y & 8) != 0
    dec = (y & 16) != 0
    wb = (y & 32) != 0
    nar = (y & 64) != 0
    miss = ~hit

    n_ph = trace.n_phases
    if n_ph > 1:
        C = {k: np.zeros((n_ph,), np.float64) for k in _COUNTERS}

        def add(name, v):
            C[name] = C[name] + np.bincount(
                trace.phase_id, weights=np.asarray(v, np.float64),
                minlength=n_ph)
    else:
        C = {k: np.float64(0.0) for k in _COUNTERS}

        def add(name, v):
            C[name] = C[name] + np.sum(np.asarray(v, np.float64))

    if use_ctc:
        add("ctc_hit", c_hit)
        add("ctc_miss", ~c_hit)
        add("probe_cols", np.where(c_hit, 0.0, probe_cost))
        add("dram_busy",
            np.where(c_hit, 0.0, dram.rcd + probe_cost + dram.rp))
        add("dram_acts", np.where(c_hit, 0.0, 1.0))
    elif not ideal_probe:
        add("ctc_miss", np.ones_like(hit))
        add("probe_cols", np.full(hit.shape, probe_cost))
        add("dram_busy",
            np.full(hit.shape, dram.rcd + probe_cost + dram.rp))
        add("dram_acts", np.ones_like(hit))

    if two_level:
        add("bypass_l1", miss & ~rs["excluded"] & ~rs["pass1"])
        add("bypass_l2", rejected)
        add("aff_decs", dec)
        if policy == "hms":
            add("probe_cols", nar)
            add("dram_busy",
                np.where(nar, dram.rcd + 1.0 + dram.rp, 0.0))
            add("dram_acts", nar)

    rd = ~is_write
    add("hit_r", hit & rd)
    add("hit_w", hit & is_write)
    add("miss_r", miss & rd)
    add("miss_w", miss & is_write)
    add("demand_dram_rd", hit & rd)
    add("demand_dram_wr", hit & is_write)
    dram_share = (dram.rcd + dram.rp) / ncols + np.where(
        is_write, dram.wr / ncols, 0.0)
    scm_share = (scm.rcd + scm.rp) / ncols + np.where(
        is_write, scm.wr / ncols, 0.0)
    add("dram_busy", np.where(hit, 1.0 + dram_share, 0.0))
    add("dram_acts", np.where(hit, 1.0 / ncols, 0.0))
    if policy == "mccache":
        wt = hit & is_write
        add("demand_scm_wr", wt)
        add("scm_busy", np.where(wt, 1.0 + scm_share, 0.0))
        add("scm_acts", np.where(wt, 1.0 / ncols, 0.0))
        add("scm_wr_acts", np.where(wt, 1.0 / ncols, 0.0))

    dem_scm_rd = miss & rd & ~do_fill
    dem_scm_wr = miss & is_write & ~do_fill
    add("demand_scm_rd", dem_scm_rd)
    add("demand_scm_wr", dem_scm_wr)
    add("scm_busy",
        np.where(dem_scm_rd | dem_scm_wr, 1.0 + scm_share, 0.0))
    add("scm_acts", np.where(dem_scm_rd | dem_scm_wr, 1.0 / ncols, 0.0))
    add("scm_wr_acts", np.where(dem_scm_wr, 1.0 / ncols, 0.0))

    add("fills", do_fill)
    add("fill_scm_rd", np.where(do_fill, cpl, 0.0))
    add("fill_dram_wr", np.where(do_fill, cpl, 0.0))
    add("meta_wr_cols", np.where(do_fill, meta_wr_cost, 0.0))
    add("scm_busy", np.where(do_fill, scm.rcd + cpl + scm.rp, 0.0))
    add("dram_busy",
        np.where(do_fill, dram.rcd + cpl + dram.wr + dram.rp
                 + meta_wr_cost, 0.0))
    add("scm_acts", do_fill)
    add("dram_acts", do_fill)

    add("dirty_evicts", wb)
    add("wb_dram_rd", np.where(wb, cpl, 0.0))
    add("wb_scm_wr", np.where(wb, cpl, 0.0))
    add("dram_busy", np.where(wb, dram.rcd + cpl + dram.rp, 0.0))
    add("scm_busy", np.where(wb, scm.rcd + cpl + scm.wr + scm.rp, 0.0))
    add("dram_acts", wb)
    add("scm_acts", wb)
    add("scm_wr_acts", wb)
    return C


def _engine_inputs(trace: Trace, cfg: HMSConfig, pre, rs,
                   key: _EngineKey) -> Dict[str, np.ndarray]:
    """Device inputs of one config at ``key``'s shard plan: shard-local
    slots, the packed request words (``rs["meta"]`` plus the shard-local
    row group) and the gather/scatter positions."""
    # packed-word layout limits (tag<<10 must stay inside int32; affinity
    # levels live in an 8-bit field; CTC tag+1 in a 23-bit field) — raised
    # as structured EngineInvariantErrors so python -O keeps the guarantee
    _rvalidate.check_hms_packing(
        trace.name, tag_max=int(pre["tag"].max(initial=0)),
        n_levels=cfg.n_levels)
    shards, depth = key.shards, key.depth
    plan = shard_plan(trace, cfg, shards)
    _rvalidate.check_hms_packing(
        trace.name, rg_max=int(plan["rg_local"].max(initial=0)))
    pos = plan["pos"]
    if plan["depth"] < depth:           # pad to the engine's (group) depth
        pad = np.full((shards, depth - plan["depth"]), trace.n, np.int32)
        pos = np.concatenate([pos, pad], axis=1)
    out = {
        "slot": plan["slot_local"],
        "meta": rs["meta"] | (plan["rg_local"].astype(np.int64) << 17),
        "pos": pos,
    }
    if key.t_segments > 1:
        # cut each shard row into T temporal segments: the scan lanes become
        # S*T, scatter positions keep replay/pad steps on the dropped
        # sentinel, gather positions re-execute the replay window
        lanes = shards * key.t_segments
        sp = tsplit.split_positions(pos, trace.n, key.t_segments, key.replay)
        out["pos"] = sp["spos"].reshape(lanes, -1)
        if key.replay > 0:
            out["gpos"] = sp["gpos"].reshape(lanes, -1)
            out["replay"] = sp["replay"].reshape(lanes, -1)
    return out


# ---------------------------------------------------------------------------
# The compiled engine: the lean, integer-only stateful scan.
# ---------------------------------------------------------------------------

def _make_engine(key: _EngineKey):
    policy = key.policy
    use_ctc = policy in _USES_CTC
    ideal_probe = policy in ("bear", "redcache", "mccache")
    dirty_ok = policy != "mccache"
    # Temporally split engines (T > 1) take explicit boundary carries and
    # return the per-lane final carries alongside the decision words, so the
    # host stitch loop can compose and re-run them to the exact fixed point.
    # Unsplit engines keep the lean (xs, p) -> y shape — no carry transfer
    # on the common path.
    split = key.t_segments > 1

    def _impl(xs, p, carry, use_replay):
        # ---- the sequential core: only genuinely stateful arrays ----------
        # The DRAM-cache metadata (tag, affinity level, dirty, valid) packs
        # into one int32 word per slot: one gather + one scatter per step
        # instead of four of each, and a single carry buffer XLA keeps
        # in-place.  Layout: tag<<10 | aff<<2 | dirty<<1 | valid; an all-zero
        # word is an invalid slot, so no -1 sentinel is needed (the valid bit
        # gates tag comparison).  Unpacked values are exactly the seed
        # engine's int32/bool state, so counters are unchanged.
        #
        # The scan runs vmapped over ``key.shards`` state-disjoint shards:
        # the per-request stream is gathered into (shards, depth) layout via
        # the shard plan's position matrix, each shard carries its own
        # cache/CTC slice, and padded steps (pos == n) are gated no-ops.
        # The decision stream is packed into one int32 word per request
        # (and the CTC state into two words per way) to keep per-lane scan
        # work minimal — the loop is work-bound, not dispatch-bound, once
        # configs x shards fills the vector units.
        n_sets = p["ctc_sets"]
        e_ways = p["ctc_ways"]

        pos = jnp.asarray(xs["pos"])            # (lanes, L), pad == n
        pvalid = pos < key.n
        if split and key.replay > 0:
            # replay-prefix steps gather real history (gpos) but scatter to
            # the dropped sentinel; their state-updates are live only in the
            # warm-up round (use_replay is a traced bool, so disabling them
            # never re-traces) — re-run rounds see pure core segments
            posc = jnp.asarray(xs["gpos"])
            live = pvalid | (jnp.asarray(xs["replay"]) & use_replay)
        else:
            posc = jnp.minimum(pos, key.n - 1)
            live = pvalid

        def gather(a):
            return jnp.take(jnp.asarray(a), posc, axis=0)

        # one int64 word per request (packed on the host): bits 0 is_write |
        # 1 dec_ok | 2 cand | 3..7 sector | 8..15 req_aff_lvl | 16 live (pad
        # gate, set after the shard gather) | 17..39 row group | 40..61 tag —
        # two input streams (slot + meta) instead of eight keeps the scan's
        # per-step slicing minimal.
        scan_xs = {
            "slot": gather(xs["slot"]),
            "meta": gather(xs["meta"]) | (live.astype(jnp.int64) << 16),
        }

        def step(carry, x):
            cache, ctcst = carry
            slot = x["slot"]
            meta = x["meta"]
            tag = (meta >> 40).astype(jnp.int32)
            rg = (meta >> 17) & 0x7FFFFF
            live = (meta & (1 << 16)) != 0
            is_wr = (meta & 1) != 0
            x_dec_ok = (meta & 2) != 0
            x_cand = (meta & 4) != 0
            sector = (meta >> 3) & 0x1F
            raff = ((meta >> 8) & 0xFF).astype(jnp.int32)

            word = cache[slot]
            victim_valid = (word & 1) == 1
            victim_dirty = ((word & 2) == 2) & victim_valid
            victim_aff = (word >> 2) & 0xFF
            stored_tag = word >> 10
            hit = victim_valid & (stored_tag == tag)

            if use_ctc:
                ctcst, c_hit = ctc_mod.probe_fill_touch_packed(
                    ctcst, rg, sector, e_ways, n_sets, update=live)
            elif ideal_probe:
                c_hit = jnp.asarray(True)
            else:
                c_hit = jnp.asarray(False)

            miss = ~hit
            if policy == "hms":
                accept = (~victim_valid) | (raff > victim_aff)
                need_aff_read = miss & x_cand & c_hit & victim_valid
            else:
                accept = jnp.asarray(True)
                need_aff_read = jnp.asarray(False)
            do_fill = miss & x_cand & accept
            rejected = miss & x_cand & ~accept
            dec = rejected & victim_valid & x_dec_ok

            set_dirty = (hit | do_fill) & is_wr & dirty_ok
            new_tag = jnp.where(do_fill, tag, stored_tag)
            new_valid = victim_valid | do_fill
            new_dirty = jnp.where(
                do_fill, set_dirty,
                ((word & 2) == 2) | (hit & is_wr & dirty_ok))
            new_aff = jnp.where(
                do_fill,
                raff,
                jnp.maximum(victim_aff - dec.astype(jnp.int32), 0),
            )
            new_word = ((new_tag << 10) | (new_aff << 2)
                        | (new_dirty.astype(jnp.int32) << 1)
                        | new_valid.astype(jnp.int32))
            cache = cache.at[slot].set(jnp.where(live, new_word, word))

            # decision flags, packed so one scatter restores trace order
            y = (hit.astype(jnp.int32)
                 | (jnp.asarray(c_hit, jnp.int32) << 1)
                 | (do_fill.astype(jnp.int32) << 2)
                 | (rejected.astype(jnp.int32) << 3)
                 | (dec.astype(jnp.int32) << 4)
                 | ((do_fill & victim_dirty).astype(jnp.int32) << 5)
                 | (jnp.asarray(need_aff_read, jnp.int32) << 6))
            return (cache, ctcst), y

        if split:
            def shard_scan(sh_xs, cache0, ctc0):
                (cf, tf), y = jax.lax.scan(step, (cache0, ctc0), sh_xs)
                return (cf, tf), y

            (cache_f, ctc_f), y_sh = jax.vmap(shard_scan)(
                scan_xs, jnp.asarray(carry[0]), jnp.asarray(carry[1]))
        else:
            def shard_scan(sh_xs):
                cache = jnp.zeros((key.lines_alloc,), jnp.int32)
                ctcst = ctc_mod.packed_init(
                    key.ctc_sets_alloc, key.ctc_ways_alloc, key.ctc_sectors)
                _, y = jax.lax.scan(step, (cache, ctcst), sh_xs)
                return y

            y_sh = jax.vmap(shard_scan)(scan_xs)      # (lanes, L) int32

        # scatter the packed decision words back to trace order; padding
        # sentinels land in the dropped overflow slot n
        y_tr = jnp.zeros((key.n + 1,), jnp.int32).at[pos.reshape(-1)].set(
            y_sh.reshape(-1))[: key.n]
        if split:
            return (cache_f, ctc_f), y_tr
        return y_tr

    if split:
        def engine(xs, p, carry, use_replay):
            return _impl(xs, p, carry, use_replay)
    else:
        def engine(xs, p):
            return _impl(xs, p, None, None)

    return engine


# Module-level jit caches: one compiled engine per static structure, plus a
# per-batch-width vmapped variant.  ``_TRACE_COUNTS`` counts Python traces of
# each engine (a retrace executes the Python body), which the no-retrace test
# asserts on.
_ENGINE_CACHE: Dict[_EngineKey, object] = {}
_BATCHED_CACHE: Dict[_EngineKey, object] = {}
_TRACE_COUNTS: Dict[_EngineKey, int] = {}


def engine_trace_count(key: _EngineKey) -> int:
    """How many times the engine for ``key`` has been traced (compiled)."""
    return _TRACE_COUNTS.get(key, 0)


def group_engine_key(trace: Trace, configs: Sequence[HMSConfig]) -> _EngineKey:
    """The engine key ``simulate_many`` uses for a batch of scan configs
    (shard count and allocations are group-wide, so this can differ from any
    single config's ``_engine_key``).  Shard plans and allocations derive
    from cached per-config preprocessing."""
    cfgs = [c.validate() for c in configs]
    policies = {c.policy for c in cfgs}
    sectors = {c.ctc_sectors_per_line for c in cfgs}
    assert len(policies) == 1 and len(sectors) == 1, (
        "group_engine_key wants configs from one static-structure group")
    policy = policies.pop()
    replay = tsplit.replay_prefix()
    with obs.span("shard_plan", policy=policy, configs=len(cfgs)):
        split = costmodel.plan_hms_split(
            lambda s: max(shard_depth(trace, c, s) for c in cfgs),
            len(cfgs), replay)
        shards, t_seg = split.shards, split.t_segments
        plans = [shard_plan(trace, c, shards) for c in cfgs]
    depth = max(p["depth"] for p in plans)
    # a forced T may exceed the shard depth; segments need >= 1 core step
    t_seg = max(1, min(t_seg, depth))
    use_ctc = policy in _USES_CTC
    key = _EngineKey(
        policy=policy,
        n=trace.n,
        shards=shards,
        depth=depth,
        lines_alloc=_bucket(max(p["lines_bound"] for p in plans)),
        # non-CTC policies carry no CTC state; allocate the minimum
        ctc_sets_alloc=_bucket(max(p["n_sets_local"] for p in plans))
        if use_ctc else 1,
        ctc_ways_alloc=_bucket(max(c.ctc_ways for c in cfgs))
        if use_ctc else 1,
        ctc_sectors=sectors.pop(),
        phases=trace.n_phases,
        t_segments=t_seg,
        replay=replay if t_seg > 1 else 0,
    )
    _PLAN_BY_KEY[key] = split
    return key


# The planner decision behind each engine key (prediction + rejected
# alternatives), kept for the ledger's plan-regret telemetry.  Bounded by
# the same static-structure diversity as the jit caches.
_PLAN_BY_KEY: Dict[_EngineKey, costmodel.SplitPlan] = {}


def _fingerprint(key: _EngineKey, width: int) -> str:
    """Sentinel/ledger fingerprint of one compiled unit: the static engine
    key plus the vmap batch width (the batched jit re-specializes per
    width, so width is part of what 'one compile' means)."""
    return (f"hms:{key.policy}:n{key.n}:s{key.shards}x{key.depth}"
            f":T{key.t_segments}r{key.replay}"
            f":L{key.lines_alloc}:C{key.ctc_sets_alloc}x{key.ctc_ways_alloc}"
            f"x{key.ctc_sectors}:p{key.phases}:w{width}")


def _obs_hms_record(entry: str, trace: Trace, key: _EngineKey, width: int,
                    compiled: bool, wall_s: float, digest: str,
                    rounds: int = 1, outcome=None,
                    cfgs: Sequence[HMSConfig] = (),
                    lanes: Sequence[Dict[str, np.ndarray]] = (),
                    plan=None, input_bytes: int | None = None,
                    score_streams: int | None = None) -> None:
    """Build + emit one HMS ledger record (caller gates on obs.enabled()).
    ``key`` is the engine key that actually produced the counters (the
    degradation ladder may have descended from the planned one);
    ``outcome`` is the guard's :class:`~repro.resilience.guard
    .LadderOutcome`.  ``cfgs``/``lanes`` are the per-vmap-lane configs and
    raw counter dicts — recorded in full (schema 3) so the silver store
    gets model counters, not just the digest.  The config key hashes the
    config alone (no link mode): these are raw scan counters, upstream of
    the UM-overflow term that makes ``nvlink`` matter.  ``plan`` is the
    :class:`~repro.core.costmodel.SplitPlan` behind the *planned* shape
    (schema 4: prediction + rejected alternatives ride the record even
    when the ladder descended).  ``input_bytes`` is what the engine call
    staged to the device (schema 5); ``score_streams`` the distinct score
    streams computed for its configs (schema 6)."""
    obs.record(obs.RunRecord(
        entry=entry, engine="hms", trace=trace.name, n=trace.n,
        phases=key.phases, engine_key=_fingerprint(key, width),
        compiled=compiled, wall_s=wall_s, batch=width,
        counter_digest=digest, shards=key.shards, depth=key.depth,
        load_imbalance=key.shards * key.depth / max(1, key.n),
        t_segments=key.t_segments, stitch_rounds=rounds,
        replay_prefix=key.replay,
        ladder_rung=outcome.rung if outcome is not None else None,
        retries=outcome.retries if outcome is not None else None,
        degradations=(outcome.events or None)
        if outcome is not None else None,
        trace_fp=_sweepckpt.trace_fingerprint(trace),
        config_digests=[_sweepckpt.config_digest(c) for c in cfgs] or None,
        counters=[_sweepckpt.encode_counters(C) for C in lanes] or None,
        plan_predicted_us=plan.predicted_us if plan is not None else None,
        plan_alternatives=list(plan.alternatives) or None
        if plan is not None else None,
        calib_fingerprint=costmodel.active_profile().fingerprint,
        input_bytes=input_bytes, score_streams=score_streams,
        host=obs.host_metadata(), **obs.git_info()))


def _counting(key: _EngineKey):
    base = _make_engine(key)

    def fn(*args):
        # body runs only when jit (re-)traces, so the span measures trace
        # (staging) time and the count increments once per compile
        _TRACE_COUNTS[key] = _TRACE_COUNTS.get(key, 0) + 1
        with obs.span("compile", engine="hms", policy=key.policy):
            return base(*args)

    return fn


def _engine_for(key: _EngineKey):
    if key not in _ENGINE_CACHE:
        _ENGINE_CACHE[key] = jax.jit(_counting(key))
    return _ENGINE_CACHE[key]


def _batched_engine_for(key: _EngineKey):
    # Stacked xs (in_axes=0 everywhere) costs batch-width host copies of the
    # trace arrays but runs ~3x faster than broadcasting shared arrays with
    # in_axes=None: the vmapped scan slices uniform batched xs contiguously
    # per step, while broadcast operands re-materialize inside the loop.
    # jit re-specializes per batch shape on its own, so the key needs no
    # width component.
    if key not in _BATCHED_CACHE:
        if key.t_segments > 1:
            # per-config xs/params/carries; the replay flag is shared
            vmapped = jax.vmap(_counting(key), in_axes=(0, 0, 0, None))
        else:
            vmapped = jax.vmap(_counting(key))
        _BATCHED_CACHE[key] = jax.jit(vmapped)
    return _BATCHED_CACHE[key]


def _local_sets(trace: Trace, cfg: HMSConfig, key: _EngineKey) -> int:
    if cfg.policy not in _USES_CTC:
        return 1
    return shard_plan(trace, cfg, key.shards)["n_sets_local"]


def _stitch_masks(trace: Trace, cfg: HMSConfig, key: _EngineKey):
    """Touched masks of the fixed-point stitch: which cache slots
    (``(S, T, lines_alloc)`` bool) and CTC set rows (``(S, T, sets_alloc)``
    bool) each (shard, segment)'s *real core* steps access.

    Every scan step reads and writes exactly its own slot and CTC set row
    (dead steps write the old value back), so a segment's output restricted
    to its touched mask is a pure function of its input restricted to that
    mask — which is what makes masked composition in ``_run_split``
    equivalent to sequential chaining at the fixed point.  Replay-prefix
    steps are excluded: their perturbations must never leak into composed
    boundaries."""
    plan = shard_plan(trace, cfg, key.shards)
    pos = plan["pos"]
    if plan["depth"] < key.depth:
        pad = np.full((key.shards, key.depth - plan["depth"]),
                      trace.n, np.int32)
        pos = np.concatenate([pos, pad], axis=1)
    sp = tsplit.split_positions(pos, trace.n, key.t_segments, key.replay)
    S, T = key.shards, key.t_segments
    core = sp["spos"][:, :, key.replay:]         # (S, T, c) real scatter pos
    valid = core < trace.n
    corec = np.minimum(core, max(trace.n - 1, 0))
    s_idx = np.broadcast_to(np.arange(S)[:, None, None], core.shape)[valid]
    t_idx = np.broadcast_to(np.arange(T)[None, :, None], core.shape)[valid]
    slot_mask = np.zeros((S, T, key.lines_alloc), bool)
    slot_mask[s_idx, t_idx, plan["slot_local"][corec][valid]] = True
    set_mask = np.zeros((S, T, key.ctc_sets_alloc), bool)
    if cfg.policy in _USES_CTC:
        sets = plan["rg_local"][corec] % plan["n_sets_local"]
        set_mask[s_idx, t_idx, sets[valid]] = True
    return slot_mask, set_mask


def _run_split(key: _EngineKey, fn, xs, params, masks):
    """Drive a T>1 engine to its exact fixed point (see ``repro.core.tsplit``).

    ``masks`` are the per-config touched masks from :func:`_stitch_masks`,
    with a leading batch axis when ``fn`` is the batched engine.  Returns
    ``(decision_words, total_rounds)`` — words from the converged round
    only, so they are bit-for-bit the sequential scan's."""
    slot_m, set_m = masks
    S, T = key.shards, key.t_segments
    lanes = S * T
    lead = slot_m.shape[:-3]                     # () or (batch,)
    ctc_row = np.asarray(ctc_mod.packed_init(
        key.ctc_sets_alloc, key.ctc_ways_alloc, key.ctc_sectors))
    cache0 = np.zeros(lead + (lanes, key.lines_alloc), np.int32)
    ctc0 = np.broadcast_to(ctc_row, lead + (lanes,) + ctc_row.shape).copy()
    seg_c = lead + (S, T, key.lines_alloc)
    seg_t = lead + (S, T) + ctc_row.shape

    def run(g, use_replay):
        (cache_f, ctc_f), y = fn(xs, params, g, np.bool_(use_replay))
        return (np.asarray(cache_f), np.asarray(ctc_f)), np.asarray(y)

    def advance(g, out):
        # compose boundary guesses from the segment outputs: a slot's value
        # at boundary t is the last earlier segment's output where touched,
        # else the cold value — exactly sequential semantics once outputs
        # are exact on their touched masks
        cache_o = out[0].reshape(seg_c)
        ctc_o = out[1].reshape(seg_t)
        new_c = np.empty_like(cache_o)
        new_t = np.empty_like(ctc_o)
        new_c[..., 0, :] = 0
        new_t[..., 0, :, :] = ctc_row
        for t in range(1, T):
            m = slot_m[..., t - 1, :]
            new_c[..., t, :] = np.where(
                m, cache_o[..., t - 1, :], new_c[..., t - 1, :])
            mt = set_m[..., t - 1, :, None]
            new_t[..., t, :, :] = np.where(
                mt, ctc_o[..., t - 1, :, :], new_t[..., t - 1, :, :])
        return new_c.reshape(cache0.shape), new_t.reshape(ctc0.shape)

    def equal(a, b):
        return np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])

    g = (cache0, ctc0)
    extra = 0
    if key.replay > 0:
        # warm-up round: replay prefixes live, to produce closer guesses.
        # Its decisions are never accepted — replay perturbs segment state,
        # so only replay-off rounds carry exact sequential semantics.
        out, _ = run(g, True)
        g = advance(g, out)
        extra = 1
    y, rounds = tsplit.stitch(
        lambda gg, _r: run(gg, False), g, advance, equal,
        max_rounds=key.t_segments + 1)
    return y, rounds + extra


def _ladder_key(trace: Trace, cfgs: Sequence[HMSConfig], key: _EngineKey,
                shards: int) -> _EngineKey:
    """Rebuild the (group) engine key at a degraded shard count, temporal
    split off.  Allocations are group-wide maxima, exactly like
    :func:`group_engine_key` — a degraded rung is just a smaller planned
    shape, not a special engine."""
    plans = [shard_plan(trace, c, shards) for c in cfgs]
    use_ctc = key.policy in _USES_CTC
    return dataclasses.replace(
        key, shards=shards,
        depth=max(p["depth"] for p in plans),
        lines_alloc=_bucket(max(p["lines_bound"] for p in plans)),
        ctc_sets_alloc=_bucket(max(p["n_sets_local"] for p in plans))
        if use_ctc else 1,
        t_segments=1, replay=0)


def _hms_ladder_keys(trace: Trace, cfgs: Sequence[HMSConfig],
                     key: _EngineKey) -> List[_EngineKey]:
    """Engine keys for the degradation rungs (S, T) -> (S, 1) -> (1, 1);
    every one reproduces the sequential scan bit-for-bit."""
    out = []
    for s, t in costmodel.degradation_ladder(key.shards, key.t_segments):
        if (s, t) == (key.shards, key.t_segments):
            out.append(key)
        elif s == key.shards:
            out.append(dataclasses.replace(key, t_segments=1, replay=0))
        else:
            out.append(_ladder_key(trace, cfgs, key, s))
    return out


def _hms_reference_attempt(trace: Trace, cfgs: Sequence[HMSConfig],
                           key: _EngineKey):
    """Last ladder rung: the frozen seed engine.  It returns whole-trace
    totals only (no per-phase vectors), so the ladder offers it for
    unphased traces — where its counters are pinned bit-equal to the
    batched engine's by ``tests/test_engine_parity.py``."""
    from . import _reference
    label = dataclasses.replace(key, shards=1, t_segments=1, replay=0)
    per = [_reference.reference_counters(trace, c) for c in cfgs]
    if len(cfgs) == 1:
        C = {k: np.float64(v) for k, v in per[0].items()}
    else:
        C = {k: np.asarray([d[k] for d in per], np.float64)
             for k in per[0]}
    return C, 1, label, False, None


def _run_hms_scan(trace: Trace, cfg: HMSConfig, pre,
                  key: _EngineKey | None = None,
                  entry: str = "simulate") -> Dict[str, np.ndarray]:
    if key is None:
        key = _engine_key(trace, cfg)
    with obs.span("request_stream", engine="hms", configs=1, streams=1):
        rs = _request_stream(trace, cfg, pre)

    def attempt(k: _EngineKey):
        def thunk():
            with obs.span("engine_inputs", engine="hms", batch=1):
                xs = _engine_inputs(trace, cfg, pre, rs, k)
                params = _runtime_params(cfg, _local_sets(trace, cfg, k))
            fn = _engine_for(k)
            before = _TRACE_COUNTS.get(k, 0)
            rounds = 1
            with obs.span("scan", engine="hms", policy=k.policy,
                          shards=k.shards, batch=1):
                if k.t_segments > 1:
                    with obs.span("stitch", engine="hms",
                                  segments=k.t_segments, replay=k.replay):
                        masks = _stitch_masks(trace, cfg, k)
                        y, rounds = _run_split(k, fn, xs, params, masks)
                else:
                    y = obs.engine_call("hms", fn, (xs, params), np.asarray)
            # scalar (unphased) or (n_phases,) vector per counter
            with obs.span("reduce_counters", engine="hms", batch=1):
                C = _reduce_counters(trace, cfg, rs, y)
            return (C, rounds, k, _TRACE_COUNTS.get(k, 0) > before,
                    obs.staged_bytes(xs, params))
        return thunk

    rungs = [(f"S{k.shards}T{k.t_segments}", attempt(k))
             for k in _hms_ladder_keys(trace, [cfg], key)]
    if key.phases == 1:
        rungs.append(
            ("reference",
             lambda: _hms_reference_attempt(trace, [cfg], key)))
    t0 = time.perf_counter()
    (C, rounds, used, compiled, staged), outcome = _guard.run_ladder(
        "hms", rungs)
    wall = time.perf_counter() - t0
    plan = _PLAN_BY_KEY.get(key)
    if outcome.rung != "reference":
        obs.engine_run(_fingerprint(used, 1), compiled)
        if plan is not None and used == key:
            costmodel.check_plan_drift(_fingerprint(used, 1),
                                       plan.predicted_us, wall, compiled)
    if obs.enabled():
        with obs.span("obs_record", engine="hms"):
            _obs_hms_record(entry, trace, used, 1, compiled, wall,
                            obs.counter_digest(C), rounds, outcome,
                            cfgs=[cfg], lanes=[C], plan=plan,
                            input_bytes=staged,
                            score_streams=None
                            if outcome.rung == "reference" else 1)
    return C


def _run_hms_batch(trace: Trace, cfgs: Sequence[HMSConfig], key: _EngineKey,
                   entry: str = "simulate_many") -> Dict[str, np.ndarray]:
    """Run one compatible config group through the batched engine (with the
    temporal-split stitch when the key says so), under the degradation
    ladder — an OOM on the whole batch bisects into guarded halves.
    Returns the stacked counter dict: ``(batch,)`` or ``(batch, phases)``
    float64 per counter."""
    with obs.span("preprocess", trace=trace.name, batch=len(cfgs)):
        pres = [preprocess(trace, c) for c in cfgs]
        keys = [_score_key(c, p) for c, p in zip(cfgs, pres)]
        n_scores = len(set(keys))
        with obs.span("request_stream", engine="hms", configs=len(cfgs),
                      streams=n_scores):
            streams = _shared_request_streams(trace, cfgs, pres, keys)

    def attempt(k: _EngineKey):
        def thunk():
            with obs.span("engine_inputs", engine="hms", batch=len(cfgs)):
                xs_list = [_engine_inputs(trace, c, p, r, k)
                           for c, p, r in zip(cfgs, pres, streams)]
                xs = {kk: np.stack([x[kk] for x in xs_list])
                      for kk in xs_list[0]}
                params_list = [_runtime_params(c, _local_sets(trace, c, k))
                               for c in cfgs]
                params = {kk: np.stack([p[kk] for p in params_list])
                          for kk in params_list[0]}
            fn = _batched_engine_for(k)
            before = _TRACE_COUNTS.get(k, 0)
            rounds = 1
            with obs.span("scan", engine="hms", policy=k.policy,
                          shards=k.shards, batch=len(cfgs)):
                if k.t_segments > 1:
                    with obs.span("stitch", engine="hms",
                                  segments=k.t_segments, replay=k.replay):
                        pairs = [_stitch_masks(trace, c, k) for c in cfgs]
                        masks = (np.stack([a for a, _ in pairs]),
                                 np.stack([b for _, b in pairs]))
                        ys, rounds = _run_split(k, fn, xs, params, masks)
                else:
                    ys = obs.engine_call("hms", fn, (xs, params), np.asarray)
            with obs.span("reduce_counters", engine="hms", batch=len(cfgs)):
                lanes = [_reduce_counters(trace, c, r, y)
                         for c, r, y in zip(cfgs, streams, ys)]
                Cs = {kk: np.stack([C[kk] for C in lanes])
                      for kk in lanes[0]}
            return (Cs, rounds, k, _TRACE_COUNTS.get(k, 0) > before,
                    obs.staged_bytes(xs, params))
        return thunk

    def bisect():
        # OOM relief: run the halves as their own guarded batches (they
        # emit their own ledger records and may bisect further); the
        # allocations in ``key`` are group maxima, so subsets reuse it.
        h = len(cfgs) // 2
        A = _run_hms_batch(trace, cfgs[:h], key, entry)
        B = _run_hms_batch(trace, cfgs[h:], key, entry)
        Cs = {kk: np.concatenate([A[kk], B[kk]], axis=0) for kk in A}
        return Cs, 1, key, False, None

    rungs = [(f"S{k.shards}T{k.t_segments}", attempt(k))
             for k in _hms_ladder_keys(trace, cfgs, key)]
    if key.phases == 1:
        rungs.append(
            ("reference",
             lambda: _hms_reference_attempt(trace, cfgs, key)))
    t0 = time.perf_counter()
    (Cs, rounds, used, compiled, staged), outcome = _guard.run_ladder(
        "hms_batch", rungs, bisect=bisect if len(cfgs) > 1 else None)
    wall = time.perf_counter() - t0
    plan = _PLAN_BY_KEY.get(key)
    ran = outcome.rung not in ("reference", "bisect")
    if ran:
        obs.engine_run(_fingerprint(used, len(cfgs)), compiled)
        if plan is not None and used == key:
            costmodel.check_plan_drift(_fingerprint(used, len(cfgs)),
                                       plan.predicted_us, wall, compiled)
    if obs.enabled():
        with obs.span("obs_record", engine="hms"):
            lanes = [{k: v[j] for k, v in Cs.items()}
                     for j in range(len(cfgs))]
            _obs_hms_record(
                entry, trace, used, len(cfgs), compiled, wall,
                obs.counter_digest(lanes), rounds, outcome,
                cfgs=cfgs, lanes=lanes, plan=plan, input_bytes=staged,
                score_streams=n_scores if ran else None)
    return Cs


# ---------------------------------------------------------------------------
# Vectorized single-tier models (InfHBM / SCM-only).
# ---------------------------------------------------------------------------

def _single_tier_counters(trace: Trace, cfg: HMSConfig, device):
    pre = preprocess(trace, cfg)
    ncols = pre["run_ncols"]
    is_write = pre["is_write"]
    share = (device.rcd + device.rp) / ncols + np.where(
        is_write, device.wr / ncols, 0.0
    )
    n_ph = trace.n_phases
    if n_ph > 1:
        # per-phase attribution; totals become sums of these vectors.
        # Fresh zero array per counter — these land in the public
        # SimResult.phase_counters, where aliased buffers would let an
        # in-place consumer update corrupt sibling counters.
        def red(w):
            return np.bincount(trace.phase_id,
                               weights=np.asarray(w, np.float64),
                               minlength=n_ph)
        C = {k: np.zeros(n_ph, np.float64) for k in _COUNTERS}
    else:
        def red(w):
            return float(np.sum(np.asarray(w, np.float64)))
        C = {k: 0.0 for k in _COUNTERS}
    is_dram = device.kind == "dram"
    C["demand_dram_rd" if is_dram else "demand_scm_rd"] = red(~is_write)
    C["demand_dram_wr" if is_dram else "demand_scm_wr"] = red(is_write)
    busy = red(1.0 + share)
    acts = red(1.0 / ncols)
    if is_dram:
        C["dram_busy"] = busy
        C["dram_acts"] = acts
    else:
        C["scm_busy"] = busy
        C["scm_acts"] = acts
        C["scm_wr_acts"] = red(is_write / ncols)
    return C


# ---------------------------------------------------------------------------
# Oversubscribed-HBM Unified-Memory baseline — routed through the batched
# paging engine in ``repro.um`` (the seed scan is frozen in
# ``repro.um._reference``).
# ---------------------------------------------------------------------------

def _um_overflow_config(trace: Trace, cfg: HMSConfig) -> HMSConfig | None:
    """The UM config of an HMS footprint overflow (Fig. 17's rel-footprint
    4.0 case), or ``None`` when the HMS capacity holds the trace.

    The UM model sizes frames as footprint * r_hbm, so footprint must be
    the TRACE's (cfg.footprint may be pinned at a nominal size — the
    scenario oversubscription sweep does exactly that) for the ratio to
    cancel and the resident bytes to equal the HMS capacity."""
    if trace.footprint <= cfg.scm_capacity + cfg.dram_cache_capacity:
        return None
    return dataclasses.replace(
        cfg, footprint=trace.footprint,
        r_hbm=(cfg.scm_capacity + cfg.dram_cache_capacity)
        / trace.footprint)


def _um_fault_cycles(um, cfg: HMSConfig, nvlink: bool) -> float:
    """Serialized fault-handling term: hardware-coherent links fault-stall
    nothing; the PCIe path pays the (overlapped) fault latency."""
    if nvlink:
        return 0.0
    return um.faults * cfg.fault_latency_ns / cfg.fault_overlap


# ---------------------------------------------------------------------------
# Runtime model + energy.
# ---------------------------------------------------------------------------

def _bus_cols(C: Dict[str, float]):
    dram_cols = (C["demand_dram_rd"] + C["demand_dram_wr"] + C["probe_cols"]
                 + C["meta_wr_cols"] + C["fill_dram_wr"] + C["wb_dram_rd"])
    scm_cols = (C["demand_scm_rd"] + C["demand_scm_wr"] + C["fill_scm_rd"]
                + C["wb_scm_wr"])
    return dram_cols, scm_cols


def _energy(C: Dict[str, float], cfg: HMSConfig, link_bytes: float):
    e = cfg.energy
    row_bits = 2048 * 8
    col_bits = COLUMN_BYTES * 8
    dram_cols, scm_cols = _bus_cols(C)
    dram_rd_cols = (C["demand_dram_rd"] + C["probe_cols"] + C["wb_dram_rd"])
    dram_wr_cols = (C["demand_dram_wr"] + C["meta_wr_cols"]
                    + C["fill_dram_wr"])
    scm_rd_cols = C["demand_scm_rd"] + C["fill_scm_rd"]
    scm_wr_cols = C["demand_scm_wr"] + C["wb_scm_wr"]
    out = {
        "dram_act": C["dram_acts"] * row_bits * (e.dram_act + e.dram_pre),
        "dram_rw": col_bits * (dram_rd_cols * e.dram_rd
                               + dram_wr_cols * e.dram_wr),
        "scm_act": C["scm_acts"] * row_bits * e.scm_act
        + C["scm_wr_acts"] * row_bits * e.scm_pre_wr,
        "scm_rw": col_bits * (scm_rd_cols * e.scm_rd + scm_wr_cols * e.scm_wr),
        "link": link_bytes * 8 * e.link_pj_per_bit,
    }
    return out


def _finish(name, cfg, C, link_bytes=0.0, fault_cycles=0.0,
            n_requests=1, phase_names=(), um=None) -> SimResult:
    # Split phased counters: per-phase vectors are kept verbatim and the
    # whole-trace totals are their sums (so per-phase attribution is exact
    # bit-for-bit by construction — np.sum over the same float64 vector is
    # deterministic).  UM paging counters (when the paging model ran) join
    # the same split: per-phase vectors for phased traces, floats otherwise.
    if um is not None:
        C = {**C, **um.counter_arrays()}
    phase_counters = None
    totals: Dict[str, float] = {}
    for k, v in C.items():
        a = np.asarray(v, np.float64)
        if a.ndim:
            if phase_counters is None:
                phase_counters = {}
            phase_counters[k] = a
            totals[k] = float(np.sum(a))
        else:
            totals[k] = float(a)
    C = totals
    dram_cols, scm_cols = _bus_cols(C)
    banks = cfg.channels * cfg.banks_per_channel
    if cfg.organization == "separate":
        bus = max(dram_cols, scm_cols) / max(1, cfg.channels // 2)
        dram_bank = C["dram_busy"] / (banks // 2)
        scm_bank = C["scm_busy"] / (banks // 2)
    else:
        bus = (dram_cols + scm_cols) / cfg.channels
        dram_bank = C["dram_busy"] / banks
        scm_bank = C["scm_busy"] / banks
    link_cycles = link_bytes / cfg.link_bw_gbps  # 1 GHz: GB/s == B/cycle
    compute = n_requests * cfg.compute_cycles_per_request
    terms = {
        "bus": bus,
        "dram_bank": dram_bank,
        "scm_bank": scm_bank,
        "link": link_cycles,
        "fault": fault_cycles,
        "compute": compute,
    }
    runtime = max(bus, dram_bank, scm_bank, link_cycles, compute) + fault_cycles
    traffic = {
        "dram_demand": (C["demand_dram_rd"] + C["demand_dram_wr"])
        * COLUMN_BYTES,
        "dram_probe": (C["probe_cols"] + C["meta_wr_cols"]) * COLUMN_BYTES,
        "dram_fill": C["fill_dram_wr"] * COLUMN_BYTES,
        "dram_wb_rd": C["wb_dram_rd"] * COLUMN_BYTES,
        "scm_demand": (C["demand_scm_rd"] + C["demand_scm_wr"])
        * COLUMN_BYTES,
        "scm_fill_rd": C["fill_scm_rd"] * COLUMN_BYTES,
        "scm_wb_wr": C["wb_scm_wr"] * COLUMN_BYTES,
        "link": link_bytes,
    }
    energy = _energy(C, cfg, link_bytes)
    tot_r = C["hit_r"] + C["miss_r"]
    tot_w = C["hit_w"] + C["miss_w"]
    tot_ctc = C["ctc_hit"] + C["ctc_miss"]
    tot_byp = C["bypass_l1"] + C["bypass_l2"]
    power = sum(energy.values()) / max(runtime, 1.0) * 1e-3  # pJ/ns -> W
    return SimResult(
        name=name,
        config=cfg,
        runtime_cycles=float(runtime),
        terms={k: float(v) for k, v in terms.items()},
        counters={k: float(v) for k, v in C.items()},
        traffic_bytes={k: float(v) for k, v in traffic.items()},
        hit_rate_read=float(C["hit_r"] / tot_r) if tot_r else 0.0,
        hit_rate_write=float(C["hit_w"] / tot_w) if tot_w else 0.0,
        ctc_hit_rate=float(C["ctc_hit"] / tot_ctc) if tot_ctc else 1.0,
        bypass_l1_frac=float(C["bypass_l1"] / tot_byp) if tot_byp else 0.0,
        energy_pj={k: float(v) for k, v in energy.items()},
        power_w=float(power),
        phase_names=tuple(phase_names) if phase_counters else (),
        phase_counters=phase_counters,
    )


def _finish_hms(trace: Trace, cfg: HMSConfig, C: Dict[str, float],
                nvlink: bool) -> SimResult:
    """Shared tail of the hms/separate path: optional UM overflow + finish.

    When the HMS itself is oversubscribed the UM model faults against the
    HMS capacity on top of the cache model; the paging run is memoized per
    (trace, spec) inside ``repro.um``, so a sweep that was prefetched by
    ``simulate_many`` never re-runs the scan here."""
    fault_cycles = 0.0
    link_bytes = 0.0
    um = None
    big = _um_overflow_config(trace, cfg)
    if big is not None:
        um = _um.simulate_um(trace, big, nvlink=nvlink)
        link_bytes = um.link_bytes
        fault_cycles = _um_fault_cycles(um, cfg, nvlink)
    return _finish(trace.name, cfg, C, link_bytes=link_bytes,
                   fault_cycles=fault_cycles, n_requests=trace.n,
                   phase_names=trace.phase_names, um=um)


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------

@x64_scoped
def simulate(trace: Trace, cfg: HMSConfig, nvlink: bool = False) -> SimResult:
    """Simulate ``trace`` on the memory system described by ``cfg``."""
    return _simulate(trace, cfg, nvlink, "simulate")


def _single_tier_record(entry: str, trace: Trace, cfg: HMSConfig,
                        C, wall_s: float) -> None:
    obs.record(obs.RunRecord(
        entry=entry, engine="single_tier", trace=trace.name, n=trace.n,
        phases=trace.n_phases,
        engine_key=f"single_tier:{cfg.organization}:n{trace.n}",
        compiled=False, wall_s=wall_s, batch=1,
        counter_digest=obs.counter_digest(C),
        trace_fp=_sweepckpt.trace_fingerprint(trace),
        config_digests=[_sweepckpt.config_digest(cfg)],
        counters=[_sweepckpt.encode_counters(C)],
        calib_fingerprint=costmodel.active_profile().fingerprint,
        host=obs.host_metadata(), **obs.git_info()))


def _simulate(trace: Trace, cfg: HMSConfig, nvlink: bool,
              entry: str) -> SimResult:
    cfg = cfg.validate()
    _rvalidate.validate_trace(trace)
    org = cfg.organization

    if org in ("inf_hbm", "scm", "hbm"):
        t0 = time.perf_counter()
        device = cfg.dram_timing if org != "scm" else cfg.scm_timing
        with obs.span("single_tier", organization=org, trace=trace.name):
            C = _single_tier_counters(trace, cfg, device)
        if org == "hbm":
            # Oversubscribed HBM + UM over the host link (batched engine;
            # it emits its own "um" ledger record).
            um = _um.simulate_um(trace, cfg, nvlink=nvlink)
            if obs.enabled():
                _single_tier_record(entry, trace, cfg, C,
                                    time.perf_counter() - t0)
            return _finish(trace.name, cfg, C, link_bytes=um.link_bytes,
                           fault_cycles=_um_fault_cycles(um, cfg, nvlink),
                           n_requests=trace.n,
                           phase_names=trace.phase_names, um=um)
        if obs.enabled():
            _single_tier_record(entry, trace, cfg, C,
                                time.perf_counter() - t0)
        return _finish(trace.name, cfg, C, n_requests=trace.n,
                       phase_names=trace.phase_names)

    # hms / separate
    with obs.span("preprocess", trace=trace.name):
        pre = preprocess(trace, cfg)
    C = _run_hms_scan(trace, cfg, pre, entry=entry)
    with obs.span("postprocess", trace=trace.name):
        return _finish_hms(trace, cfg, C, nvlink)


@x64_scoped
def simulate_many(trace: Trace, configs: Sequence[HMSConfig],
                  nvlink: bool = False) -> List[SimResult]:
    """Simulate one trace under many configs, batching compatible configs.

    Configs whose static structure matches (same policy and compatible
    bucketed geometry) are vmapped over their runtime parameters and run as
    one compiled, batched scan — a CTC-way sweep or tag-layout ablation
    costs one compile + one device loop over ``configs x shards``.  Every
    UM paging point the batch needs — hbm-organization configs and HMS
    footprint overflows — is prefetched through ONE batched
    ``um.simulate_um_many`` call, deduped by UM spec, so configs sharing
    (capacity, chunk, link mode) run the paging scan once for the whole
    sweep.  Where some HMS config overflows, that call runs in a
    ``um_overflow`` span and its run record counts the overflowing
    configs (``overflow_points``).  Results come back in input order and
    match sequential
    ``simulate`` counter-for-counter.
    """
    configs = [c.validate() for c in configs]
    _rvalidate.validate_trace(trace)
    results: List[SimResult | None] = [None] * len(configs)

    # resumable sweeps: journal raw engine counters per (trace, config)
    # so a killed sweep replays finished points from the checkpoint
    ck = _sweepckpt.active()
    tfp = _sweepckpt.trace_fingerprint(trace) if ck is not None else None

    um_specs = [_um.um_spec(cfg, nvlink) for cfg in configs
                if cfg.organization == "hbm"]
    overflow = [big for cfg in configs
                if cfg.organization in ("hms", "separate")
                and (big := _um_overflow_config(trace, cfg)) is not None]
    # warm the per-trace UM result cache in one vmapped engine call; the
    # per-config paths below hit the memoized results
    if overflow:
        um_specs += [_um.um_spec(big, nvlink) for big in overflow]
        with obs.span("um_overflow", points=len(overflow),
                      specs=len(set(um_specs))):
            _um.simulate_um_many(trace, um_specs,
                                 overflow_points=len(overflow))
    elif um_specs:
        _um.simulate_um_many(trace, um_specs)

    groups: Dict[tuple, List[int]] = {}
    for i, cfg in enumerate(configs):
        if cfg.organization in ("hms", "separate"):
            groups.setdefault(
                (cfg.policy, cfg.ctc_sectors_per_line), []).append(i)
        else:
            results[i] = _simulate(trace, cfg, nvlink, "simulate_many")

    for (policy, sectors), idxs in groups.items():
        if ck is not None:
            pend = []
            for i in idxs:
                hit = ck.get_hms(tfp, configs[i], nvlink)
                if hit is not None:
                    results[i] = _finish_hms(trace, configs[i], hit, nvlink)
                else:
                    pend.append(i)
            idxs = pend
            if not idxs:
                continue
        key = group_engine_key(trace, [configs[i] for i in idxs])
        if len(idxs) == 1:
            i = idxs[0]
            C = _run_hms_scan(trace, configs[i],
                              preprocess(trace, configs[i]), key,
                              entry="simulate_many")
            if ck is not None:
                ck.put_hms(tfp, configs[i], nvlink, C)
            results[i] = _finish_hms(trace, configs[i], C, nvlink)
            continue
        Cs = _run_hms_batch(trace, [configs[i] for i in idxs], key)
        with obs.span("postprocess", trace=trace.name, batch=len(idxs)):
            for j, i in enumerate(idxs):
                C = {k: np.asarray(v[j], np.float64)
                     for k, v in Cs.items()}
                if ck is not None:
                    # journal before finishing, so a kill mid-batch keeps
                    # every lane the engine already produced
                    ck.put_hms(tfp, configs[i], nvlink, C)
                results[i] = _finish_hms(trace, configs[i], C, nvlink)

    return results


def run_workload(name: str, cfg: HMSConfig, n: int | None = None,
                 nvlink: bool = False) -> SimResult:
    from .traces import make_trace

    trace = make_trace(name, n=n)
    cfg = dataclasses.replace(cfg, footprint=trace.footprint)
    return simulate(trace, cfg, nvlink=nvlink)

"""Seed (pre-batching) HMS scan engine, kept as the golden reference.

This is the original per-request ``lax.scan`` formulation that closes over a
full ``HMSConfig`` and carries every piece of statistics state (activation
counters, penalty EMA / maxima, PRNG) through the scan.  It re-traces for
every distinct config, so it is slow — but it is the semantics the batched
engine in ``simulator`` must reproduce counter-for-counter, and the parity
test in ``tests/test_engine_parity.py`` runs both on a fixed seeded trace.

Do not "optimize" this module; its value is being a frozen reference.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import bypass as bp
from . import ctc as ctc_mod
from .timing import HMSConfig
from .traces import Trace, preprocess
from .x64 import x64_scoped

_COUNTERS = (
    "demand_dram_rd", "demand_dram_wr", "demand_scm_rd", "demand_scm_wr",
    "probe_cols", "meta_wr_cols",
    "fill_scm_rd", "fill_dram_wr", "wb_dram_rd", "wb_scm_wr",
    "dram_busy", "scm_busy",
    "dram_acts", "scm_acts", "scm_wr_acts",
    "hit_r", "hit_w", "miss_r", "miss_w",
    "bypass_l1", "bypass_l2", "fills", "dirty_evicts", "aff_decs",
    "ctc_hit", "ctc_miss",
)


def _zero_counters():
    return {k: jnp.zeros((), jnp.float64) for k in _COUNTERS}


def _build_step(cfg: HMSConfig, n_pages: int):
    dram = cfg.dram_timing
    scm = cfg.scm_timing
    cpl = cfg.columns_per_line
    policy = cfg.policy
    layout = cfg.tag_layout
    use_ctc = policy in ("hms", "no_bypass", "no_second_level")
    ideal_probe = policy in ("bear", "redcache", "mccache")
    probe_cost = 1.0 if layout == "amil" else float(cfg.lines_per_row)
    meta_wr_cost = 1.0 if layout == "amil" else 0.0

    def step(carry, x):
        cache, ctcst, act, scal, C = carry
        (max_act, pen_ema, pen_max, aff_max, rng) = scal

        slot = x["slot"]
        tag = x["tag"]
        is_write = x["is_write"]
        page = x["page"]
        run_start = x["run_start"]
        ncols = x["run_ncols"]
        haswrite = x["run_haswrite"]
        excluded = x["amil_excluded"] & (layout == "amil")

        def add(name, v):
            C[name] = C[name] + jnp.asarray(v, jnp.float64)

        # -- activation counter (2 MiB-grain analogue) ---------------------
        act = act.at[page].add(run_start.astype(jnp.int32))
        page_act = act[page]
        max_act = jnp.maximum(max_act, page_act.astype(jnp.float64))

        # -- DRAM cache lookup ---------------------------------------------
        hit = cache["valid"][slot] & (cache["tags"][slot] == tag)

        # -- CTC -------------------------------------------------------------
        if use_ctc:
            c_hit, way, line_present, line_way = ctc_mod.probe(
                ctcst, x["row_group"], x["sector"], cfg.ctc_ways
            )
            add("ctc_hit", c_hit)
            add("ctc_miss", ~c_hit)
            add("probe_cols", jnp.where(c_hit, 0.0, probe_cost))
            add("dram_busy",
                jnp.where(c_hit, 0.0, dram.rcd + probe_cost + dram.rp))
            add("dram_acts", jnp.where(c_hit, 0.0, 1.0))
            new_ctc, _ = ctc_mod.fill(
                ctcst, x["row_group"], x["sector"], cfg.ctc_ways
            )
            touched = ctc_mod.touch(ctcst, x["row_group"], way)
            ctcst = jax.tree.map(
                lambda a, b: jnp.where(c_hit, a, b), touched, new_ctc
            )
        elif ideal_probe:
            c_hit = jnp.asarray(True)
        else:
            c_hit = jnp.asarray(False)
            add("ctc_miss", 1.0)
            add("probe_cols", probe_cost)
            add("dram_busy", dram.rcd + probe_cost + dram.rp)
            add("dram_acts", 1.0)

        # -- SCM penalty / affinity scores ----------------------------------
        pen = bp.scm_penalty_score(ncols, haswrite, dram, scm)
        pen_max = jnp.maximum(pen_max, pen.astype(jnp.float64))
        pen_ema = bp.ema_update(pen_ema, pen.astype(jnp.float64),
                                cfg.ema_weight)
        req_lvl = bp.discretize(pen, pen_max, cfg.n_levels)
        avg_lvl = bp.discretize(pen_ema, pen_max, cfg.n_levels)

        aff = bp.affinity_score(pen, page_act, cfg.use_activation_counter)
        aff_max = jnp.maximum(aff_max, aff.astype(jnp.float64))
        req_aff_lvl = bp.discretize(aff, aff_max, cfg.n_levels)

        victim_valid = cache["valid"][slot]
        victim_dirty = cache["dirty"][slot] & victim_valid
        victim_aff = cache["aff"][slot]

        rng = bp.xorshift32(rng)
        dice = bp.uniform01(rng)

        # -- fill / bypass decision -----------------------------------------
        miss = ~hit
        if policy in ("hms", "no_second_level"):
            pass1 = req_lvl > avg_lvl
            add("bypass_l1", miss & ~excluded & ~pass1)
            if policy == "hms":
                accept = (~victim_valid) | (req_aff_lvl > victim_aff)
                need_aff_read = miss & pass1 & ~excluded & c_hit & victim_valid
                add("probe_cols", need_aff_read)
                add("dram_busy",
                    jnp.where(need_aff_read, dram.rcd + 1.0 + dram.rp, 0.0))
                add("dram_acts", need_aff_read)
            else:
                accept = jnp.asarray(True)
            do_fill = miss & ~excluded & pass1 & accept
            rejected = miss & ~excluded & pass1 & ~accept
            add("bypass_l2", rejected)
            dec = rejected & victim_valid & (dice < bp.p_dec(page_act, max_act))
            add("aff_decs", dec)
        elif policy in ("no_bypass", "no_bypass_no_ctc", "always_cache"):
            do_fill = miss & ~excluded
            dec = jnp.asarray(False)
        elif policy == "bear":
            do_fill = miss & (dice < cfg.bear_fill_prob)
            dec = jnp.asarray(False)
        elif policy == "redcache":
            do_fill = miss & (page_act >= cfg.redcache_threshold)
            dec = jnp.asarray(False)
        elif policy == "mccache":
            do_fill = miss & ~is_write
            dec = jnp.asarray(False)
        else:
            raise ValueError(policy)

        # -- demand service ---------------------------------------------------
        mc_wt = policy == "mccache"
        dirty_ok = jnp.asarray(not mc_wt)
        rd = ~is_write
        add("hit_r", hit & rd)
        add("hit_w", hit & is_write)
        add("miss_r", miss & rd)
        add("miss_w", miss & is_write)
        add("demand_dram_rd", hit & rd)
        add("demand_dram_wr", hit & is_write)
        dram_share = (dram.rcd + dram.rp) / ncols + jnp.where(
            is_write, dram.wr / ncols, 0.0
        )
        scm_share = (scm.rcd + scm.rp) / ncols + jnp.where(
            is_write, scm.wr / ncols, 0.0
        )
        add("dram_busy", jnp.where(hit, 1.0 + dram_share, 0.0))
        add("dram_acts", jnp.where(hit, 1.0 / ncols, 0.0))
        if mc_wt:
            wt = hit & is_write
            add("demand_scm_wr", wt)
            add("scm_busy", jnp.where(wt, 1.0 + scm_share, 0.0))
            add("scm_acts", jnp.where(wt, 1.0 / ncols, 0.0))
            add("scm_wr_acts", jnp.where(wt, 1.0 / ncols, 0.0))

        dem_scm_rd = miss & rd & ~do_fill
        dem_scm_wr = miss & is_write & ~do_fill
        add("demand_scm_rd", dem_scm_rd)
        add("demand_scm_wr", dem_scm_wr)
        add("scm_busy",
            jnp.where(dem_scm_rd | dem_scm_wr, 1.0 + scm_share, 0.0))
        add("scm_acts", jnp.where(dem_scm_rd | dem_scm_wr, 1.0 / ncols, 0.0))
        add("scm_wr_acts", jnp.where(dem_scm_wr, 1.0 / ncols, 0.0))

        add("fills", do_fill)
        add("fill_scm_rd", jnp.where(do_fill, float(cpl), 0.0))
        add("fill_dram_wr", jnp.where(do_fill, float(cpl), 0.0))
        add("meta_wr_cols", jnp.where(do_fill, meta_wr_cost, 0.0))
        add("scm_busy",
            jnp.where(do_fill, scm.rcd + cpl + scm.rp, 0.0))
        add("dram_busy",
            jnp.where(do_fill, dram.rcd + cpl + dram.wr + dram.rp
                      + meta_wr_cost, 0.0))
        add("scm_acts", do_fill)
        add("dram_acts", do_fill)

        wb = do_fill & victim_dirty
        add("dirty_evicts", wb)
        add("wb_dram_rd", jnp.where(wb, float(cpl), 0.0))
        add("wb_scm_wr", jnp.where(wb, float(cpl), 0.0))
        add("dram_busy", jnp.where(wb, dram.rcd + cpl + dram.rp, 0.0))
        add("scm_busy", jnp.where(wb, scm.rcd + cpl + scm.wr + scm.rp, 0.0))
        add("dram_acts", wb)
        add("scm_acts", wb)
        add("scm_wr_acts", wb)

        # -- cache state update ----------------------------------------------
        set_dirty = (hit | do_fill) & is_write & dirty_ok
        tags = cache["tags"].at[slot].set(
            jnp.where(do_fill, tag, cache["tags"][slot]))
        valid = cache["valid"].at[slot].set(cache["valid"][slot] | do_fill)
        dirty = cache["dirty"].at[slot].set(
            jnp.where(do_fill, set_dirty,
                      cache["dirty"][slot] | (hit & is_write & dirty_ok)))
        affn = cache["aff"].at[slot].set(
            jnp.where(
                do_fill,
                req_aff_lvl,
                jnp.maximum(cache["aff"][slot] - dec.astype(jnp.int32), 0),
            )
        )
        cache = {"tags": tags, "valid": valid, "dirty": dirty, "aff": affn}

        scal = (max_act, pen_ema, pen_max, aff_max, rng)
        return (cache, ctcst, act, scal, C), None

    return step


@x64_scoped
def reference_counters(trace: Trace, cfg: HMSConfig) -> Dict[str, float]:
    """Run the seed scan engine and return its counter dict."""
    cfg = cfg.validate()
    pre = preprocess(trace, cfg)
    n_pages = int(pre["n_pages"])
    cache = {
        "tags": jnp.full((cfg.num_lines,), -1, jnp.int32),
        "valid": jnp.zeros((cfg.num_lines,), jnp.bool_),
        "dirty": jnp.zeros((cfg.num_lines,), jnp.bool_),
        "aff": jnp.zeros((cfg.num_lines,), jnp.int32),
    }
    ctcst = ctc_mod.init_state(
        cfg.ctc_sets, cfg.ctc_ways, cfg.ctc_sectors_per_line
    )
    act = jnp.zeros((n_pages,), jnp.int32)
    scal = (
        jnp.zeros((), jnp.float64),    # max_act
        jnp.zeros((), jnp.float64),    # pen_ema
        jnp.zeros((), jnp.float64),    # pen_max
        jnp.zeros((), jnp.float64),    # aff_max
        jnp.asarray(0x9E3779B9, jnp.uint32),
    )
    xs = {
        k: jnp.asarray(pre[k])
        for k in (
            "slot", "tag", "is_write", "page", "run_start", "run_ncols",
            "run_haswrite", "amil_excluded", "row_group", "sector",
        )
    }
    step = _build_step(cfg, n_pages)
    init = (cache, ctcst, act, scal, _zero_counters())
    (cache, ctcst, act, scal, C), _ = jax.lax.scan(step, init, xs)
    return {k: float(v) for k, v in C.items()}

"""Plain reference of the HMS memory system: the seed scan engine.

A copy of the program's frozen seed engine (``repro.core._reference``),
with the trace preprocessing and the runtime / energy model (``_finish``)
it feeds, kept here so that a change to the program cannot move the
reference.  One change of form: the configuration's timings, tag-layout
costs, CTC ways and CTC set count enter the compiled scan as arguments (as
weakly typed scalars, so every expression keeps the seed's dtypes)
instead of as Python constants, so a grid of design points compiles once
rather than once per point.

``fdtype`` is the floating type of the counters and of the running
statistics (penalty EMA and maxima); the seed's is float64.  The control
of the benchmark's correctness check runs this reference in float32.
"""

from __future__ import annotations

import functools
import types
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import policy as bp
from .timing import COLUMN_BYTES, COLUMNS_PER_ROW, RefConfig

COUNTERS = (
    "demand_dram_rd", "demand_dram_wr", "demand_scm_rd", "demand_scm_wr",
    "probe_cols", "meta_wr_cols",
    "fill_scm_rd", "fill_dram_wr", "wb_dram_rd", "wb_scm_wr",
    "dram_busy", "scm_busy",
    "dram_acts", "scm_acts", "scm_wr_acts",
    "hit_r", "hit_w", "miss_r", "miss_w",
    "bypass_l1", "bypass_l2", "fills", "dirty_evicts", "aff_decs",
    "ctc_hit", "ctc_miss",
)
_SEED = 0x9E3779B9


# ---------------------------------------------------------------------------
# Preprocessing: address decomposition and MSHR-window run segmentation.
# ---------------------------------------------------------------------------

def preprocess(col: np.ndarray, is_write: np.ndarray,
               cfg: RefConfig) -> Dict[str, np.ndarray]:
    col = col.astype(np.int64)
    is_write = is_write.astype(bool)
    n = col.shape[0]
    cpl = cfg.columns_per_line
    lpr = cfg.lines_per_row
    num_lines = cfg.num_lines

    line = col // cpl
    scm_row = col // COLUMNS_PER_ROW
    slot = line % num_lines
    tag = line // num_lines
    coff = col % cpl
    line_in_row = slot % lpr
    dram_row = slot // lpr
    row_group = dram_row // cfg.ctc_sectors_per_line
    sector = dram_row % cfg.ctc_sectors_per_line
    page = (col * COLUMN_BYTES) // cfg.act_page_bytes

    new_run = np.ones(n, dtype=bool)
    new_run[1:] = scm_row[1:] != scm_row[:-1]
    run_id = np.cumsum(new_run) - 1
    n_runs = int(run_id[-1]) + 1 if n else 0
    run_ncols = np.bincount(run_id, minlength=n_runs)
    run_haswrite = np.zeros(n_runs, dtype=bool)
    np.maximum.at(run_haswrite.view(np.int8), run_id, is_write.view(np.int8))
    amil_excluded = (line_in_row == lpr - 1) & (coff == cpl - 1)
    n_pages = int(page.max(initial=0)) + 1 if n else 1
    return {
        "slot": slot.astype(np.int32),
        "tag": tag.astype(np.int32),
        "is_write": is_write,
        "page": page.astype(np.int32),
        "run_start": new_run,
        "run_ncols": run_ncols[run_id].astype(np.float32),
        "run_haswrite": run_haswrite[run_id],
        "amil_excluded": amil_excluded,
        "row_group": row_group.astype(np.int32),
        "sector": sector.astype(np.int32),
        "n_pages": n_pages,
    }


# ---------------------------------------------------------------------------
# The seed scan.
# ---------------------------------------------------------------------------

def _runtime(cfg: RefConfig) -> dict:
    """The per-point scalars the compiled scan takes as arguments.  Python
    numbers, so that jit traces them weakly typed, as the seed's
    constants were."""
    dram, scm = cfg.dram_timing, cfg.scm_timing
    amil = cfg.tag_layout == "amil"
    return {
        "dram": {"rcd": dram.rcd, "wr": dram.wr, "rp": dram.rp},
        "scm": {"rcd": scm.rcd, "wr": scm.wr, "rp": scm.rp},
        "amil": amil,
        "probe_cost": 1.0 if amil else float(cfg.lines_per_row),
        "meta_wr_cost": 1.0 if amil else 0.0,
        "cpl": float(cfg.columns_per_line),
        "ctc_ways": int(cfg.ctc_ways),
        "ctc_sets": int(cfg.ctc_sets),
        "bear_fill_prob": float(cfg.bear_fill_prob),
        "redcache_threshold": int(cfg.redcache_threshold),
    }


def _static(cfg: RefConfig, n_pages: int) -> tuple:
    """What the compiled scan is specialized on."""
    return (cfg.policy, cfg.num_lines, cfg.ctc_sectors_per_line,
            cfg.n_levels, float(cfg.ema_weight),
            bool(cfg.use_activation_counter), int(n_pages))


def _build_step(static, fdt):
    policy, _, _, n_levels, ema_weight, use_counter, _ = static
    use_ctc = policy in ("hms", "no_bypass", "no_second_level")
    ideal_probe = policy in ("bear", "redcache", "mccache")

    def step(rt, carry, x):
        cache, ctcst, act, scal, C = carry
        (max_act, pen_ema, pen_max, aff_max, rng) = scal
        dram = types.SimpleNamespace(**rt["dram"])
        scm = types.SimpleNamespace(**rt["scm"])
        probe_cost = rt["probe_cost"]
        meta_wr_cost = rt["meta_wr_cost"]
        cpl = rt["cpl"]

        slot = x["slot"]
        tag = x["tag"]
        is_write = x["is_write"]
        page = x["page"]
        run_start = x["run_start"]
        ncols = x["run_ncols"]
        haswrite = x["run_haswrite"]
        excluded = x["amil_excluded"] & rt["amil"]

        def add(name, v):
            C[name] = C[name] + jnp.asarray(v, fdt)

        # activation counter
        act = act.at[page].add(run_start.astype(jnp.int32))
        page_act = act[page]
        max_act = jnp.maximum(max_act, page_act.astype(fdt))

        # the slot's cache word, read once before any update (the barrier
        # keeps XLA from re-reading the arrays after the in-place update,
        # which on the CPU costs a copy of the whole cache per step)
        old = jax.lax.optimization_barrier(
            {k: cache[k][slot] for k in ("tags", "valid", "dirty", "aff")})

        # DRAM cache lookup
        hit = old["valid"] & (old["tags"] == tag)

        # CTC
        if use_ctc:
            c_hit, way = bp.ctc_probe(ctcst, x["row_group"], x["sector"],
                                      rt["ctc_ways"], rt["ctc_sets"])
            add("ctc_hit", c_hit)
            add("ctc_miss", ~c_hit)
            add("probe_cols", jnp.where(c_hit, 0.0, probe_cost))
            add("dram_busy",
                jnp.where(c_hit, 0.0, dram.rcd + probe_cost + dram.rp))
            add("dram_acts", jnp.where(c_hit, 0.0, 1.0))
            new_ctc = bp.ctc_fill(ctcst, x["row_group"], x["sector"],
                                  rt["ctc_ways"], rt["ctc_sets"])
            touched = bp.ctc_touch(ctcst, x["row_group"], way,
                                   rt["ctc_sets"])
            ctcst = jax.tree.map(
                lambda a, b: jnp.where(c_hit, a, b), touched, new_ctc)
        elif ideal_probe:
            c_hit = jnp.asarray(True)
        else:
            c_hit = jnp.asarray(False)
            add("ctc_miss", 1.0)
            add("probe_cols", probe_cost)
            add("dram_busy", dram.rcd + probe_cost + dram.rp)
            add("dram_acts", 1.0)

        # SCM penalty / affinity scores
        pen = bp.scm_penalty_score(ncols, haswrite, dram, scm)
        pen_max = jnp.maximum(pen_max, pen.astype(fdt))
        pen_ema = bp.ema_update(pen_ema, pen.astype(fdt), ema_weight)
        req_lvl = bp.discretize(pen, pen_max, n_levels)
        avg_lvl = bp.discretize(pen_ema, pen_max, n_levels)
        aff = bp.affinity_score(pen, page_act, use_counter)
        aff_max = jnp.maximum(aff_max, aff.astype(fdt))
        req_aff_lvl = bp.discretize(aff, aff_max, n_levels)

        victim_valid = old["valid"]
        victim_dirty = old["dirty"] & victim_valid
        victim_aff = old["aff"]

        rng = bp.xorshift32(rng)
        dice = bp.uniform01(rng)

        # fill / bypass decision
        miss = ~hit
        if policy in ("hms", "no_second_level"):
            pass1 = req_lvl > avg_lvl
            add("bypass_l1", miss & ~excluded & ~pass1)
            if policy == "hms":
                accept = (~victim_valid) | (req_aff_lvl > victim_aff)
                need_aff_read = (miss & pass1 & ~excluded & c_hit
                                 & victim_valid)
                add("probe_cols", need_aff_read)
                add("dram_busy",
                    jnp.where(need_aff_read, dram.rcd + 1.0 + dram.rp, 0.0))
                add("dram_acts", need_aff_read)
            else:
                accept = jnp.asarray(True)
            do_fill = miss & ~excluded & pass1 & accept
            rejected = miss & ~excluded & pass1 & ~accept
            add("bypass_l2", rejected)
            dec = rejected & victim_valid & (dice < bp.p_dec(page_act,
                                                              max_act))
            add("aff_decs", dec)
        elif policy in ("no_bypass", "no_bypass_no_ctc", "always_cache"):
            do_fill = miss & ~excluded
            dec = jnp.asarray(False)
        elif policy == "bear":
            do_fill = miss & (dice < rt["bear_fill_prob"])
            dec = jnp.asarray(False)
        elif policy == "redcache":
            do_fill = miss & (page_act >= rt["redcache_threshold"])
            dec = jnp.asarray(False)
        elif policy == "mccache":
            do_fill = miss & ~is_write
            dec = jnp.asarray(False)
        else:
            raise ValueError(policy)

        # demand service
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
            is_write, dram.wr / ncols, 0.0)
        scm_share = (scm.rcd + scm.rp) / ncols + jnp.where(
            is_write, scm.wr / ncols, 0.0)
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
        add("fill_scm_rd", jnp.where(do_fill, cpl, 0.0))
        add("fill_dram_wr", jnp.where(do_fill, cpl, 0.0))
        add("meta_wr_cols", jnp.where(do_fill, meta_wr_cost, 0.0))
        add("scm_busy", jnp.where(do_fill, scm.rcd + cpl + scm.rp, 0.0))
        add("dram_busy",
            jnp.where(do_fill, dram.rcd + cpl + dram.wr + dram.rp
                      + meta_wr_cost, 0.0))
        add("scm_acts", do_fill)
        add("dram_acts", do_fill)

        wb = do_fill & victim_dirty
        add("dirty_evicts", wb)
        add("wb_dram_rd", jnp.where(wb, cpl, 0.0))
        add("wb_scm_wr", jnp.where(wb, cpl, 0.0))
        add("dram_busy", jnp.where(wb, dram.rcd + cpl + dram.rp, 0.0))
        add("scm_busy", jnp.where(wb, scm.rcd + cpl + scm.wr + scm.rp, 0.0))
        add("dram_acts", wb)
        add("scm_acts", wb)
        add("scm_wr_acts", wb)

        # cache state update
        set_dirty = (hit | do_fill) & is_write & dirty_ok
        tags = cache["tags"].at[slot].set(
            jnp.where(do_fill, tag, old["tags"]))
        valid = cache["valid"].at[slot].set(old["valid"] | do_fill)
        dirty = cache["dirty"].at[slot].set(
            jnp.where(do_fill, set_dirty,
                      old["dirty"] | (hit & is_write & dirty_ok)))
        affn = cache["aff"].at[slot].set(
            jnp.where(
                do_fill,
                req_aff_lvl,
                jnp.maximum(old["aff"] - dec.astype(jnp.int32), 0),
            )
        )
        cache = {"tags": tags, "valid": valid, "dirty": dirty, "aff": affn}
        scal = (max_act, pen_ema, pen_max, aff_max, rng)
        return (cache, ctcst, act, scal, C), None

    return step


@functools.lru_cache(maxsize=None)
def _compiled(static, sets_alloc, ways_alloc, fdt_name):
    fdt = jnp.dtype(fdt_name)
    step = _build_step(static, fdt)
    _, num_lines, sectors, _, _, _, n_pages = static

    @jax.jit
    def run(xs, rt):
        cache = {
            "tags": jnp.full((num_lines,), -1, jnp.int32),
            "valid": jnp.zeros((num_lines,), jnp.bool_),
            "dirty": jnp.zeros((num_lines,), jnp.bool_),
            "aff": jnp.zeros((num_lines,), jnp.int32),
        }
        scal = (jnp.zeros((), fdt), jnp.zeros((), fdt), jnp.zeros((), fdt),
                jnp.zeros((), fdt), jnp.asarray(_SEED, jnp.uint32))
        init = (cache, bp.ctc_init(sets_alloc, ways_alloc, sectors),
                jnp.zeros((n_pages,), jnp.int32), scal,
                {k: jnp.zeros((), fdt) for k in COUNTERS})
        carry, _ = jax.lax.scan(lambda c, x: step(rt, c, x), init, xs)
        return carry[-1]

    return run


_XS = ("slot", "tag", "is_write", "page", "run_start", "run_ncols",
       "run_haswrite", "amil_excluded", "row_group", "sector")


def counters(col: np.ndarray, is_write: np.ndarray,
             cfgs: Sequence[RefConfig],
             fdtype=np.float64) -> List[Dict[str, float]]:
    """The seed engine's counters for each configuration, in order."""
    fdt_name = np.dtype(fdtype).name
    with jax.enable_x64(True):
        pres: Dict[tuple, dict] = {}
        statics = []
        for cfg in cfgs:
            gk = (cfg.line_bytes, cfg.dram_cache_capacity,
                  cfg.ctc_sectors_per_line, cfg.act_page_bytes)
            if gk not in pres:
                pres[gk] = preprocess(col, is_write, cfg)
            statics.append((gk, _static(cfg, pres[gk]["n_pages"])))
        sets_alloc = max(c.ctc_sets for c in cfgs)
        ways_alloc = max(c.ctc_ways for c in cfgs)
        out = []
        for cfg, (gk, static) in zip(cfgs, statics):
            run = _compiled(static, sets_alloc, ways_alloc, fdt_name)
            xs = {k: jnp.asarray(pres[gk][k]) for k in _XS}
            C = run(xs, _runtime(cfg))
            out.append({k: float(v) for k, v in C.items()})
        return out


# ---------------------------------------------------------------------------
# Runtime (bottleneck) and energy model.
# ---------------------------------------------------------------------------

def _bus_cols(C):
    dram_cols = (C["demand_dram_rd"] + C["demand_dram_wr"] + C["probe_cols"]
                 + C["meta_wr_cols"] + C["fill_dram_wr"] + C["wb_dram_rd"])
    scm_cols = (C["demand_scm_rd"] + C["demand_scm_wr"] + C["fill_scm_rd"]
                + C["wb_scm_wr"])
    return dram_cols, scm_cols


def _energy(C, cfg: RefConfig, link_bytes: float):
    e = cfg.energy
    row_bits = 2048 * 8
    col_bits = COLUMN_BYTES * 8
    dram_rd_cols = (C["demand_dram_rd"] + C["probe_cols"] + C["wb_dram_rd"])
    dram_wr_cols = (C["demand_dram_wr"] + C["meta_wr_cols"]
                    + C["fill_dram_wr"])
    scm_rd_cols = C["demand_scm_rd"] + C["fill_scm_rd"]
    scm_wr_cols = C["demand_scm_wr"] + C["wb_scm_wr"]
    return {
        "dram_act": C["dram_acts"] * row_bits * (e.dram_act + e.dram_pre),
        "dram_rw": col_bits * (dram_rd_cols * e.dram_rd
                               + dram_wr_cols * e.dram_wr),
        "scm_act": C["scm_acts"] * row_bits * e.scm_act
        + C["scm_wr_acts"] * row_bits * e.scm_pre_wr,
        "scm_rw": col_bits * (scm_rd_cols * e.scm_rd + scm_wr_cols * e.scm_wr),
        "link": link_bytes * 8 * e.link_pj_per_bit,
    }


def finish(cfg: RefConfig, C: Dict[str, float], n_requests: int,
           link_bytes: float = 0.0, fault_cycles: float = 0.0) -> dict:
    """What a user reads off one design point: counters, the runtime with
    its bottleneck terms, traffic, hit rates, energy and power."""
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
    link_cycles = link_bytes / cfg.link_bw_gbps
    compute = n_requests * cfg.compute_cycles_per_request
    terms = {"bus": bus, "dram_bank": dram_bank, "scm_bank": scm_bank,
             "link": link_cycles, "fault": fault_cycles, "compute": compute}
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
    return {
        "runtime_cycles": float(runtime),
        "terms": {k: float(v) for k, v in terms.items()},
        "counters": {k: float(v) for k, v in C.items()},
        "traffic_bytes": {k: float(v) for k, v in traffic.items()},
        "hit_rate_read": float(C["hit_r"] / tot_r) if tot_r else 0.0,
        "hit_rate_write": float(C["hit_w"] / tot_w) if tot_w else 0.0,
        "ctc_hit_rate": float(C["ctc_hit"] / tot_ctc) if tot_ctc else 1.0,
        "bypass_l1_frac": float(C["bypass_l1"] / tot_byp) if tot_byp else 0.0,
        "energy_pj": {k: float(v) for k, v in energy.items()},
        "power_w": float(sum(energy.values()) / max(runtime, 1.0) * 1e-3),
    }

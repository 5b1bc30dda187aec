"""Bypass-policy arithmetic and the Configurable Tag Cache, as the frozen
reference scan uses them.

A copy of the seed formulation of the program's ``repro.core.bypass`` and
``repro.core.ctc`` (the unpacked probe / fill / touch), kept here so that
a change to the program cannot move the reference.
"""

from __future__ import annotations

import jax.numpy as jnp


# --- SCM-aware bypass (Sec. III-C) -------------------------------------------

def scm_penalty_score(ncols, has_write, dram, scm):
    """Eq. 1: (tRCD_scm - tRCD_dram) [+ (tWR_scm - tWR_dram)] per column."""
    ncols = jnp.maximum(jnp.asarray(ncols, dtype=jnp.float32), 1.0)
    num = (scm.rcd - dram.rcd) + jnp.asarray(has_write, jnp.float32) * (
        scm.wr - dram.wr)
    return num / ncols


def discretize(score, max_seen, n_levels):
    max_seen = jnp.maximum(jnp.asarray(max_seen, jnp.float32), 1e-6)
    lvl = jnp.floor(
        jnp.asarray(score, jnp.float32) / max_seen * n_levels
    ).astype(jnp.int32)
    return jnp.clip(lvl, 0, n_levels - 1)


def ema_update(avg, value, weight):
    return (1.0 - weight) * avg + weight * value


def affinity_score(penalty, act_count, use_counter):
    act = jnp.asarray(act_count, jnp.float32)
    return penalty * jnp.where(use_counter, jnp.maximum(act, 1.0), 1.0)


def p_dec(act_count, max_act):
    max_act = jnp.maximum(jnp.asarray(max_act, jnp.float32), 1.0)
    return jnp.clip(jnp.asarray(act_count, jnp.float32) / max_act, 0.0, 1.0)


def xorshift32(state):
    state = jnp.asarray(state, jnp.uint32)
    state = state ^ (state << jnp.uint32(13))
    state = state ^ (state >> jnp.uint32(17))
    state = state ^ (state << jnp.uint32(5))
    return state


def uniform01(state):
    return state.astype(jnp.float32) * (1.0 / 4294967296.0)


# --- Configurable Tag Cache (Sec. III-D) ---------------------------------------
#
# tags int32[sets, ways] (-1 = invalid), svalid bool[sets, ways, sectors],
# age int32[sets, ways] (0 = MRU; a permutation of 0..ways-1 per set).
# ``n_sets`` and ``enabled_ways`` may be smaller than the allocation.

def ctc_init(sets, ways, sectors):
    return {
        "tags": jnp.full((sets, ways), -1, dtype=jnp.int32),
        "svalid": jnp.zeros((sets, ways, sectors), dtype=jnp.bool_),
        "age": jnp.tile(jnp.arange(ways, dtype=jnp.int32), (sets, 1)),
    }


def _way_mask(state, enabled_ways):
    return jnp.arange(state["tags"].shape[1]) < enabled_ways


def ctc_probe(state, row_group, sector, enabled_ways, n_sets):
    set_idx = row_group % n_sets
    line_hit = (state["tags"][set_idx] == row_group) & _way_mask(
        state, enabled_ways)
    sector_hit = line_hit & state["svalid"][set_idx, :, sector]
    return jnp.any(sector_hit), jnp.argmax(sector_hit)


def ctc_touch(state, row_group, way, n_sets):
    set_idx = row_group % n_sets
    ages = state["age"][set_idx]
    my_age = ages[way]
    ages = jnp.where(ages < my_age, ages + 1, ages)
    ages = ages.at[way].set(0)
    return {**state, "age": state["age"].at[set_idx].set(ages)}


def ctc_fill(state, row_group, sector, enabled_ways, n_sets):
    set_idx = row_group % n_sets
    mask = _way_mask(state, enabled_ways)
    line_hit = (state["tags"][set_idx] == row_group) & mask
    line_present = jnp.any(line_hit)
    hit_way = jnp.argmax(line_hit)
    ages = jnp.where(mask, state["age"][set_idx], -1)
    lru_way = jnp.argmax(ages)
    way = jnp.where(line_present, hit_way, lru_way)
    tags = state["tags"].at[set_idx, way].set(row_group)
    svalid_set = state["svalid"][set_idx]
    svalid_set = jnp.where(
        line_present,
        svalid_set,
        svalid_set.at[way].set(jnp.zeros_like(svalid_set[way])),
    )
    svalid_set = svalid_set.at[way, sector].set(True)
    svalid = state["svalid"].at[set_idx].set(svalid_set)
    new = {"tags": tags, "svalid": svalid, "age": state["age"]}
    return ctc_touch(new, row_group, way, n_sets)

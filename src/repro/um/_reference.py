"""Seed (pre-batching) Unified-Memory paging scan, kept as the golden
reference.

This is the original ``_run_um`` formulation from ``repro.core.simulator``:
a per-request ``lax.scan`` that closes over the page count, frame count,
migration chunk and link mode as Python-level constants — so it re-traces
for every distinct (trace, capacity, chunk, nvlink) point and runs one
config at a time.  It is slow, but it is the semantics the batched engine
in ``repro.um.engine`` must reproduce counter-for-counter, and
``tests/test_um_engine.py`` pins the engine to it on every output.

Do not "optimize" this module; its value is being a frozen reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timing import COLUMN_BYTES, UM_PAGE_BYTES, HMSConfig
from repro.core.traces import Trace
from repro.core.x64 import x64_scoped


@x64_scoped
def run_um_reference(trace: Trace, cfg: HMSConfig, nvlink: bool = False):
    """Page-granular UM simulation: FIFO frames + TBN-style chunk migration.

    Returns (faults, migrated_pages, writeback_pages, remote_cols).
    """
    page = (trace.col * COLUMN_BYTES) // UM_PAGE_BYTES
    is_write = trace.is_write
    n_pages = int(page.max(initial=0)) + 1
    n_frames = max(1, cfg.hbm_capacity // UM_PAGE_BYTES)
    chunk = cfg.um_prefetch_pages

    if n_frames >= n_pages:
        return 0, 0, 0, 0

    page_j = jnp.asarray(page.astype(np.int32))
    wr_j = jnp.asarray(is_write)

    def step(carry, x):
        resident, dirty, frames, ptr, f, mig, wb, rem, hotness = carry
        p, w = x
        hotness = hotness.at[p].add(1)
        is_res = resident[p]

        if nvlink:
            # Access-counter migration: cold pages are accessed remotely in
            # cacheline granularity; pages crossing the hotness threshold
            # migrate (no fault stall on hardware-coherent links).
            migrate = (~is_res) & (hotness[p] >= 4)
            remote = (~is_res) & ~migrate
            rem = rem + remote
            mchunk = 1
            fault = migrate
        else:
            fault = ~is_res
            migrate = fault
            mchunk = chunk
            remote = jnp.asarray(False)

        f = f + fault

        def do_migrate(args):
            resident, dirty, frames, ptr, mig, wb = args
            base = (p // mchunk) * mchunk
            idx = base + jnp.arange(mchunk, dtype=jnp.int32)
            idx = jnp.clip(idx, 0, n_pages - 1).astype(jnp.int32)
            newly = ~resident[idx]
            mig_n = jnp.sum(newly)
            # Evict as many frames as we bring in.  CLOCK-flavoured: scan a
            # window of 4x chunk candidates from the hand and prefer cold
            # (low-hotness) victims, approximating UM's pre-eviction policy
            # (plain FIFO thrashes hot pages and wildly over-penalizes
            # oversubscription relative to the paper's measurements).
            window = 4 * mchunk
            cand_idx = (ptr + jnp.arange(window, dtype=jnp.int32)) % n_frames
            cand_pages = frames[cand_idx]
            cand_hot = jnp.where(cand_pages >= 0,
                                 hotness[jnp.maximum(cand_pages, 0)], 0)
            order = jnp.argsort(cand_hot)           # coldest first
            ev_slot = cand_idx[order[:mchunk]]
            ev_pages = frames[ev_slot]
            ev_valid = (ev_pages >= 0) & newly      # evict one per new page
            wb_n = jnp.sum(jnp.where(ev_valid, dirty[ev_pages], False))
            # Only the evicted pages are written.  A clipped chunk at the
            # end of the page range lists the last page more than once, so
            # it can sit in several frames and be both an evicted victim
            # and one that is not; writing the latter back with its old
            # value undid the eviction in whatever order the scatter took.
            ev_pg = jnp.where(ev_valid, ev_pages, n_pages)
            resident = resident.at[ev_pg].set(False, mode="drop")
            dirty = dirty.at[ev_pg].set(False, mode="drop")
            resident = resident.at[idx].set(True)
            frames = frames.at[ev_slot].set(jnp.where(newly, idx, ev_pages))
            ptr2 = ((ptr + mig_n) % n_frames).astype(jnp.int32)
            return resident, dirty, frames, ptr2, mig + mig_n, wb + wb_n

        resident, dirty, frames, ptr, mig, wb = jax.lax.cond(
            migrate,
            do_migrate,
            lambda a: a,
            (resident, dirty, frames, ptr, mig, wb),
        )
        dirty = dirty.at[p].set(dirty[p] | (w & resident[p]))
        return (resident, dirty, frames, ptr, f, mig, wb, rem, hotness), None

    init = (
        jnp.zeros((n_pages,), jnp.bool_),
        jnp.zeros((n_pages,), jnp.bool_),
        jnp.full((n_frames,), -1, jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int64),
        jnp.zeros((), jnp.int64),
        jnp.zeros((), jnp.int64),
        jnp.zeros((), jnp.int64),
        jnp.zeros((n_pages,), jnp.int32),
    )
    (res, dirty, frames, ptr, f, mig, wb, rem, hot), _ = jax.lax.scan(
        step, init, (page_j, wr_j)
    )
    return int(f), int(mig), int(wb), int(rem)

"""Plain reference of the Unified-Memory paging baseline: the seed scan.

A copy of the program's frozen sequential paging scan
(``repro.um._reference.run_um_reference``), kept here so that a change to
the program cannot move the reference.  It closes over the page count,
frame count, migration chunk and link mode, so it compiles once per
design point.

One step is made well defined where the seed's was not.  When the page
count is not a multiple of the migration chunk, a chunk at the end is
clipped to the last page and lists it more than once, so one page can
sit in several frames.  Two of the eviction window's victims can then be
the same page, one evicted and one not; the seed wrote the one not
evicted back with its old value, and which write the scatter kept was
left to its order.  Here a victim that is not evicted writes nothing, as
the program's engine does.  The clipped chunk itself is the seed's: it
counts the repeated page once per lane.

``hot_dtype`` is the integer type of the per-page access counters; the
seed's is int32.  The control of the benchmark's correctness check runs
this reference with 8-bit counters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .timing import COLUMN_BYTES, UM_PAGE_BYTES


def counters(col: np.ndarray, is_write: np.ndarray, n_frames: int,
             chunk: int, nvlink: bool, hot_threshold: int = 4,
             hot_dtype=np.int32):
    """Page-granular UM simulation: FIFO frames + TBN-style chunk migration.

    Returns (faults, migrated_pages, writeback_pages, remote_cols)."""
    page = (col * COLUMN_BYTES) // UM_PAGE_BYTES
    n_pages = int(page.max(initial=0)) + 1
    if n_frames >= n_pages:
        return 0, 0, 0, 0
    with jax.enable_x64(True):
        return _run(page, is_write, n_pages, max(1, n_frames), chunk,
                    nvlink, hot_threshold, jnp.dtype(hot_dtype))


def _run(page, is_write, n_pages, n_frames, chunk, nvlink, hot_threshold,
         hot_dtype):
    page_j = jnp.asarray(page.astype(np.int32))
    wr_j = jnp.asarray(is_write)

    def step(carry, x):
        resident, dirty, frames, ptr, f, mig, wb, rem, hotness = carry
        p, w = x
        hotness = hotness.at[p].add(1)
        is_res = resident[p]

        if nvlink:
            # access-counter migration: cold pages are accessed remotely
            # at cacheline granularity; hot pages migrate, no fault stall
            migrate = (~is_res) & (hotness[p] >= hot_threshold)
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
            # evict as many frames as come in: a window of 4x chunk
            # candidates from the hand, coldest (least accessed) first
            window = 4 * mchunk
            cand_idx = (ptr + jnp.arange(window, dtype=jnp.int32)) % n_frames
            cand_pages = frames[cand_idx]
            cand_hot = jnp.where(cand_pages >= 0,
                                 hotness[jnp.maximum(cand_pages, 0)], 0)
            order = jnp.argsort(cand_hot)
            ev_slot = cand_idx[order[:mchunk]]
            ev_pages = frames[ev_slot]
            ev_valid = (ev_pages >= 0) & newly
            wb_n = jnp.sum(jnp.where(ev_valid, dirty[ev_pages], False))
            # only the evicted pages are written: a victim that is not
            # evicted writes nothing (the seed wrote its old value back,
            # which undid the eviction of the same page from another
            # frame in an order the scatter leaves undefined)
            ev_pg = jnp.where(ev_valid, ev_pages, n_pages)
            resident = resident.at[ev_pg].set(False, mode="drop")
            dirty = dirty.at[ev_pg].set(False, mode="drop")
            resident = resident.at[idx].set(True)
            frames = frames.at[ev_slot].set(jnp.where(newly, idx, ev_pages))
            ptr2 = ((ptr + mig_n) % n_frames).astype(jnp.int32)
            return resident, dirty, frames, ptr2, mig + mig_n, wb + wb_n

        resident, dirty, frames, ptr, mig, wb = jax.lax.cond(
            migrate, do_migrate, lambda a: a,
            (resident, dirty, frames, ptr, mig, wb))
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
        jnp.zeros((n_pages,), hot_dtype),
    )
    (_, _, _, _, f, mig, wb, rem, _), _ = jax.lax.scan(
        step, init, (page_j, wr_j))
    return int(f), int(mig), int(wb), int(rem)

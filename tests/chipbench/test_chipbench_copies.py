"""The benchmark's copies of the trace generator and of the frozen
references give what the program's own give today."""

from __future__ import annotations

import numpy as np
import pytest

import _chipbench_util as u
from chipbench import harness
from chipbench.reference import hms as ref_hms
from chipbench.reference import um as ref_um
from chipbench.reference.timing import UM_PAGE_BYTES, RefConfig

N = 1500


def _traffic(cell):
    return harness.load_cell(cell).traffic


@pytest.mark.parametrize("seed", [10, 3, 2**31 + 11])
def test_generator_copy_matches_program(seed):
    from repro.core.traces import gen_bfs

    p = _traffic(u.HMS)["params"]
    gen = harness.load_module("generators", _traffic(u.HMS)["generator"])
    col, wr = gen.gen_bfs(seed, p["footprint"], N, p["write_frac"],
                          p["burst"], p["alpha"])
    prog = gen_bfs(footprint=p["footprint"], n=N, seed=seed,
                   burst=p["burst"], write_frac=p["write_frac"])
    np.testing.assert_array_equal(col, prog.col)
    np.testing.assert_array_equal(wr, prog.is_write)
    assert col.dtype == np.int64 and wr.dtype == np.bool_


def test_every_seed_orders_the_registered_bfs_tu():
    """Each seed gives the visits and write flags of the program's
    registered ``bfs_tu`` trace, in an order of its own."""
    from repro.core.traces import WORKLOADS

    t = _traffic(u.HMS)
    p = {**t["params"], "n": N}
    gen = harness.load_module("generators", t["generator"])
    prog = WORKLOADS["bfs_tu"](n=N)
    assert prog.footprint == p["footprint"]
    def visits(col):
        v = col.reshape(-1, p["burst"])
        return v[np.lexsort(v.T[::-1])]

    runs = [gen.generate(s, **p) for s in (1, 2, 2**31 + 3)]
    for col, wr in runs:
        np.testing.assert_array_equal(visits(col), visits(prog.col))
        assert wr.sum() == prog.is_write.sum()
    assert not np.array_equal(runs[0][0], runs[1][0])
    again = gen.generate(2, **p)
    np.testing.assert_array_equal(again[0], runs[1][0])
    np.testing.assert_array_equal(again[1], runs[1][1])


def _trace(seed=5):
    from repro.core.traces import Trace

    t = _traffic(u.HMS)
    gen = harness.load_module("generators", t["generator"])
    col, wr = gen.generate(seed, **{**t["params"], "n": N})
    return col, wr, Trace("bfs_tu", col, wr, t["params"]["footprint"])


@pytest.mark.parametrize("point", [
    {"tag_layout": "amil", "ctc_fraction": 0.25, "scm_mode": "mlc"},
    {"tag_layout": "tad", "ctc_fraction": 0.0625, "scm_mode": "slc"},
    {"tag_layout": "amil", "ctc_fraction": 0.0625, "scm_mode": "tlc"},
])
def test_hms_reference_copy_matches_frozen_reference(point):
    """One compile for the whole grid, the frozen engine's counters."""
    from repro.core import HMSConfig
    from repro.core._reference import reference_counters

    col, wr, trace = _trace()
    base = harness.load_cell(u.HMS).config["base"]
    grid = [{**base, **p} for p in harness.load_cell(u.HMS).points]
    mine = ref_hms.counters(
        col, wr, [RefConfig(**g, footprint=trace.footprint) for g in grid])
    i = grid.index({**base, **point})
    frozen = reference_counters(
        trace, HMSConfig(**{**base, **point}, footprint=trace.footprint))
    assert mine[i] == frozen


def test_hms_reference_finish_matches_program():
    """The copied runtime / energy model gives the program's SimResult
    values, bit for bit, on the same counters."""
    col, wr, trace = _trace(6)
    cell = harness.load_cell(u.HMS)
    entry = harness.load_module("entries", cell.config["entry"])
    ref = entry.reference(col, wr, trace.footprint, cell.config["base"],
                          cell.points)
    got = entry.answers(entry.study(trace, cell.config["base"], cell.points))
    assert got == ref
    assert len(ref[0]) > 50


@pytest.mark.parametrize("nvlink", [False, True])
@pytest.mark.parametrize("rel", [1.25, 4.0])
def test_um_reference_copy_matches_frozen_reference(nvlink, rel):
    from repro.core import HMSConfig
    from repro.um._reference import run_um_reference

    col, wr, trace = _trace(7)
    cfg = HMSConfig(footprint=trace.footprint, organization="hbm",
                    r_hbm=1.0 / rel)
    frozen = run_um_reference(trace, cfg, nvlink=nvlink)
    mine = ref_um.counters(col, wr, max(1, cfg.hbm_capacity // UM_PAGE_BYTES),
                           1 if nvlink else cfg.um_prefetch_pages, nvlink)
    assert mine == tuple(frozen)
    assert sum(mine) > 0


# 34 pages (not a multiple of the 4-page chunk) in 16 frames: the chunk
# at the end is clipped to page 33, which then sits in several frames; at
# one fault two victims are page 33, one evicted and one not
_CLIPPED_PAGES = [
    33, 2, 21, 15, 18, 12, 15, 7, 27, 11, 2, 6, 7, 22, 6, 29, 9, 18, 14, 4,
    18, 31, 17, 17, 24, 28, 27, 18, 23, 7, 30, 25, 24, 26, 14, 23, 19, 8,
    33, 18, 7, 9, 31, 25, 1, 8, 8, 5, 7, 15, 9, 3, 7, 25, 18]
_CLIPPED_WRITES = "1111100001110111110010010101011111111101100100110001000"


def _paging_by_hand(page, wr, n_pages, n_frames, chunk):
    """The paging scan, one request at a time, writing the resident and
    dirty bits of the evicted victims only."""
    res = np.zeros(n_pages, bool)
    dirty = np.zeros(n_pages, bool)
    hot = np.zeros(n_pages, np.int64)
    frames = np.full(n_frames, -1)
    ptr = faults = mig = wb = 0
    for p, w in zip(page, wr):
        hot[p] += 1
        if not res[p]:
            faults += 1
            idx = np.minimum((p // chunk) * chunk + np.arange(chunk),
                             n_pages - 1)
            newly = ~res[idx]
            cand = (ptr + np.arange(4 * chunk)) % n_frames
            cand_hot = np.where(frames[cand] >= 0,
                                hot[np.maximum(frames[cand], 0)], 0)
            ev_slot = cand[np.argsort(cand_hot, kind="stable")[:chunk]]
            ev_pages = frames[ev_slot]
            evicted = ev_pages[(ev_pages >= 0) & newly]
            wb += int(dirty[evicted].sum())
            res[evicted] = False
            dirty[evicted] = False
            res[idx] = True
            frames[ev_slot] = np.where(newly, idx, ev_pages)
            ptr = (ptr + int(newly.sum())) % n_frames
            mig += int(newly.sum())
        dirty[p] |= w & res[p]
    return faults, mig, wb, 0


def test_um_reference_clipped_chunk_is_well_defined():
    """Where a victim that is not evicted is the same page as one that
    is, the reference writes nothing for it: it agrees with the paging
    worked by hand and with the program's engine."""
    from repro import um
    from repro.core import HMSConfig
    from repro.core.traces import Trace

    page = np.asarray(_CLIPPED_PAGES, np.int64)
    wr = np.asarray([c == "1" for c in _CLIPPED_WRITES])
    col = page * (UM_PAGE_BYTES // 32)
    mine = ref_um.counters(col, wr, 16, 4, False)
    assert mine == _paging_by_hand(page, wr, 34, 16, 4) == (29, 95, 14, 0)
    fp = 34 * UM_PAGE_BYTES
    cfg = HMSConfig(footprint=fp, organization="hbm", r_hbm=16 / 34)
    (r,) = um.simulate_um_many(Trace("clipped", col, wr, fp),
                               [um.um_spec(cfg, nvlink=False)])
    assert (r.faults, r.migrated, r.writebacks, r.remote_cols) == mine

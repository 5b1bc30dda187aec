"""An HMS stack that the footprint oversubscribes, through ``simulate_many``.

The grid is the benchmark's: ``r_hbm`` {0.75, 0.5, 0.375, 0.25} x SCM cell
mode, over PCIe.  The stack holds the DRAM cache (0.5 r F) plus the SCM
({1, 2, 3} r F in SLC, MLC, TLC), so 6 of the 12 points overflow and page
the excess in by Unified Memory.  The contracts under test:

  * every answer of every point equals per-point ``simulate`` and the
    frozen HMS scan composed with the frozen paging scan, bit for bit,
  * exactly the 6 expected points overflow, and one prefetching UM call
    of 6 lanes serves them, its record counting ``overflow_points`` 6,
  * that call runs in a ``um_overflow`` span with its arguments, and a
    grid that fits opens none,
  * ``scm_mode="auto"`` equals the explicit mode it resolves to.
"""

import dataclasses

import pytest

from repro import obs
from repro.core import HMSConfig, simulate, simulate_many
from repro.core import simulator as sim_mod
from repro.core._reference import reference_counters
from repro.core.timing import COLUMN_BYTES, UM_PAGE_BYTES
from repro.core.traces import Trace, gen_bfs
from repro.um._reference import run_um_reference

MiB = 1 << 20
N = 10000
SEEDS = (1, 2)
BASE = dict(organization="hms", policy="hms", tag_layout="amil",
            ctc_fraction=0.25, dram_ratio=0.5, line_bytes=256,
            act_page_bytes=64 * 1024, um_prefetch_pages=4)
R_HBM = (0.75, 0.5, 0.375, 0.25)
MODES = ("slc", "mlc", "tlc")
GRID = [(r, m) for r in R_HBM for m in MODES]
OVERFLOWING = {(0.5, "slc"), (0.375, "slc"), (0.375, "mlc"),
               (0.25, "slc"), (0.25, "mlc"), (0.25, "tlc")}
# what "auto" resolves to: the fastest mode whose SCM alone holds the
# footprint (SCM = 2 r F in MLC)
AUTO = {0.75: "mlc", 0.5: "mlc", 0.375: "tlc", 0.25: "tlc"}


def _trace(seed):
    return gen_bfs(footprint=2 * MiB, n=N, seed=seed, name="bfs_tu")


def _configs(trace, grid=GRID, **kw):
    return [HMSConfig(**{**BASE, **kw}, r_hbm=r, scm_mode=m,
                      footprint=trace.footprint) for r, m in grid]


def _answers(r):
    """Everything a user reads off a ``SimResult``."""
    return (r.counters, r.runtime_cycles, r.terms, r.traffic_bytes,
            r.hit_rate_read, r.hit_rate_write, r.ctc_hit_rate,
            r.bypass_l1_frac, r.energy_pj, r.power_w)


def _composed_reference(trace, cfg):
    """The frozen HMS scan's counters, and where the footprint exceeds the
    stack (DRAM cache + SCM in its cell mode) the frozen paging scan's
    over PCIe, into as many 4 KiB frames as the stack holds."""
    C = reference_counters(trace, cfg)
    link_bytes = fault_cycles = 0.0
    cap = cfg.scm_capacity + cfg.dram_cache_capacity
    if trace.footprint > cap:
        big = dataclasses.replace(cfg, r_hbm=cap / trace.footprint)
        f, mig, wb, rem = (float(v) for v in run_um_reference(trace, big))
        C = {**C, "um_faults": f, "um_migrated": mig, "um_writebacks": wb,
             "um_remote_cols": rem}
        link_bytes = (mig + wb) * UM_PAGE_BYTES + rem * COLUMN_BYTES
        fault_cycles = f * cfg.fault_latency_ns / cfg.fault_overlap
    return sim_mod._finish(trace.name, cfg, C, link_bytes=link_bytes,
                           fault_cycles=fault_cycles, n_requests=trace.n)


@pytest.fixture(scope="module", params=SEEDS)
def study(request):
    """One ``simulate_many`` over the grid, with its records and spans."""
    trace = _trace(request.param)
    obs.enable()
    try:
        results = simulate_many(trace, _configs(trace))
        records, events = obs.records(), obs.events()
    finally:
        obs.disable()
        obs.clear_records()
        obs.clear_events()
    return trace, results, records, events


def test_many_equals_per_point_simulate_and_the_composed_references(study):
    trace, results, _, _ = study
    # a Trace of its own, so the paging runs again rather than memoized
    again = Trace(trace.name, trace.col, trace.is_write, trace.footprint)
    for cfg, got in zip(_configs(trace), results):
        assert _answers(got) == _answers(simulate(again, cfg)), cfg
        assert _answers(got) == _answers(_composed_reference(trace, cfg)), \
            cfg


def test_exactly_the_expected_points_overflow(study):
    _, results, _, _ = study
    over = {pt for pt, r in zip(GRID, results) if "um_faults" in r.counters}
    assert over == OVERFLOWING
    for pt, r in zip(GRID, results):
        paged = pt in OVERFLOWING
        assert (r.terms["fault"] > 0) == paged, pt
        assert (r.traffic_bytes["link"] > 0) == paged, pt


def test_one_prefetching_um_call_serves_the_overflowing_points(study):
    _, _, records, _ = study
    (rec,) = [r for r in records if r.overflow_points is not None]
    assert rec.engine == "um" and rec.ladder_rung == "T1"
    assert rec.overflow_points == 6
    assert rec.um_lanes_requested == rec.um_lanes_run == 6
    # the per-point finish hits the memoized results
    assert [r.engine for r in records if r.ladder_rung is not None] == [
        "um", "hms"]


def test_the_prefetch_runs_in_a_um_overflow_span(study):
    _, _, _, events = study
    (span,) = [e for e in events if e[0] == "um_overflow"]
    assert span[4] == {"points": 6, "specs": 6}
    (scan,) = [e for e in events if e[0] == "um_scan"]
    assert span[1] <= scan[1] and scan[1] + scan[2] <= span[1] + span[2]


def test_a_grid_that_fits_opens_no_overflow_span():
    """The sweep cell's grid at r_hbm 0.75: the stack holds the footprint
    at every point."""
    trace = _trace(SEEDS[0])
    cfgs = [HMSConfig(**{**BASE, "tag_layout": t, "ctc_fraction": f},
                      scm_mode=m, r_hbm=0.75, footprint=trace.footprint)
            for t in ("amil", "tad") for f in (0.25, 0.0625) for m in MODES]
    obs.enable()
    try:
        results = simulate_many(trace, cfgs)
        records, events = obs.records(), obs.events()
    finally:
        obs.disable()
        obs.clear_records()
        obs.clear_events()
    assert not any("um_faults" in r.counters for r in results)
    assert not any(e[0] == "um_overflow" for e in events)
    assert all(r.overflow_points is None for r in records)
    assert not any(r.engine == "um" for r in records)


def test_auto_mode_equals_the_mode_it_resolves_to(study):
    trace, results, _, _ = study
    auto = _configs(trace, [(r, "auto") for r in R_HBM])
    assert [c.effective_scm_mode for c in auto] == [AUTO[r] for r in R_HBM]
    got = simulate_many(trace, auto)
    for r, res in zip(R_HBM, got):
        want = results[GRID.index((r, AUTO[r]))]
        assert _answers(res) == _answers(want), r

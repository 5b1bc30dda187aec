"""The oversubscribed HMS cell and the single-point cell: they load, read
``correct`` on the CPU, their controls do not, and the overflow metrics
read the program's span and counter."""

from __future__ import annotations

import io
import time
import types

import numpy as np
import pytest

import _chipbench_util as u
from chipbench import harness
from chipbench.reference import oversub
from chipbench.reference.timing import RefConfig

OVERSUB = "hms_oversub.grid12.bfs_tu"
POINT = "hms.point.bfs_tu"
# a test run's sizes: at 2 MiB the 10 000 requests touch every page, so the
# overflowing points evict and the frames the paging holds move the answers
SMALL = {OVERSUB: {"n": 10000, "footprint": 2 << 20}, POINT: {"n": 20000}}
OVERFLOWING = {(0.5, "slc"), (0.375, "slc"), (0.375, "mlc"),
               (0.25, "slc"), (0.25, "mlc"), (0.25, "tlc")}


def _run(cell, *, trace=False, seed=20251017):
    import jax

    from repro.core import costmodel

    out, err = io.StringIO(), io.StringIO()
    old = costmodel.set_calib_mode("off")
    try:
        result = harness.run_cell(
            cell, seed, 0.05, trace, t_start_ns=time.perf_counter_ns(),
            root=u.ROOT, out=out, err=err, devices=jax.devices("cpu")[:1],
            traffic_params=SMALL[cell])
    finally:
        costmodel.set_calib_mode(old)
    return result, out.getvalue()


def test_grids_are_the_issued_design_points():
    grid = harness.load_cell(OVERSUB).points
    assert len(grid) == 12
    assert {(p["r_hbm"], p["scm_mode"]) for p in grid} == {
        (r, m) for r in (0.75, 0.5, 0.375, 0.25)
        for m in ("slc", "mlc", "tlc")}
    assert harness.load_cell(POINT).points == [{}]


def test_the_copied_overflow_rule_is_the_programs():
    """Which points overflow, and the frames their paging holds, at the
    cell's size."""
    from repro import um
    from repro.core import HMSConfig
    from repro.core.simulator import _um_overflow_config
    from repro.core.traces import Trace

    cell = harness.load_cell(OVERSUB)
    fp = cell.footprint
    trace = Trace("t", np.zeros(1, np.int64), np.zeros(1, bool), fp)
    over = set()
    for pt in cell.points:
        g = {**cell.config["base"], **pt}
        big = _um_overflow_config(trace, HMSConfig(**g, footprint=fp))
        ref = RefConfig(**g, footprint=fp)
        assert (big is not None) == (fp > oversub.capacity(ref)), pt
        if big is not None:
            over.add((pt["r_hbm"], pt["scm_mode"]))
            spec = um.um_spec(big, nvlink=False)
            assert spec.n_frames == oversub.um_frames(ref)
            assert spec.chunk == ref.um_prefetch_pages
            assert oversub.um_frames(ref, control=True) < spec.n_frames
    assert over == OVERFLOWING


@pytest.mark.parametrize("cell", [OVERSUB, POINT])
def test_untraced_run_is_correct(cell):
    r, out = _run(cell)
    assert r["correct"] is True, out
    assert r["failed"] == 0
    assert r["attempted"] % len(harness.load_cell(cell).points) == 0
    assert r["checks"]["values_differing"] == {"value": 0, "limit": 0}
    assert "compiles_in_window=0" in out


def test_oversubscribed_answers_carry_the_paging_counters():
    c = harness.load_cell(OVERSUB)
    entry = harness.load_module("entries", c.config["entry"])
    gen = harness.load_module("generators", c.traffic["generator"])
    p = {**c.traffic["params"], **SMALL[OVERSUB]}
    col, wr = gen.generate(3, **p)
    ref = entry.reference(col, wr, p["footprint"], c.config["base"], c.points)
    for pt, a in zip(c.points, ref):
        assert len(a) == 55
        over = (pt["r_hbm"], pt["scm_mode"]) in OVERFLOWING
        assert (a["um_faults"] > 0) == over, pt
        assert (a["terms.fault"] > 0) == over
        assert (a["traffic_bytes.link"] > 0) == over


def _frames_by_hbm(monkeypatch):
    real = oversub.um_frames
    monkeypatch.setattr(oversub, "um_frames",
                        lambda cfg, control=False: real(cfg, True))


def _no_paging(monkeypatch):
    monkeypatch.setattr(
        oversub, "paging", lambda col, wr, cfg, control=False: (
            dict.fromkeys(oversub.UM_KEYS, 0.0), 0.0, 0.0))


@pytest.mark.parametrize("fault", [_frames_by_hbm, _no_paging],
                         ids=["frames_by_hbm", "no_um_terms"])
def test_reference_with_a_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    assert _run(OVERSUB)[0]["correct"] is False


def test_frames_control_is_not_correct_on_three_seeds():
    c = harness.load_cell(OVERSUB)
    entry = harness.load_module("entries", c.config["entry"])
    gen = harness.load_module("generators", c.traffic["generator"])
    p = {**c.traffic["params"], **SMALL[OVERSUB]}
    for seed in (1, 2, 3):
        col, wr = gen.generate(seed, **p)
        args = (col, wr, p["footprint"], c.config["base"], c.points)
        ref = entry.reference(*args)
        ctl = entry.reference(*args, control=True)
        _, differing, _ = harness.compare([harness.Study(0, 0, ctl)], ref)
        assert differing > harness.LIMITS["values_differing"], seed


def test_traced_run_reads_the_overflow_metrics():
    r, out = _run(OVERSUB, trace=True)
    assert r["correct"] is True, out
    m = r["metrics"]
    assert m["overflow_points_per_study"]["value"] == 6
    assert 0 < m["um_overflow_share"]["value"] < 100
    assert "engine=um planned_S=None planned_T=1 depth=None batch=6" in out


def _ctx(spans=(), records=(), studies=2):
    return harness.Context(cell=None, window_s=1.0, studies=[None] * studies,
                           spans=list(spans), records=list(records))


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def test_um_overflow_share_reads_the_spans():
    spans = [("um_overflow", 0, 300_000_000), ("scan", 0, 900_000_000),
             ("um_overflow", 10**9, 10**9 + 200_000_000)]
    assert _read("um_overflow_share", _ctx(spans)) == pytest.approx(50.0)
    assert _read("um_overflow_share", _ctx(spans[1:2])) is None


def _rec(**kw):
    return types.SimpleNamespace(ladder_rung="T1", **kw)


@pytest.mark.parametrize("recs,studies,want", [
    ([_rec(overflow_points=6), _rec(overflow_points=None)], 1, 6.0),
    ([_rec(overflow_points=6)] * 3, 3, 6.0),
    ([_rec(overflow_points=6), _rec(overflow_points=2)], 2, 4.0),
    ([_rec(), _rec()], 2, None),                        # the parent's records
    ([_rec(overflow_points=None)], 1, None),            # nothing overflowed
    ([], 1, None),
])
def test_overflow_points_per_study_reads_the_records(recs, studies, want):
    got = _read("overflow_points_per_study", _ctx(records=recs,
                                                  studies=studies))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)

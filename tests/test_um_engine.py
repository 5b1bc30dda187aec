"""Golden parity + batching guarantees of the UM paging engine.

The batched engine in ``repro.um`` must reproduce the frozen sequential
reference (``repro.um._reference``) on all four outputs — faults, migrated
pages, writeback pages, remote columns — in both link modes, run a whole
rel-footprint sweep through ONE compiled engine entry, dedupe identical
sweep points, and attribute every counter per phase with per-phase sums
equal to the whole-trace totals float64-bit-for-bit.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from repro import obs, um
from repro.core import HMSConfig, costmodel, make_trace, simulate, \
    simulate_many, tsplit
from repro.core.simulator import _um_overflow_config
from repro.core.timing import COLUMN_BYTES, UM_PAGE_BYTES
from repro.core.traces import Trace
from repro.um import engine as um_engine
from repro.um._reference import run_um_reference
from repro.workloads import SCENARIOS

UM_KEYS = ("um_faults", "um_migrated", "um_writebacks", "um_remote_cols")


def _um_trace(n=6000, footprint=8 * 2**20, seed=5):
    """Zipf-hot mix with writes: hot pages should stay resident, the cold
    tail should churn frames — exercises migration, eviction and
    writebacks."""
    rng = np.random.default_rng(seed)
    total = footprint // COLUMN_BYTES
    hot = total // 16
    is_hot = rng.random(n) < 0.6
    col = np.where(is_hot,
                   rng.integers(0, hot, size=n),
                   rng.integers(hot, total, size=n)).astype(np.int64)
    # a streaming tail so faults cluster per phase-less region too
    col[2 * n // 3:] = (np.arange(n - 2 * n // 3, dtype=np.int64)
                        * 7) % total
    wr = rng.random(n) < 0.3
    return Trace("um_golden", col, wr, footprint)


def _totals(r: um.UMResult):
    return (r.faults, r.migrated, r.writebacks, r.remote_cols)


# ---------------------------------------------------------------------------
# Frozen-reference parity.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r_hbm,chunk", [(0.3, 4), (0.6, 1), (0.85, 8)],
                         ids=["deep_oversub", "unchunked", "shallow_chunk8"])
def test_reference_parity_fault_mode(r_hbm, chunk):
    """Fault-driven chunked migration matches the frozen scan exactly."""
    t = _um_trace()
    cfg = HMSConfig(footprint=t.footprint, r_hbm=r_hbm,
                    um_prefetch_pages=chunk, organization="hbm")
    ref = run_um_reference(t, cfg, nvlink=False)
    got = _totals(um.simulate_um(t, cfg, nvlink=False))
    assert got == tuple(float(x) for x in ref)
    assert got[0] > 0 and got[1] > 0      # the case actually paged


@pytest.mark.parametrize("r_hbm", [0.3, 0.7], ids=["deep", "shallow"])
def test_reference_parity_nvlink(r_hbm):
    """Access-counter migration + remote cacheline accesses match the
    frozen scan exactly (including the remote-column count)."""
    t = _um_trace()
    cfg = HMSConfig(footprint=t.footprint, r_hbm=r_hbm, organization="hbm")
    ref = run_um_reference(t, cfg, nvlink=True)
    got = _totals(um.simulate_um(t, cfg, nvlink=True))
    assert got == tuple(float(x) for x in ref)
    assert got[3] > 0                      # remote traffic flowed


# 34 pages (not a multiple of the 4-page chunk) in 16 frames: a fault near
# the end migrates the chunk clipped to [32, 33, 33, 33], so page 33 sits
# in several frames, and at one fault two victims are page 33, one evicted
# and one not.  Every duplicate of a page must carry one final page word.
_CLIPPED_PAGES = [
    33, 2, 21, 15, 18, 12, 15, 7, 27, 11, 2, 6, 7, 22, 6, 29, 9, 18, 14, 4,
    18, 31, 17, 17, 24, 28, 27, 18, 23, 7, 30, 25, 24, 26, 14, 23, 19, 8,
    33, 18, 7, 9, 31, 25, 1, 8, 8, 5, 7, 15, 9, 3, 7, 25, 18]
_CLIPPED_WRITES = "1111100001110111110010010101011111111101100100110001000"


@pytest.mark.parametrize("t_seg", [1, 4], ids=["T1", "T4"])
@pytest.mark.parametrize("nvlink", [False, True], ids=["fault", "nvlink"])
def test_clipped_chunk_duplicate_pages_match_reference(nvlink, t_seg):
    """A page listed several times in one step's touched set (the clipped
    chunk's last page, as a migrated page and as evicted and kept victims)
    gets the frozen scan's residency and dirtiness, at T=1 and split."""
    page = np.asarray(_CLIPPED_PAGES, np.int64)
    wr = np.asarray([c == "1" for c in _CLIPPED_WRITES])
    fp = 34 * UM_PAGE_BYTES
    t = Trace("clipped", page * (UM_PAGE_BYTES // COLUMN_BYTES), wr, fp)
    cfg = HMSConfig(footprint=fp, organization="hbm", r_hbm=16 / 34)
    spec = um.um_spec(cfg, nvlink=nvlink)
    assert spec.n_frames == 16
    ref = run_um_reference(t, cfg, nvlink=nvlink)
    old_t = costmodel.set_forced_tsplit(t_seg)
    old_r = tsplit.set_replay_prefix(0)
    try:
        got = _totals(um.simulate_um_many(t, [spec])[0])
    finally:
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)
    assert got == tuple(float(x) for x in ref)
    assert got[1] > 0                      # the case actually migrated
    if not nvlink:
        assert got == (29.0, 95.0, 14.0, 0.0)


def _hlo_computations(text):
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return comps


def _scatter_shapes(comps, name):
    """Result shapes of every scatter run by one pass of computation
    ``name``, following its calls (a call repeated is counted again)."""
    out = []
    for line in comps[name]:
        m = re.search(r"= (\w+\[[\d,]*\])\S* scatter\(", line)
        if m:
            out.append(m.group(1))
        for callee in re.findall(r" call\(.*to_apply=%?([\w.\-]+)", line):
            out += _scatter_shapes(comps, callee)
    return out


def test_paging_step_keeps_one_page_word_per_lane():
    """The T=1 engine's loop carries one int32 page word array per lane
    (resident | dirty << 1) and no boolean page arrays, keeps the shared
    hotness unbatched, and writes per-lane state with at most two
    scatters a step: the page words and the victims' frames."""
    n, lanes, unroll = 64, 2, 4
    key = um_engine._UMKey(n=n, pages_alloc=32, frames_alloc=16,
                           chunk_alloc=4, phases=1)
    xs = {"page": np.zeros(n, np.int32), "is_write": np.zeros(n, bool),
          "phase": np.zeros(n, np.int32)}
    p = {"n_pages": np.full(lanes, 30, np.int32),
         "n_frames": np.full(lanes, 16, np.int32),
         "chunk": np.full(lanes, 4, np.int32),
         "nvlink": np.zeros(lanes, bool),
         "hot_thresh": np.zeros(lanes, np.int32)}
    with jax.enable_x64(True):
        text = um_engine._engine_for(key).lower(xs, p).as_text(dialect="hlo")
    (loop,) = [ln for ln in text.splitlines() if " while(" in ln]
    carry = loop.split(" while(")[0]
    assert carry.count("s32[2,33]") == 1            # page words, dump slot
    assert "pred[2,33]" not in carry                # no resident / dirty
    assert carry.count("s32[2,17]") == 1            # frames
    assert re.search(r"s32\[32\]\S*[,)]", carry)    # hotness, unbatched
    body = re.search(r"body=%?([\w.\-]+)", loop).group(1)
    shapes = _scatter_shapes(_hlo_computations(text), body)
    lane_scatters = [s for s in shapes if re.match(rf"\w+\[{lanes},", s)]
    assert sorted(set(lane_scatters)) == ["s32[2,17]", "s32[2,33]"]
    assert len(lane_scatters) / unroll <= 2
    assert set(shapes) - set(lane_scatters) == {"s32[32]"}  # hotness += 1


def test_early_out_when_frames_cover_pages():
    """n_frames >= n_pages: zero counters, no engine lane executed."""
    t = _um_trace()
    cfg = HMSConfig(footprint=t.footprint, r_hbm=1.5, organization="hbm")
    before = obs.cache_stats()["um_lanes_run"]
    r = um.simulate_um(t, cfg)
    assert _totals(r) == (0.0, 0.0, 0.0, 0.0)
    assert obs.cache_stats()["um_lanes_run"] == before
    assert run_um_reference(t, cfg) == (0, 0, 0, 0)


def test_um_outputs_are_exact_integers():
    t = _um_trace()
    r = um.simulate_um(t, HMSConfig(footprint=t.footprint, r_hbm=0.5,
                                    organization="hbm"))
    for v in _totals(r):
        assert v == int(v)


# ---------------------------------------------------------------------------
# Compile-once batching.
# ---------------------------------------------------------------------------

def test_rel_footprint_sweep_is_one_engine_entry():
    """A rel-footprint x link-mode grid runs as ONE compiled, vmapped scan
    (one engine-cache entry, traced once) and equals per-spec sequential
    runs counter-for-counter."""
    t = _um_trace()
    specs = [um.um_spec(HMSConfig(footprint=t.footprint, r_hbm=1.0 / rel),
                        nvlink=nv)
             for rel in (1.25, 1.5, 2.0, 4.0) for nv in (False, True)]
    obs.reset(hms=False)
    batched = um.simulate_um_many(t, specs)
    assert obs.cache_stats()["um_engines"] == 1
    assert um.um_engine_trace_count(um.um_group_key(t, specs)) == 1
    obs.reset(hms=False)
    for s, rb in zip(specs, batched):
        rs = um.simulate_um_many(t, [s])[0]
        assert _totals(rb) == _totals(rs), s
        np.testing.assert_array_equal(rb.phase_faults, rs.phase_faults)


def test_runtime_scalar_resweep_never_retraces():
    """A second sweep with different capacities but the same bucketed
    allocations and batch width reuses the compiled engine (runtime
    scalars only; jit re-specializes per batch width like the HMS
    engine's batched variant)."""
    t = _um_trace()
    obs.reset(hms=False)
    specs_a = [um.um_spec(HMSConfig(footprint=t.footprint, r_hbm=r))
               for r in (0.50, 0.55, 0.60)]
    um.simulate_um_many(t, specs_a)
    key = um.um_group_key(t, specs_a)
    warm = um.um_engine_trace_count(key)
    specs_b = [um.um_spec(HMSConfig(footprint=t.footprint, r_hbm=r,
                                    um_prefetch_pages=c))
               for r, c in ((0.52, 4), (0.58, 2), (0.61, 3))]
    assert um.um_group_key(t, specs_b) == key
    with obs.assert_no_retrace():      # same fingerprint, warm at entry
        um.simulate_um_many(t, specs_b)
    assert um.um_engine_trace_count(key) == warm, "re-sweep re-traced"


def test_simulate_many_dedupes_identical_um_points():
    """hbm-org configs sharing (capacity, chunk, nvlink) run the paging
    scan once for the whole batch; distinct points add one lane each."""
    t = _um_trace(seed=9)
    kw = dict(footprint=t.footprint, organization="hbm")
    cfgs = [HMSConfig(r_hbm=0.5, **kw),
            HMSConfig(r_hbm=0.5, scm_mode="slc", **kw),   # same UM spec
            HMSConfig(r_hbm=0.4, **kw)]
    before = obs.cache_stats()["um_lanes_run"]
    rs = simulate_many(t, cfgs)
    assert obs.cache_stats()["um_lanes_run"] - before == 2
    for k in UM_KEYS:
        assert rs[0].counters[k] == rs[1].counters[k]
    # the memoized point is also shared by later sequential calls
    before = obs.cache_stats()["um_lanes_run"]
    r_seq = simulate(t, cfgs[0])
    assert obs.cache_stats()["um_lanes_run"] == before
    assert r_seq.counters["um_faults"] == rs[0].counters["um_faults"]


# ---------------------------------------------------------------------------
# Per-phase attribution.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_phase_um_sums_equal_totals(scenario):
    """Every registered scenario, oversubscribed hbm organization: the
    per-phase UM counter sums equal the whole-trace totals bit-for-bit,
    and phase_summary gains consistent UM columns."""
    t = make_trace(scenario, n=5000)
    r = simulate(t, HMSConfig(footprint=t.footprint, organization="hbm",
                              r_hbm=0.5))
    assert r.counters["um_faults"] > 0, "case never paged — dead test"
    for k in UM_KEYS:
        assert k in r.phase_counters
        assert r.phase_counters[k].shape == (t.n_phases,)
        assert float(np.sum(r.phase_counters[k])) == r.counters[k], (
            f"{scenario}: phase sums drifted on {k}")
    s = r.phase_summary()
    assert all("um_faults" in p for p in s.values())
    assert sum(p["um_link_bytes"] for p in s.values()) == pytest.approx(
        r.traffic_bytes["link"])


def test_phased_totals_match_reference():
    """Phase-segmented reduction must not change whole-trace UM semantics:
    totals still equal the frozen (phase-blind) reference scan."""
    t = make_trace("moe_expert", n=5000)
    cfg = HMSConfig(footprint=t.footprint, organization="hbm", r_hbm=0.5)
    ref = run_um_reference(t, cfg)
    r = simulate(t, cfg)
    assert (r.counters["um_faults"], r.counters["um_migrated"],
            r.counters["um_writebacks"],
            r.counters["um_remote_cols"]) == tuple(float(x) for x in ref)


def test_overflow_path_uses_um_engine_and_reports_phases():
    """HMS footprint overflow (oversub > capacity) routes through the
    engine: UM counters appear, match the frozen reference on the derived
    overflow config, and feed the fault/link runtime terms."""
    t = SCENARIOS["llm_serve"].compile(n=5000, oversub=4.0)
    cfg = HMSConfig(footprint=t.footprint // 4)   # pinned nominal capacity
    big = _um_overflow_config(t, cfg)
    assert big is not None
    ref = run_um_reference(t, big)
    r = simulate(t, cfg)
    assert r.counters["um_faults"] == float(ref[0]) > 0
    for k in UM_KEYS:
        assert float(np.sum(r.phase_counters[k])) == r.counters[k], k
    assert r.terms["fault"] == (ref[0] * cfg.fault_latency_ns
                                / cfg.fault_overlap)
    assert r.traffic_bytes["link"] == ((ref[1] + ref[2]) * UM_PAGE_BYTES
                                       + ref[3] * COLUMN_BYTES)
    # within-capacity runs carry no UM counters at all
    r_fit = simulate(SCENARIOS["llm_serve"].compile(n=5000),
                     HMSConfig(footprint=t.footprint // 4))
    assert "um_faults" not in r_fit.counters


def test_unphased_traces_keep_scalar_um_counters():
    t = _um_trace()
    r = simulate(t, HMSConfig(footprint=t.footprint, organization="hbm",
                              r_hbm=0.5))
    assert r.phase_counters is None
    assert r.counters["um_faults"] > 0


def test_nvlink_fault_term_is_zero():
    """Hardware-coherent links pay link occupancy, not fault stalls."""
    t = _um_trace()
    cfg = HMSConfig(footprint=t.footprint, organization="hbm", r_hbm=0.4)
    r = simulate(t, cfg, nvlink=True)
    assert r.terms["fault"] == 0.0
    assert r.counters["um_remote_cols"] > 0
    assert r.traffic_bytes["link"] > 0


# ---------------------------------------------------------------------------
# Temporal splitting: the paging scan's only depth lever.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nvlink", [False, True], ids=["fault", "nvlink"])
@pytest.mark.parametrize("t_seg,replay", [(4, 0), (8, 32)],
                         ids=["T4", "T8r32"])
def test_temporal_split_parity_vs_reference_um(nvlink, t_seg, replay):
    """A temporally split UM run (gauge-canonical frame-ring stitch, exact
    hotness boundaries) matches the frozen sequential scan on all four
    outputs in both link modes."""
    t = _um_trace()
    cfg = HMSConfig(footprint=t.footprint, r_hbm=0.4, organization="hbm")
    ref = run_um_reference(t, cfg, nvlink=nvlink)
    old_t = costmodel.set_forced_tsplit(t_seg)
    old_r = tsplit.set_replay_prefix(replay)
    try:
        key = um.um_group_key(t, [um.um_spec(cfg, nvlink=nvlink)],
                              t_segments=t_seg, replay=replay)
        assert key.t_segments == t_seg and key.replay == replay
        got = _totals(um.simulate_um(t, cfg, nvlink=nvlink))
    finally:
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)
    assert got == tuple(float(x) for x in ref)
    assert (got[0] > 0) or (got[3] > 0)       # the case actually paged


def test_temporal_split_phase_attribution_exact():
    """Per-phase UM vectors at T=4 equal the unsplit vectors bit-for-bit
    on a phased scenario trace (flattened segment-sum keeps trace order)."""
    t1 = make_trace("moe_expert", n=5000)
    t2 = make_trace("moe_expert", n=5000)
    cfg = HMSConfig(footprint=t1.footprint, organization="hbm", r_hbm=0.5)
    spec = um.um_spec(cfg)
    old_t = costmodel.set_forced_tsplit(1)
    try:
        base = um.simulate_um_many(t1, [spec])[0]
    finally:
        costmodel.set_forced_tsplit(old_t)
    old_t = costmodel.set_forced_tsplit(4)
    try:
        got = um.simulate_um_many(t2, [spec])[0]
    finally:
        costmodel.set_forced_tsplit(old_t)
    assert base.faults > 0
    for f in ("phase_faults", "phase_migrated", "phase_writebacks",
              "phase_remote_cols"):
        np.testing.assert_array_equal(getattr(got, f), getattr(base, f), f)


def test_hot_threshold_is_runtime_data():
    """Sweeping the nvlink migration threshold reuses the compiled engine
    and monotonically trades migrations for remote accesses."""
    t = _um_trace()
    base = HMSConfig(footprint=t.footprint, organization="hbm", r_hbm=0.4)
    specs = [um.um_spec(dataclasses.replace(base, um_hot_threshold=h),
                        nvlink=True) for h in (2, 4, 16)]
    obs.reset(hms=False, keep_compiled=True)
    rs = um.simulate_um_many(t, specs)
    migs = [r.migrated for r in rs]
    rems = [r.remote_cols for r in rs]
    assert migs[0] >= migs[1] >= migs[2]
    assert rems[0] <= rems[1] <= rems[2]

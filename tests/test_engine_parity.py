"""Golden parity + compile-once guarantees of the batched HMS engine.

The batched engine in ``repro.core.simulator`` must reproduce the seed
engine (frozen in ``repro.core._reference``) counter-for-counter, compile
exactly once across runtime-scalar sweeps, and give identical results
whether configs run sequentially or vmapped through ``simulate_many``.
"""

import dataclasses
import re

import numpy as np
import pytest

from repro import obs
from repro.core import HMSConfig, costmodel, simulate, simulate_many, tsplit
from repro.core._reference import reference_counters
from repro.core.simulator import (_COUNTERS, engine_trace_count,
                                  group_engine_key)
from repro.core.traces import Trace


def _golden_trace(n=6000, footprint=4 * 2**20, seed=7):
    """Fixed seeded mix of random and streaming requests with writes."""
    rng = np.random.default_rng(seed)
    total = footprint // 32
    col = np.concatenate([
        rng.integers(0, total, size=n // 2),
        (rng.integers(0, total, size=1)[0] + np.arange(n - n // 2)) % total,
    ]).astype(np.int64)
    wr = rng.random(n) < 0.3
    return Trace("golden", col, wr, footprint)


GOLDEN_CONFIGS = [
    {},                                        # full HMS, AMIL
    {"tag_layout": "tad"},
    {"policy": "no_bypass"},
    {"policy": "no_second_level", "n_levels": 8},
    {"policy": "bear", "scm_mode": "slc"},
    {"policy": "mccache"},
    {"policy": "redcache"},
    {"policy": "no_bypass_no_ctc", "throttle_wr": True},
]


@pytest.mark.parametrize(
    "kw", GOLDEN_CONFIGS,
    ids=["hms", "tad", "no_bypass", "no_2nd", "bear", "mccache",
         "redcache", "no_ctc"])
def test_golden_parity_vs_reference(kw):
    """Every counter of the batched engine matches the seed scan engine."""
    t = _golden_trace()
    cfg = HMSConfig(footprint=t.footprint, **kw)
    ref = reference_counters(t, cfg)
    new = simulate(t, cfg).counters
    assert set(ref) == set(_COUNTERS) == set(new)
    for k in _COUNTERS:
        np.testing.assert_allclose(new[k], ref[k], rtol=1e-9, atol=1e-6,
                                   err_msg=f"counter {k} diverged for {kw}")


def test_runtime_scalar_sweep_compiles_once():
    """Configs differing only in runtime scalars share one compiled engine."""
    t = _golden_trace()
    base = HMSConfig(footprint=t.footprint).validate()
    key = group_engine_key(t, [base])
    simulate(t, base)
    warm = engine_trace_count(key)
    assert warm >= 1
    sweeps = (
        {"scm_mode": "slc"},
        {"scm_mode": "tlc"},
        {"ema_weight": 0.05},
        {"n_levels": 8},
        {"tag_layout": "tad"},
        {"throttle_act": True, "throttle_wr": True},
        {"use_activation_counter": True},
        {"organization": "separate"},
    )
    with obs.assert_no_retrace():      # key is warm at entry
        for kw in sweeps:
            cfg = dataclasses.replace(base, **kw).validate()
            assert group_engine_key(t, [cfg]) == key, (
                f"{kw} changed the static key")
            simulate(t, cfg)
    assert engine_trace_count(key) == warm, (
        "runtime-scalar sweep re-traced the engine")


def test_simulate_and_a_batch_of_one_share_one_engine():
    """``simulate`` runs its config as ``simulate_many`` runs a group of
    one: the same compiled engine, the same record shape, equal counters."""
    t = _golden_trace(seed=29)
    cfg = HMSConfig(footprint=t.footprint).validate()
    key = group_engine_key(t, [cfg])
    obs.reset(um=False)
    obs.clear_records()
    obs.enable()
    try:
        single = simulate(t, cfg)
        (many,) = simulate_many(t, [cfg])
        recs = [r for r in obs.records() if r.engine == "hms"]
    finally:
        obs.disable()
        obs.clear_records()
    assert engine_trace_count(key) == 1
    assert obs.cache_stats()["hms_engines"] == 1
    assert [(r.entry, r.batch) for r in recs] == [("simulate", 1),
                                                   ("simulate_many", 1)]
    assert recs[0].engine_key == recs[1].engine_key
    assert recs[0].engine_key.endswith(":w1")
    assert recs[0].counter_digest == recs[1].counter_digest
    assert set(single.counters) == set(many.counters) == set(_COUNTERS)
    for k in _COUNTERS:
        np.testing.assert_array_equal(many.counters[k], single.counters[k],
                                      err_msg=k)


@pytest.mark.parametrize("t_seg", [1, 2], ids=["T1", "T2"])
def test_batch_of_one_scans_without_a_lane_axis(t_seg):
    """A width-1 call loops over the same state as the engine body run
    alone: no size-1 lane axis in the scan's carry."""
    import jax

    from repro.core import ctc
    from repro.core import simulator as sim
    from repro.core.traces import preprocess

    t = _golden_trace(seed=31)
    cfg = HMSConfig(footprint=t.footprint).validate()
    old_s = costmodel.set_forced_shards(2)
    old_t = costmodel.set_forced_tsplit(t_seg)
    try:
        key = group_engine_key(t, [cfg])
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
    assert key.t_segments == t_seg
    with jax.enable_x64(True):
        pre = preprocess(t, cfg)
        rs = sim._pack_stream(t, cfg, pre, sim._score_stream(t, cfg, pre))
        args = [sim._engine_inputs(t, cfg, pre, rs, key),
                sim._runtime_params(cfg, sim._local_sets(t, cfg, key))]
        if t_seg > 1:
            lanes = key.shards * key.t_segments
            row = np.asarray(ctc.packed_init(
                key.ctc_sets_alloc, key.ctc_ways_alloc, key.ctc_sectors))
            args += [(np.zeros((lanes, key.lines_alloc), np.int32),
                      np.broadcast_to(row, (lanes,) + row.shape).copy()),
                     np.bool_(False)]
        one = [jax.tree_util.tree_map(lambda x: np.stack([x]), arg)
               for arg in args[:3]] + args[3:]

        def loops(lowered):
            text = lowered.as_text(dialect="hlo")
            return re.findall(r"= (\(.*?\)) while\(", text)

        alone = loops(jax.jit(sim._make_engine(key)).lower(*args))
        width1 = loops(sim._engine_for(key).lower(*one))
    assert alone and width1 == alone


def _engine_loops(t, cfgs, shards):
    """The ``while`` tuples of the lowered engine at a forced (S, T=1)."""
    import jax

    from repro.core import simulator as sim
    from repro.core.traces import preprocess

    old_s = costmodel.set_forced_shards(shards)
    old_t = costmodel.set_forced_tsplit(1)
    try:
        key = group_engine_key(t, cfgs)
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
    assert (key.shards, key.t_segments) == (shards, 1)
    lanes = []
    with jax.enable_x64(True):
        for cfg in cfgs:
            pre = preprocess(t, cfg)
            rs = sim._pack_stream(t, cfg, pre, sim._score_stream(t, cfg, pre))
            lanes.append((sim._engine_inputs(t, cfg, pre, rs, key),
                          sim._runtime_params(cfg,
                                              sim._local_sets(t, cfg, key))))
        args = jax.tree_util.tree_map(lambda *x: np.stack(x), *lanes)
        text = sim._engine_for(key).lower(*args).as_text(dialect="hlo")
    return re.findall(r"= (\(.*?\)) while\(", text)


@pytest.mark.parametrize("shards", [1, 2], ids=["S1", "S2"])
def test_single_shard_scans_without_a_shard_axis(shards):
    """A 3-lane call at one shard loops over (lanes, ...) state and streams:
    no size-1 shard axis in the scan's carry.  At two shards the shard
    axis stays."""
    t = _aliasing_trace()
    cfgs = [HMSConfig(footprint=t.footprint, scm_mode=m).validate()
            for m in ("slc", "mlc", "tlc")]
    loops = _engine_loops(t, cfgs, shards)
    assert loops
    if shards == 1:
        assert not any("[3,1," in w for w in loops), loops
    else:
        assert all("[3,2," in w for w in loops), loops


@pytest.mark.parametrize("width", [1, 3], ids=["w1", "w3"])
@pytest.mark.parametrize(
    "kw", GOLDEN_CONFIGS,
    ids=["hms", "tad", "no_bypass", "no_2nd", "bear", "mccache",
         "redcache", "no_ctc"])
def test_single_shard_parity_vs_reference(kw, width):
    """At a forced single shard (S = 1, T = 1), the scan run without a shard
    axis reproduces the seed scan engine for every golden policy, alone
    and in a batch of three."""
    t = _golden_trace()
    cfg = HMSConfig(footprint=t.footprint, **kw)
    cfgs = [cfg] if width == 1 else [
        dataclasses.replace(cfg, scm_mode=m) for m in ("slc", "mlc", "tlc")]
    old_s = costmodel.set_forced_shards(1)
    old_t = costmodel.set_forced_tsplit(1)
    try:
        key = group_engine_key(t, cfgs)
        assert (key.shards, key.t_segments) == (1, 1)
        got = simulate_many(t, cfgs)
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
    for c, r in zip(cfgs, got):
        ref = reference_counters(t, c)
        for k in _COUNTERS:
            np.testing.assert_array_equal(
                r.counters[k], ref[k],
                err_msg=f"counter {k} diverged for {kw}, {c.scm_mode}")


def test_simulate_many_matches_sequential():
    """Batched vmap execution reproduces per-config sequential counters."""
    t = _golden_trace()
    kws = [
        {},
        {"scm_mode": "slc"},
        {"tag_layout": "tad"},
        {"ctc_fraction": 0.125},          # different CTC sets, same batch
        {"ema_weight": 0.05},
        {"policy": "bear"},               # different static structure
        {"organization": "scm"},          # non-scan path
    ]
    cfgs = [HMSConfig(footprint=t.footprint, **kw) for kw in kws]
    batched = simulate_many(t, cfgs)
    assert len(batched) == len(cfgs)
    for kw, cfg, rb in zip(kws, cfgs, batched):
        rs = simulate(t, cfg)
        for k in _COUNTERS:
            np.testing.assert_allclose(
                rb.counters[k], rs.counters[k], rtol=1e-9, atol=1e-6,
                err_msg=f"simulate_many diverged on {k} for {kw}")
        assert rb.config.policy == cfg.policy


def _aliasing_trace(n=4000, footprint=64 * 2**20, seed=11, hot_slots=96):
    """Many tags aliasing onto few DRAM-cache slots: random requests over the
    full footprint interleaved with a hot stream hammering a small slot
    range — the conflict-heavy case where any shard-order bug would surface
    as different fill/evict decisions."""
    rng = np.random.default_rng(seed)
    total = footprint // 32
    hot = rng.integers(0, hot_slots * 8, size=n // 2)      # few slots
    cold = rng.integers(0, total, size=n - n // 2)         # full tag space
    col = np.empty(n, dtype=np.int64)
    col[0::2] = hot
    col[1::2] = cold
    wr = rng.random(n) < 0.4
    return Trace("alias", col, wr, footprint)


@pytest.mark.parametrize("kw", [{}, {"policy": "no_bypass"},
                                {"policy": "mccache"}],
                         ids=["hms", "no_bypass", "mccache"])
def test_shard_parallel_parity_vs_reference(kw):
    """The shard-parallel engine must reproduce the seed scan engine exactly
    on a trace that aliases many tags onto few slots.  The shard count is
    pinned (S=4) so the test stays a shard-parallel test regardless of how
    the host-tuned cost model would choose."""
    t = _aliasing_trace()
    # small r_hbm -> small DRAM cache -> deep tag aliasing, and a CTC with
    # multiple sets so the hms policy distributes across shards too
    cfg = HMSConfig(footprint=t.footprint, r_hbm=0.1, **kw)
    old = costmodel.set_forced_shards(4)
    try:
        key = group_engine_key(t, [cfg])
        assert key.shards == 4
        new = simulate(t, cfg).counters
    finally:
        costmodel.set_forced_shards(old)
    ref = reference_counters(t, cfg)
    for k in _COUNTERS:
        np.testing.assert_allclose(new[k], ref[k], rtol=1e-9, atol=1e-6,
                                   err_msg=f"counter {k} diverged for {kw}")


def test_shard_engine_matches_sequential_scan():
    """Pinned shard-parallel execution == forced S=1 sequential scan,
    counter for counter, on a real (zipf-skewed) workload trace."""
    from repro.core import make_trace

    t = make_trace("bfs_tu", n=30_000)
    cfg = HMSConfig(footprint=t.footprint)
    old = costmodel.set_forced_shards(8)
    try:
        assert group_engine_key(t, [cfg]).shards == 8
        sharded = simulate(t, cfg).counters
    finally:
        costmodel.set_forced_shards(old)
    old_cap = costmodel.set_max_shards(1)
    try:
        assert group_engine_key(t, [cfg]).shards == 1
        seq = simulate(t, cfg).counters
    finally:
        costmodel.set_max_shards(old_cap)
    for k in _COUNTERS:
        np.testing.assert_allclose(sharded[k], seq[k], rtol=1e-12, atol=0,
                                   err_msg=f"shard-parallel diverged on {k}")


# ---------------------------------------------------------------------------
# Temporal splitting: every (S, T) execution shape is the same simulator.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,t_seg,replay",
                         [(1, 4, 0), (4, 2, 0), (2, 4, 16), (1, 8, 32)],
                         ids=["T4", "S4T2", "S2T4r16", "T8r32"])
def test_temporal_split_parity_vs_reference(s, t_seg, replay):
    """Temporally split execution (with and without spatial shards and
    replay prefixes) reproduces the seed scan engine on the conflict-heavy
    aliasing trace, counter for counter."""
    t = _aliasing_trace()
    cfg = HMSConfig(footprint=t.footprint, r_hbm=0.1)
    ref = reference_counters(t, cfg)
    old_s = costmodel.set_forced_shards(s)
    old_t = costmodel.set_forced_tsplit(t_seg)
    old_r = tsplit.set_replay_prefix(replay)
    try:
        key = group_engine_key(t, [cfg])
        assert key.shards == s and key.t_segments == t_seg
        new = simulate(t, cfg).counters
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)
    for k in _COUNTERS:
        np.testing.assert_allclose(
            new[k], ref[k], rtol=1e-9, atol=1e-6,
            err_msg=f"counter {k} diverged at S={s} T={t_seg}")


@pytest.mark.parametrize(
    "kw", GOLDEN_CONFIGS,
    ids=["hms", "tad", "no_bypass", "no_2nd", "bear", "mccache",
         "redcache", "no_ctc"])
def test_temporal_split_matches_unsplit(kw):
    """Stitched (S=2, T=4, replay) execution is bit-for-bit the unsplit
    (S=1, T=1) scan for every golden policy — not approximately: the
    stitch only terminates at an exact fixed point."""
    t = _golden_trace()
    cfg = HMSConfig(footprint=t.footprint, **kw)
    old_s = costmodel.set_forced_shards(1)
    old_t = costmodel.set_forced_tsplit(1)
    try:
        base = simulate(t, cfg).counters
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
    old_s = costmodel.set_forced_shards(2)
    old_t = costmodel.set_forced_tsplit(4)
    old_r = tsplit.set_replay_prefix(16)
    try:
        got = simulate(t, cfg).counters
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)
    for k in _COUNTERS:
        np.testing.assert_array_equal(got[k], base[k], err_msg=f"{kw}: {k}")


def test_event_counters_are_exact_integers():
    """Pure event counts must come out as exact whole numbers."""
    t = _golden_trace()
    r = simulate(t, HMSConfig(footprint=t.footprint))
    for k in ("hit_r", "hit_w", "miss_r", "miss_w", "fills", "dirty_evicts",
              "bypass_l1", "bypass_l2", "ctc_hit", "ctc_miss", "aff_decs"):
        assert r.counters[k] == int(r.counters[k]), k
    assert (r.counters["hit_r"] + r.counters["miss_r"]
            + r.counters["hit_w"] + r.counters["miss_w"]) == t.n

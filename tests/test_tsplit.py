"""Temporal trace splitting: planner, stitch loop, and end-to-end parity.

Three layers of ``repro.core.tsplit`` under test:

* the index planner (``split_positions``) — pure shape/invariant units;
* the fixed-point ``stitch`` loop — driven by a toy exactly-composable
  system, including the non-convergence guard and both engines' fallback
  to T=1 when the guard fires;
* both engines end to end — a property: ANY (S, T, replay) split of a
  random phased trace reproduces the unsplit counters bit-for-bit within
  the stitch round bound, across every cache policy and both UM link
  modes.  Runs under hypothesis when the library is present, else over a
  fixed seed battery exercising the same generator.
"""

import contextlib

import numpy as np
import pytest

from repro import obs, um
from repro.core import HMSConfig, costmodel, simulate, tsplit
from repro.core.traces import Trace
from repro.um.engine import _page_stream

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # container ships without hypothesis
    HAVE_HYPOTHESIS = False

SEEDS = [0, 1, 2, 3, 4, 5]

POLICY_KWS = [
    {},
    {"tag_layout": "tad"},
    {"policy": "no_bypass"},
    {"policy": "no_second_level", "n_levels": 8},
    {"policy": "bear", "scm_mode": "slc"},
    {"policy": "mccache"},
    {"policy": "redcache"},
    {"policy": "no_bypass_no_ctc", "throttle_wr": True},
]


@contextlib.contextmanager
def forced(shards=None, t_segments=None, replay=0):
    """Pin the execution shape for the duration of a block."""
    old_s = costmodel.set_forced_shards(shards)
    old_t = costmodel.set_forced_tsplit(t_segments)
    old_r = tsplit.set_replay_prefix(replay)
    try:
        yield
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)


# ---------------------------------------------------------------------------
# split_positions: the shared index plan.
# ---------------------------------------------------------------------------

def test_split_positions_partitions_cores():
    """Core slots of the segments are exactly the input positions, in
    order, sentinel-padded to t*ceil(depth/t)."""
    pos = np.arange(10, dtype=np.int32).reshape(1, 10)
    sp = tsplit.split_positions(pos, 10, 4, 0)
    assert sp["spos"].shape == (1, 4, 3)       # core = ceil(10/4)
    real = sp["spos"][sp["spos"] < 10]
    np.testing.assert_array_equal(np.sort(real), np.arange(10))
    np.testing.assert_array_equal(sp["spos"][0, :, 0], [0, 3, 6, 9])
    assert (sp["gpos"] <= 9).all()             # pads clamp to n-1
    assert not sp["replay"].any()              # no prefix requested


def test_split_positions_replay_windows():
    """Replay slots scatter nowhere (sentinel) but gather the last rp real
    positions before each boundary; segment 0 has no history to replay."""
    n, t, rp = 20, 4, 3
    pos = np.arange(n, dtype=np.int32).reshape(1, n)
    sp = tsplit.split_positions(pos, n, t, rp)
    core = 5
    assert sp["spos"].shape == (1, t, core + rp)
    assert (sp["spos"][0, :, :rp] == n).all()
    for k in range(1, t):
        np.testing.assert_array_equal(
            sp["gpos"][0, k, :rp], np.arange(k * core - rp, k * core))
        assert sp["replay"][0, k, :rp].all()
    assert not sp["replay"][0, 0].any()
    # core slots are live in every segment
    assert not sp["replay"][0, :, rp:].any()


def test_split_positions_uneven_depth_and_shards():
    """Non-divisible depths pad the tail segment; per-shard rows split
    independently (the HMS engine hands one row per spatial shard)."""
    pos = np.stack([np.arange(7, dtype=np.int32),
                    np.full(7, 9, dtype=np.int32)])   # shard 1: all pad
    pos[1, :2] = [7, 8]
    sp = tsplit.split_positions(pos, 9, 3, 2)
    assert sp["spos"].shape == (2, 3, 5)              # core 3 + replay 2
    row0 = sp["spos"][0][:, 2:]
    np.testing.assert_array_equal(row0.reshape(-1)[:7], np.arange(7))
    assert (row0.reshape(-1)[7:] == 9).all()          # sentinel tail
    # shard 1's replay windows only replay its own real history
    assert sp["replay"][1].sum() <= 2


# ---------------------------------------------------------------------------
# stitch: the fixed-point loop on a toy composable system.
# ---------------------------------------------------------------------------

def test_stitch_prefix_sum_converges_exactly():
    """Segmented prefix-sum with guessed boundary offsets reaches the
    sequential result in <= T rounds + confirmation."""
    x = np.arange(1, 13, dtype=np.int64)
    segs = x.reshape(4, 3)
    rounds_seen = []

    def run(g, rnd):
        rounds_seen.append(rnd)
        out = g[:, None] + np.cumsum(segs, axis=1)
        return out[:, -1], out

    def advance(g, finals):
        return np.concatenate([[np.int64(0)], finals[:-1]])

    aux, rounds = tsplit.stitch(run, np.zeros(4, np.int64), advance,
                                np.array_equal, max_rounds=5)
    np.testing.assert_array_equal(aux.reshape(-1), np.cumsum(x))
    assert rounds <= 5
    assert rounds_seen == list(range(1, rounds + 1))


def test_stitch_good_guesses_converge_in_two_rounds():
    """Exactly right guesses still take one run + one confirmation."""
    x = np.arange(1, 13, dtype=np.int64)
    segs = x.reshape(4, 3)
    truth = np.concatenate([[0], np.cumsum(x)[2::3][:-1]]).astype(np.int64)

    def run(g, rnd):
        out = g[:, None] + np.cumsum(segs, axis=1)
        return out[:, -1], out

    def advance(g, finals):
        return np.concatenate([[np.int64(0)], finals[:-1]])

    _, rounds = tsplit.stitch(run, truth, advance, np.array_equal, 5)
    assert rounds == 1


def test_stitch_raises_past_round_bound():
    """A composition rule with no fixed point trips the guard instead of
    looping (or worse: returning speculative results)."""
    def run(g, rnd):
        return -g, None

    with pytest.raises(tsplit.StitchError):
        tsplit.stitch(run, np.array([1]), lambda g, o: o,
                      np.array_equal, max_rounds=3)


def test_seg_length_and_replay_knob():
    assert tsplit.seg_length(100, 1, 64) == 100    # replay only when split
    assert tsplit.seg_length(100, 4, 16) == 41
    old = tsplit.set_replay_prefix(32)
    try:
        assert tsplit.replay_prefix() == 32
        assert tsplit.set_replay_prefix(-5) == 32  # clamped to >= 0
        assert tsplit.replay_prefix() == 0
    finally:
        tsplit.set_replay_prefix(old)


# ---------------------------------------------------------------------------
# Engine fallback: StitchError never surfaces, counters stay exact.
# ---------------------------------------------------------------------------

def _fallback_trace(seed=3, n=4000, footprint=4 * 2**20):
    rng = np.random.default_rng(seed)
    col = rng.integers(0, footprint // 32, size=n).astype(np.int64)
    return Trace(f"fallback_{seed}", col, rng.random(n) < 0.3, footprint)


def test_hms_falls_back_to_unsplit_on_stitch_failure(monkeypatch):
    from repro.core import simulator

    t = _fallback_trace()
    cfg = HMSConfig(footprint=t.footprint)
    with forced(1, 1):
        base = simulate(t, cfg).counters

    def boom(*a, **k):
        raise tsplit.StitchError("forced failure")

    monkeypatch.setattr(simulator.tsplit, "stitch", boom)
    obs.enable()
    try:
        obs.clear_records()
        with forced(1, 4):
            got = simulate(t, cfg).counters
        rec = [r for r in obs.records() if r.engine == "hms"][-1]
    finally:
        obs.disable()
    assert rec.t_segments == 1                  # the run that was recorded
    for k in base:
        np.testing.assert_array_equal(got[k], base[k], k)


def test_um_falls_back_to_unsplit_on_stitch_failure(monkeypatch):
    from repro.um import engine as um_engine

    t1, t2 = _fallback_trace(11), _fallback_trace(11)
    _, n_pages = _page_stream(t1)
    spec = um.UMSpec(n_frames=max(1, n_pages // 3), chunk=4)
    with forced(None, 1):
        base = um.simulate_um_many(t1, [spec])[0]

    monkeypatch.setattr(
        um_engine.tsplit, "stitch",
        lambda *a, **k: (_ for _ in ()).throw(tsplit.StitchError("forced")))
    obs.enable()
    try:
        obs.clear_records()
        with forced(None, 4):
            got = um.simulate_um_many(t2, [spec])[0]
        rec = [r for r in obs.records() if r.engine == "um"][-1]
    finally:
        obs.disable()
    assert rec.t_segments == 1
    np.testing.assert_array_equal(got.phase_faults, base.phase_faults)
    assert (got.faults, got.migrated, got.writebacks, got.remote_cols) == \
        (base.faults, base.migrated, base.writebacks, base.remote_cols)


# ---------------------------------------------------------------------------
# Cost model knobs.
# ---------------------------------------------------------------------------

def test_costmodel_forced_shapes_win():
    with forced(3, 5):
        assert costmodel.choose_hms_split(lambda s: 1000, 1) == (3, 5)
        assert costmodel.choose_um_split(10_000, 2) == 5


def test_costmodel_caps_disable_splitting():
    old = costmodel.set_max_tsplit(1)
    try:
        _, t = costmodel.choose_hms_split(lambda s: 200_000 // s, 1)
        assert t == 1
        assert costmodel.choose_um_split(1_000_000, 1) == 1
    finally:
        costmodel.set_max_tsplit(old)


def test_costmodel_splits_when_lanes_scarce():
    """The tentpole's motivating regime: a deep scan that cannot shard
    must buy depth with temporal segments."""
    old = costmodel.set_max_shards(1)
    try:
        s, t = costmodel.choose_hms_split(lambda s: 200_000, 1)
        assert s == 1 and t > 1
        assert costmodel.choose_um_split(1_000_000, 1) > 1
    finally:
        costmodel.set_max_shards(old)


def test_costmodel_keeps_sequential_when_wide():
    """A wide batch already fills the lanes — T=1 must win (splitting
    would pay stitch rounds for nothing)."""
    assert costmodel.choose_um_split(6_000, 8) == 1
    s, t = costmodel.choose_hms_split(lambda s: 6_000 // s, 16)
    assert t == 1


def test_engine_key_clamps_forced_t_to_depth():
    """Forcing T beyond the scan depth degrades gracefully (T <= depth)."""
    from repro.core.simulator import group_engine_key

    t = _fallback_trace(21, n=40)
    cfg = HMSConfig(footprint=t.footprint)
    with forced(8, 16):
        key = group_engine_key(t, [cfg])
        assert key.t_segments <= key.depth


# ---------------------------------------------------------------------------
# The property: any split shape is bit-exact, within the round bound.
# ---------------------------------------------------------------------------

def _random_phased_trace(seed, n=3000, footprint=4 * 2**20):
    """Three random phases drawn from {uniform, streaming, zipf-hot} —
    phase boundaries land anywhere, so segment boundaries cut phases at
    arbitrary points."""
    rng = np.random.default_rng(seed)
    total = footprint // 32
    bounds = np.sort(rng.choice(np.arange(1, n), size=2, replace=False))
    sizes = np.diff(np.concatenate([[0], bounds, [n]]))
    parts = []
    for sz in sizes:
        kind = rng.integers(0, 3)
        if kind == 0:
            parts.append(rng.integers(0, total, size=sz))
        elif kind == 1:
            start = rng.integers(0, total)
            parts.append((start + np.arange(sz)) % total)
        else:
            parts.append(rng.integers(0, max(8, total // 64), size=sz))
    col = np.concatenate(parts).astype(np.int64)
    wr = rng.random(n) < 0.35
    phase_id = np.repeat(np.arange(3, dtype=np.int32), sizes)
    return Trace(f"tsplit_prop_{seed}", col, wr, footprint,
                 phase_id=phase_id, phase_names=("a", "b", "c"))


def _check_hms_property(seed):
    rng = np.random.default_rng(seed * 2654435761 % (2**32))
    t = _random_phased_trace(seed)
    kw = POLICY_KWS[int(rng.integers(0, len(POLICY_KWS)))]
    cfg = HMSConfig(footprint=t.footprint, **kw)
    s = int(rng.choice([1, 2, 4]))
    t_seg = int(rng.choice([2, 4, 8]))
    rp = int(rng.choice([0, 16]))
    with forced(1, 1):
        base = simulate(t, cfg).counters
    obs.enable()
    try:
        obs.clear_records()
        with forced(s, t_seg, rp):
            got = simulate(t, cfg).counters
        rec = [r for r in obs.records() if r.engine == "hms"][-1]
    finally:
        obs.disable()
    assert rec.t_segments == t_seg and rec.shards == s
    assert rec.stitch_rounds <= t_seg + 1 + (1 if rp else 0), (
        f"seed {seed}: stitch blew the round bound")
    for k in base:
        np.testing.assert_array_equal(
            got[k], base[k],
            err_msg=f"seed {seed} {kw} S={s} T={t_seg} r={rp}: {k}")


def _check_um_property(seed):
    rng = np.random.default_rng(seed * 2246822519 % (2**32))
    t1, t2 = _random_phased_trace(seed), _random_phased_trace(seed)
    _, n_pages = _page_stream(t1)
    specs = [
        um.UMSpec(n_frames=max(1, n_pages // int(rng.integers(2, 8))),
                  chunk=int(rng.choice([1, 4, 16])), nvlink=False),
        um.UMSpec(n_frames=max(1, n_pages // 3), chunk=1, nvlink=True,
                  hot_thresh=int(rng.integers(1, 6))),
    ]
    t_seg = int(rng.choice([2, 4, 8]))
    rp = int(rng.choice([0, 16]))
    with forced(None, 1):
        base = um.simulate_um_many(t1, specs)
    obs.enable()
    try:
        obs.clear_records()
        with forced(None, t_seg, rp):
            got = um.simulate_um_many(t2, specs)
        rec = [r for r in obs.records() if r.engine == "um"][-1]
    finally:
        obs.disable()
    assert rec.t_segments == t_seg
    assert rec.stitch_rounds <= t_seg + 1 + (1 if rp else 0)
    for b, g in zip(base, got):
        for f in ("phase_faults", "phase_migrated", "phase_writebacks",
                  "phase_remote_cols"):
            np.testing.assert_array_equal(
                getattr(g, f), getattr(b, f),
                err_msg=f"seed {seed} T={t_seg} r={rp} {b.spec}: {f}")


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(st.integers(min_value=0, max_value=2**20))
    def test_hms_split_parity_property(seed):
        _check_hms_property(seed)

    @settings(max_examples=4, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(st.integers(min_value=0, max_value=2**20))
    def test_um_split_parity_property(seed):
        _check_um_property(seed)
else:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_hms_split_parity_property(seed):
        _check_hms_property(seed)

    @pytest.mark.parametrize("seed", SEEDS[:4])
    def test_um_split_parity_property(seed):
        _check_um_property(seed)


# ---------------------------------------------------------------------------
# Deep-trace regime (CI job: tsplit-deep, needs --runslow).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_zipf_deep_trace_split_parity():
    """10^6-request zipf-skewed trace, the regime the tentpole targets:
    LPT sharding saturates early (the hottest CTC set bounds the padded
    depth), so S x T execution must carry the speedup — and stay
    bit-for-bit exact while doing it."""
    from repro.core import make_trace

    t = make_trace("bfs_tu", n=1_000_000)
    cfg = HMSConfig(footprint=t.footprint)
    with forced(1, 1):
        base = simulate(t, cfg).counters
    obs.enable()
    try:
        obs.clear_records()
        with forced(4, 4, 64):
            got = simulate(t, cfg).counters
        rec = [r for r in obs.records() if r.engine == "hms"][-1]
    finally:
        obs.disable()
    assert rec.t_segments == 4 and rec.stitch_rounds <= 6
    for k in base:
        np.testing.assert_array_equal(got[k], base[k], err_msg=k)

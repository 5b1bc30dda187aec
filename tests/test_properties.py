"""Property-based tests (hypothesis) on the system's invariants."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the 'dev' extra")
from hypothesis import given, settings, strategies as st

from repro.core import HMSConfig, simulate
from repro.core import bypass as bp
from repro.core.timing import DRAM, SCM_MLC
from repro.core.traces import Trace


# ---------------------------------------------------------------------------
# Bypass-policy scoring functions.
# ---------------------------------------------------------------------------

@given(st.integers(1, 64), st.booleans())
@settings(max_examples=50, deadline=None)
def test_penalty_positive_and_monotone_in_locality(ncols, has_write):
    """More row-buffer locality -> lower per-access SCM penalty (Eq. 1)."""
    p1 = float(bp.scm_penalty_score(ncols, has_write, DRAM, SCM_MLC))
    p2 = float(bp.scm_penalty_score(ncols + 1, has_write, DRAM, SCM_MLC))
    assert p1 > 0
    assert p2 < p1


@given(st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_penalty_write_dominates(ncols):
    """A write in the run always raises the penalty (tWR gap)."""
    pr = float(bp.scm_penalty_score(ncols, False, DRAM, SCM_MLC))
    pw = float(bp.scm_penalty_score(ncols, True, DRAM, SCM_MLC))
    assert pw > pr


@given(st.floats(0, 1e6, allow_nan=False), st.floats(1e-3, 1e6),
       st.integers(2, 8))
@settings(max_examples=60, deadline=None)
def test_discretize_bounded(score, max_seen, n_levels):
    lvl = int(bp.discretize(score, max_seen, n_levels))
    assert 0 <= lvl <= n_levels - 1


@given(st.floats(0, 100), st.floats(0, 100), st.floats(0.001, 0.5))
@settings(max_examples=40, deadline=None)
def test_ema_stays_in_hull(avg, value, w):
    out = float(bp.ema_update(avg, value, w))
    lo, hi = min(avg, value), max(avg, value)
    assert lo - 1e-6 <= out <= hi + 1e-6


@given(st.integers(0, 1000), st.integers(1, 1000))
@settings(max_examples=40, deadline=None)
def test_p_dec_is_probability(act, max_act):
    p = float(bp.p_dec(act, max_act))
    assert 0.0 <= p <= 1.0


def test_xorshift_period_sanity():
    s = jnp.asarray(1, jnp.uint32)
    seen = set()
    for _ in range(1000):
        s = bp.xorshift32(s)
        seen.add(int(s))
    assert len(seen) == 1000          # no short cycles


# ---------------------------------------------------------------------------
# CTC invariants (§III-D): LRU ages stay a permutation, disabled ways stay
# untouched, and the packed hot-loop variant is state-equivalent.
# ---------------------------------------------------------------------------

_ctc_ops = st.lists(st.tuples(st.integers(0, 40),      # row group
                              st.integers(0, 7)),      # sector
                    min_size=1, max_size=40)


def _unpack_packed(ps):
    """Decode the packed int64 CTC state into reference-layout arrays."""
    ps = np.asarray(ps)
    return {
        "tags": (ps >> 40).astype(np.int64) - 1,
        "age": ((ps >> 32) & 0xFF).astype(np.int64),
        "svalid": np.stack([((ps >> k) & 1).astype(bool) for k in range(8)],
                           axis=-1),
    }


@given(st.integers(1, 4).map(lambda k: 2 ** (k - 1)),   # sets: 1,2,4,8
       st.integers(1, 8), _ctc_ops)
@settings(max_examples=25, deadline=None)
def test_ctc_lru_ages_stay_permutation(sets, enabled, ops):
    """After any probe/fill/touch sequence, the ages of the enabled ways in
    every set are a permutation of 0..enabled-1 (true LRU needs a total
    recency order), and disabled ways keep their high init ages."""
    from repro.core import ctc

    ways = 8
    state = ctc.init_state(sets, ways, 8)
    for rg, sector in ops:
        state, _ = ctc.probe_fill_touch(state, jnp.int32(rg),
                                        jnp.int32(sector), enabled, sets)
    age = np.asarray(state["age"])
    for s in range(sets):
        assert sorted(age[s, :enabled].tolist()) == list(range(enabled)), (
            f"set {s}: enabled ages {age[s, :enabled]} not a permutation")
        assert age[s, enabled:].tolist() == list(range(enabled, ways)), (
            f"set {s}: disabled ages changed: {age[s, enabled:]}")


@given(st.integers(1, 4).map(lambda k: 2 ** (k - 1)),
       st.integers(1, 8), _ctc_ops)
@settings(max_examples=25, deadline=None)
def test_ctc_disabled_ways_never_allocated(sets, enabled, ops):
    """Ways beyond the enabled count must never receive a tag or a valid
    sector, whatever the access sequence."""
    from repro.core import ctc

    ways = 8
    state = ctc.init_state(sets, ways, 8)
    for rg, sector in ops:
        state, _ = ctc.probe_fill_touch(state, jnp.int32(rg),
                                        jnp.int32(sector), enabled, sets)
    assert np.all(np.asarray(state["tags"])[:, enabled:] == -1)
    assert not np.asarray(state["svalid"])[:, enabled:, :].any()


@given(st.integers(1, 4).map(lambda k: 2 ** (k - 1)),
       st.integers(1, 8), _ctc_ops)
@settings(max_examples=25, deadline=None)
def test_ctc_packed_variant_matches_reference_layout(sets, enabled, ops):
    """The simulator's packed int64 CTC (one gather/scatter/argmax per
    access) must track the reference probe_fill_touch state bit-for-bit."""
    import jax

    from repro.core import ctc

    ways = 8
    with jax.enable_x64(True):            # the packed ways are int64
        state = ctc.init_state(sets, ways, 8)
        pstate = ctc.packed_init(sets, ways, 8)
        for rg, sector in ops:
            state, hit = ctc.probe_fill_touch(state, jnp.int32(rg),
                                              jnp.int32(sector), enabled,
                                              sets)
            pstate, phit = ctc.probe_fill_touch_packed(
                pstate, jnp.int32(rg), jnp.int32(sector), enabled, sets)
            assert bool(hit) == bool(phit)
    dec = _unpack_packed(pstate)
    np.testing.assert_array_equal(np.asarray(state["tags"]), dec["tags"])
    np.testing.assert_array_equal(np.asarray(state["age"]), dec["age"])
    np.testing.assert_array_equal(np.asarray(state["svalid"]), dec["svalid"])


# ---------------------------------------------------------------------------
# Simulator conservation laws.
# ---------------------------------------------------------------------------

def _random_trace(seed, n=8000, footprint=4 * 2**20, write_frac=0.3):
    rng = np.random.default_rng(seed)
    col = rng.integers(0, footprint // 32, size=n).astype(np.int64)
    wr = rng.random(n) < write_frac
    return Trace(f"prop{seed}", col, wr, footprint)


@given(st.integers(0, 10_000), st.floats(0.0, 1.0),
       st.sampled_from(["hms", "no_bypass", "bear", "redcache", "mccache"]))
@settings(max_examples=10, deadline=None)
def test_every_request_served_once(seed, write_frac, policy):
    t = _random_trace(seed, write_frac=write_frac)
    r = simulate(t, HMSConfig(footprint=t.footprint, policy=policy))
    c = r.counters
    assert c["hit_r"] + c["miss_r"] + c["hit_w"] + c["miss_w"] == t.n
    # demand accesses (DRAM hit + SCM bypass + absorbed-in-fill) == requests
    served = (c["demand_dram_rd"] + c["demand_dram_wr"]
              + c["demand_scm_rd"] + c["demand_scm_wr"] + c["fills"])
    assert served >= t.n * 0.999  # fills can absorb >1 demand in principle


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_writebacks_require_prior_write(seed):
    """No dirty evictions on a read-only trace."""
    t = _random_trace(seed, write_frac=0.0)
    r = simulate(t, HMSConfig(footprint=t.footprint, policy="no_bypass"))
    assert r.counters["dirty_evicts"] == 0
    assert r.counters["wb_scm_wr"] == 0


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_mccache_never_dirty(seed):
    """Mostly-clean cache: write-through leaves no dirty lines to evict."""
    t = _random_trace(seed, write_frac=0.5)
    r = simulate(t, HMSConfig(footprint=t.footprint, policy="mccache"))
    assert r.counters["dirty_evicts"] == 0


@given(st.sampled_from(["hms", "no_bypass"]), st.integers(0, 100))
@settings(max_examples=8, deadline=None)
def test_throttling_never_reduces_runtime(policy, seed):
    t = _random_trace(seed)
    base = simulate(t, HMSConfig(footprint=t.footprint, policy=policy))
    thr = simulate(t, HMSConfig(footprint=t.footprint, policy=policy,
                                throttle_act=True, throttle_wr=True))
    assert thr.runtime_cycles >= base.runtime_cycles * 0.999


# ---------------------------------------------------------------------------
# memtier block table coherence.
# ---------------------------------------------------------------------------

@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_fill_then_probe_hits(seed):
    from repro.memtier import TierConfig, access, init_state, probe_blocks
    cfg = TierConfig(num_slots=32, num_blocks=256)
    st_ = init_state(cfg)
    rng = np.random.default_rng(seed)
    blocks = jnp.asarray(rng.integers(0, 256, (16,)), jnp.int32)
    st_, d = access(st_, blocks, jnp.ones(16, bool),
                    jnp.ones(16, jnp.float32), cfg)
    hit, _, _, _ = probe_blocks(st_, blocks, cfg)
    # every filled block must now be resident (later fill to the same slot
    # in the same round may evict an earlier one — allow that)
    filled = np.asarray(d["fill"])
    hits = np.asarray(hit)
    slots = np.asarray(blocks) % cfg.num_slots
    for i in range(16):
        if filled[i]:
            later_same_slot = [j for j in range(i + 1, 16)
                               if slots[j] == slots[i]]
            if not later_same_slot:
                assert hits[i] == 1


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_tag_aliasing_never_false_hits(seed):
    """Blocks mapping to the same slot with different tags must not both
    report hits after one fill."""
    from repro.memtier import TierConfig, access, init_state, probe_blocks
    cfg = TierConfig(num_slots=16, num_blocks=64)
    st_ = init_state(cfg)
    b = int(np.random.default_rng(seed).integers(0, 16))
    blocks = jnp.asarray([b], jnp.int32)
    st_, d = access(st_, blocks, jnp.ones(1, bool),
                    jnp.ones(1, jnp.float32), cfg)
    alias = jnp.asarray([b + 16], jnp.int32)     # same slot, tag+1
    hit, _, _, _ = probe_blocks(st_, alias, cfg)
    assert int(hit[0]) == 0


# ---------------------------------------------------------------------------
# Data pipeline.
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(0, 500))
@settings(max_examples=15, deadline=None)
def test_data_pure_function_of_step(seed, step):
    from repro.data.synthetic import DataConfig, SyntheticTokens
    cfg = DataConfig(vocab=101, seq_len=16, global_batch=4, seed=seed)
    a = SyntheticTokens(cfg).batch_at(step)
    b = SyntheticTokens(cfg).batch_at(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 101
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])

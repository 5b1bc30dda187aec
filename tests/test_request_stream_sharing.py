"""Sharing of the HMS score stream across the configs of one engine call.

The contracts under test:

  * the shared path (one ``_score_stream`` per distinct ``_score_key``,
    then each config packed from it) gives every config the arrays its
    own score stream packs to, array for array,
  * ``_score_key`` holds exactly what ``_score_stream`` reads: a change of
    any other ``HMSConfig`` field leaves the score stream as it was, and a
    change of a field it reads moves the key,
  * ``simulate_many`` over a grid that shares streams equals per-config
    ``simulate``, counters bit for bit,
  * a record's ``score_streams`` counts the distinct streams its engine
    call computed, and is None where no engine ran.
"""

import contextlib
import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core import HMSConfig, costmodel, simulate, simulate_many
from repro.core import simulator as sim_mod
from repro.core.timing import EnergyParams
from repro.core.traces import make_trace, preprocess
from repro.resilience import faults

N = 3000
POLICIES = ("hms", "no_bypass", "no_bypass_no_ctc", "no_second_level",
            "bear", "redcache", "mccache", "always_cache")
# each variant changes one field (or none) of the defaults
VARIANTS = (
    {},
    {"tag_layout": "tad"},
    {"scm_mode": "slc"},
    {"scm_mode": "tlc"},
    {"scm_mode": "auto"},
    {"throttle_act": True},
    {"throttle_wr": True},
    {"ema_weight": 0.05},
    {"n_levels": 8},
    {"use_activation_counter": True},
)
STREAM_KEYS = ("meta", "is_write", "excluded", "pass1", "ncols")


@pytest.fixture(scope="module")
def trace():
    return make_trace("bfs_tu", n=N)


def _grid(trace):
    return [HMSConfig(footprint=trace.footprint, policy=p, **v).validate()
            for p in POLICIES for v in VARIANTS]


@pytest.fixture(scope="module")
def shared(trace):
    """The whole grid's streams through the shared path, once."""
    cfgs = _grid(trace)
    pres = [preprocess(trace, c) for c in cfgs]
    keys = [sim_mod._score_key(c, p) for c, p in zip(cfgs, pres)]
    return cfgs, pres, keys, sim_mod._shared_request_streams(
        trace, cfgs, pres, keys)


@pytest.mark.parametrize("policy", POLICIES)
def test_shared_streams_equal_per_config_streams(trace, shared, policy):
    cfgs, pres, keys, streams = shared
    assert len(set(keys)) < len(keys)          # the grid does share
    for c, p, s in zip(cfgs, pres, streams):
        if c.policy != policy:
            continue
        alone = sim_mod._pack_stream(trace, c, p,
                                     sim_mod._score_stream(trace, c, p))
        assert set(s) == set(alone) == set(STREAM_KEYS)
        for k in STREAM_KEYS:
            assert s[k].dtype == alone[k].dtype, (c, k)
            assert np.array_equal(s[k], alone[k]), (c, k)
    # configs with one key hold one score stream, not equal copies
    first = {}
    for k, s in zip(keys, streams):
        assert s["pass1"] is first.setdefault(k, s)["pass1"]


def test_shared_path_scores_each_key_once(trace, monkeypatch):
    calls = []
    real = sim_mod._score_stream

    def counting(*args):
        calls.append(args[1])
        return real(*args)

    monkeypatch.setattr(sim_mod, "_score_stream", counting)
    cfgs = _grid(trace)
    pres = [preprocess(trace, c) for c in cfgs]
    keys = [sim_mod._score_key(c, p) for c, p in zip(cfgs, pres)]
    sim_mod._shared_request_streams(trace, cfgs, pres, keys)
    assert len(calls) == len(set(keys))


# A changed value for every HMSConfig field.  The fields that move the
# score key: those the score stream reads (the SCM timings through the
# mode and throttle flags, the EMA weight, the levels, the activation
# counter) and those that change the preprocessed trace it reads.
CHANGED = {
    "footprint": 2 * 2**20,
    "r_hbm": 0.5,
    "dram_ratio": 0.25,
    "line_bytes": 128,
    "organization": "separate",
    "policy": "bear",
    "tag_layout": "tad",
    "scm_mode": "slc",
    "channels": 4,
    "banks_per_channel": 8,
    "n_levels": 8,
    "ema_weight": 0.05,
    "use_activation_counter": True,
    "bear_fill_prob": 0.5,
    "redcache_threshold": 4,
    "ctc_fraction": 0.0625,
    "ctc_ways": 8,
    "ctc_sectors_per_line": 4,
    "link_bw_gbps": 64.0,
    "fault_latency_ns": 5000.0,
    "fault_overlap": 4.0,
    "um_prefetch_pages": 8,
    "um_hot_threshold": 8,
    "act_page_bytes": 16 * 1024,
    "throttle_act": True,
    "throttle_wr": True,
    "energy": dataclasses.replace(EnergyParams(), scm_act=4.0),
    "compute_cycles_per_request": 0.5,
}
KEYED = {"scm_mode", "throttle_act", "throttle_wr", "ema_weight",
         "n_levels", "use_activation_counter",
         # through the preprocessed trace
         "footprint", "r_hbm", "dram_ratio", "line_bytes",
         "ctc_sectors_per_line", "act_page_bytes"}


def test_every_config_field_is_classified():
    names = {f.name for f in dataclasses.fields(HMSConfig)}
    assert names == set(CHANGED)
    assert KEYED <= names


@pytest.mark.parametrize("field", sorted(CHANGED))
def test_score_key_holds_what_the_score_stream_reads(trace, field):
    base = HMSConfig(footprint=trace.footprint)
    cfg = dataclasses.replace(base, **{field: CHANGED[field]})
    assert getattr(cfg, field) != getattr(base, field)
    pre0, pre1 = preprocess(trace, base), preprocess(trace, cfg)
    k0 = sim_mod._score_key(base, pre0)
    k1 = sim_mod._score_key(cfg, pre1)
    if field in KEYED:
        assert k1 != k0
        return
    assert k1 == k0
    s0 = sim_mod._score_stream(trace, base, pre0)
    s1 = sim_mod._score_stream(trace, cfg, pre1)
    assert set(s0) == set(s1)
    for k in s0:
        assert np.array_equal(s0[k], s1[k]), k


@pytest.fixture(scope="module")
def batched(trace):
    cfgs = _grid(trace)
    return cfgs, simulate_many(trace, cfgs)


@pytest.mark.parametrize("policy", POLICIES)
def test_simulate_many_equals_simulate_on_the_grid(trace, batched, policy):
    cfgs, results = batched
    for c, r in zip(cfgs, results):
        if c.policy != policy:
            continue
        alone = simulate(trace, c).counters
        assert set(r.counters) == set(alone)
        for k, v in alone.items():
            np.testing.assert_array_equal(r.counters[k], v, err_msg=k)


@contextlib.contextmanager
def _records():
    obs.clear_records()
    obs.enable()
    try:
        yield lambda: [r for r in obs.records() if r.engine == "hms"]
    finally:
        obs.disable()
        obs.clear_records()


def test_score_streams_of_the_sweep_grid(trace):
    """The sweep cell's grid: tag layout x CTC fraction x SCM mode, one
    engine call, one score stream per SCM mode."""
    cfgs = [HMSConfig(footprint=trace.footprint, tag_layout=t,
                      ctc_fraction=f, scm_mode=m)
            for t in ("amil", "tad") for f in (0.25, 0.0625)
            for m in ("slc", "mlc", "tlc")]
    with _records() as recs:
        simulate_many(trace, cfgs)
        (rec,) = recs()
    assert rec.batch == 12 and rec.score_streams == 3


def test_score_streams_of_a_policy_grid(trace):
    """Five policies at one SCM mode: each policy's engine call shares
    one stream among its tag layouts and CTC fractions."""
    policies = ("hms", "bear", "redcache", "mccache", "no_bypass")
    cfgs = [HMSConfig(footprint=trace.footprint, policy=p, tag_layout=t,
                      ctc_fraction=f)
            for p in policies for t in ("amil", "tad")
            for f in (0.25, 0.0625)]
    with _records() as recs:
        simulate_many(trace, cfgs)
        got = recs()
    assert sorted(r.engine_key.split(":")[1] for r in got) == sorted(policies)
    assert [(r.batch, r.score_streams) for r in got] == [(4, 1)] * 5


def test_score_streams_on_the_single_config_path(trace):
    with _records() as recs:
        simulate(trace, HMSConfig(footprint=trace.footprint))
        (rec,) = recs()
    assert rec.score_streams == 1


@contextlib.contextmanager
def _engine_fails(monkeypatch, spec):
    """Plan (1, 1), retries off, and the engine calls in ``spec`` fail."""
    monkeypatch.setenv("REPRO_RETRY", "0")
    old_s = costmodel.set_forced_shards(1)
    old_t = costmodel.set_forced_tsplit(1)
    try:
        with faults.inject(spec):
            yield
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)


def test_score_streams_is_none_on_the_reference_rung(trace, monkeypatch):
    cfg = HMSConfig(footprint=trace.footprint)
    with _records() as recs, _engine_fails(monkeypatch, "oom@1"):
        simulate(trace, cfg)
        (rec,) = recs()
    assert rec.ladder_rung == "reference"
    assert rec.score_streams is None and rec.input_bytes is None


def test_score_streams_of_a_bisected_batch(trace, monkeypatch):
    """The whole batch ran no engine; each half counts its own streams."""
    cfgs = [HMSConfig(footprint=trace.footprint, scm_mode=m, n_levels=5)
            for m in ("mlc", "tlc")]
    with _records() as recs, _engine_fails(monkeypatch, "oom@1"):
        simulate_many(trace, cfgs)
        got = recs()
    assert [r.ladder_rung for r in got] == ["S1T1", "S1T1", "bisect"]
    assert [r.score_streams for r in got] == [1, 1, None]

"""Engine telemetry subsystem: ledger round-trip, counter-digest
stability, retrace sentinel, span export, deprecation shims, and the
benchmarks.compare regression gate.

The contracts under test:

  * every engine invocation emits one :class:`RunRecord` with the shard
    plan, compile-vs-cache-hit flag, and a counter digest; records survive
    a JSONL round trip intact,
  * the counter digest is bit-exact across shard counts and execution
    shapes (the ledger-level face of the engines' parity guarantees),
  * ``assert_no_retrace`` catches a warm engine deliberately recompiling
    and stays quiet after a blessed ``obs.reset``,
  * the old scattered instrumentation entry points (deprecated PR 6-9)
    are gone; ``obs.cache_stats`` / ``obs.reset`` are the only cache API,
  * ``benchmarks.compare`` exits 0 on a self-diff and non-zero when a
    model output is perturbed.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro import obs, um
from repro.core import HMSConfig, make_trace, simulate, simulate_many
from repro.core import simulator as sim_mod
from repro.core.simulator import set_max_shards
from repro.core.traces import Trace


@pytest.fixture
def ledger(tmp_path):
    """Observability on, streaming to a tmp dir; restored afterwards."""
    obs.clear_records()
    obs.clear_events()
    obs.enable(str(tmp_path))
    yield tmp_path
    obs.disable()
    obs.clear_records()
    obs.clear_events()


def _trace(n=4000, footprint=4 * 2**20, seed=3):
    rng = np.random.default_rng(seed)
    total = footprint // 32
    col = rng.integers(0, total, size=n).astype(np.int64)
    wr = rng.random(n) < 0.3
    return Trace("obs_golden", col, wr, footprint)


# ---------------------------------------------------------------------------
# Run ledger.
# ---------------------------------------------------------------------------

def test_ledger_jsonl_roundtrip(ledger):
    t = _trace()
    cfg = HMSConfig(footprint=t.footprint)
    simulate(t, cfg)
    simulate_many(t, [cfg, dataclasses.replace(cfg, scm_mode="slc"),
                      dataclasses.replace(cfg, ema_weight=0.05)])
    recs = obs.records()
    assert len(recs) >= 2
    loaded = obs.load_ledger(str(ledger))
    assert len(loaded) == len(recs)
    for a, b in zip(recs, loaded):
        assert a.to_dict() == b.to_dict()
    hms = [r for r in loaded if r.engine == "hms"]
    assert {r.entry for r in hms} == {"simulate", "simulate_many"}
    for r in hms:
        assert r.engine_key.startswith("hms:")
        assert r.shards >= 1 and r.depth >= 1
        assert r.load_imbalance >= 1.0
        assert len(r.counter_digest) == 16
        assert r.wall_s > 0
        assert r.host["python"]
    batched = [r for r in hms if r.entry == "simulate_many"]
    assert batched and batched[0].batch == 3


def test_ledger_records_compile_vs_cache_hit(ledger):
    t = _trace(seed=21)
    cfg = HMSConfig(footprint=t.footprint)
    obs.reset(um=False)                    # guarantee a cold start
    simulate(t, cfg)
    simulate(t, cfg)
    a, b = [r for r in obs.records() if r.engine == "hms"][-2:]
    assert a.engine_key == b.engine_key
    assert a.compiled and not b.compiled
    assert a.counter_digest == b.counter_digest
    split = obs.compile_split([a, b])
    assert split["runs"] == 2 and split["compiled_runs"] == 1
    assert split["wall_s"] == pytest.approx(a.wall_s + b.wall_s)


def test_git_identity_in_records(ledger):
    t = _trace()
    simulate(t, HMSConfig(footprint=t.footprint))
    r = obs.records()[-1]
    info = obs.git_info()
    assert r.git_sha == info["git_sha"]
    if r.git_sha is not None:              # running from a git checkout
        assert len(r.git_sha) == 40
        assert isinstance(r.git_dirty, bool)


def test_um_records_carry_dedupe_accounting(ledger):
    t = make_trace("zipf", n=4000)
    base = HMSConfig(footprint=t.footprint, organization="hbm")
    specs = [um.um_spec(dataclasses.replace(base, r_hbm=r))
             for r in (0.25, 0.5, 0.25)]          # one duplicate
    obs.reset(hms=False)
    um.simulate_um_many(t, specs)
    um.simulate_um_many(t, specs)                 # fully memoized
    ran, memo = [r for r in obs.records() if r.engine == "um"][-2:]
    assert (ran.um_lanes_requested, ran.um_lanes_run,
            ran.um_lanes_deduped) == (3, 2, 1)
    assert ran.engine_key.startswith("um:")
    assert (memo.um_lanes_run, memo.engine_key) == (0, "um:memoized")
    assert memo.counter_digest == ran.counter_digest   # same results


def test_disabled_by_default_emits_nothing():
    assert not obs.enabled()
    before = len(obs.records())
    t = _trace(seed=8)
    simulate(t, HMSConfig(footprint=t.footprint))
    assert len(obs.records()) == before


# ---------------------------------------------------------------------------
# Counter digest.
# ---------------------------------------------------------------------------

def test_counter_digest_stable_across_shard_counts():
    """Auto shard selection and forced S=1 produce bit-identical counters,
    hence equal digests — the cross-host comparability guarantee."""
    t = make_trace("bfs_tu", n=20_000)
    cfg = HMSConfig(footprint=t.footprint)
    auto = obs.counter_digest(simulate(t, cfg).counters)
    old = set_max_shards(1)
    try:
        seq = obs.counter_digest(simulate(t, cfg).counters)
    finally:
        set_max_shards(old)
    assert auto == seq


def test_counter_digest_stable_across_execution_shapes():
    """simulate vs simulate_many (vmapped) digests agree per config."""
    t = _trace(seed=13)
    kws = [{}, {"scm_mode": "slc"}, {"ema_weight": 0.05}]
    cfgs = [HMSConfig(footprint=t.footprint, **kw) for kw in kws]
    batched = simulate_many(t, cfgs)
    for cfg, rb in zip(cfgs, batched):
        assert (obs.counter_digest(simulate(t, cfg).counters)
                == obs.counter_digest(rb.counters))


def test_counter_digest_sensitivity():
    c = {"a": 1.0, "b": np.array([2.0, 3.0])}
    assert obs.counter_digest(c) == obs.counter_digest(
        {"b": np.array([2.0, 3.0]), "a": 1.0})       # order-insensitive
    assert obs.counter_digest(c) != obs.counter_digest(
        {"a": 1.0, "b": np.array([2.0, 3.0000001])})  # value-sensitive
    assert obs.counter_digest(c) != obs.counter_digest(
        {"a": 1.0, "c": np.array([2.0, 3.0])})        # key-sensitive
    assert obs.counter_digest([c, c]) != obs.counter_digest(c)


# ---------------------------------------------------------------------------
# Retrace sentinel.
# ---------------------------------------------------------------------------

def test_assert_no_retrace_catches_deliberate_retrace():
    t = _trace(seed=17)
    cfg = HMSConfig(footprint=t.footprint)
    simulate(t, cfg)                       # warm the engine
    with pytest.raises(obs.RetraceError, match="hms:"):
        with obs.assert_no_retrace():
            # dropping the jit cache behind the sentinel's back — the
            # rerun compiles a warm fingerprint
            sim_mod._ENGINE_CACHE.clear()
            simulate(t, cfg)


def test_assert_no_retrace_allows_cold_and_reset():
    t = _trace(seed=19)
    cfg = HMSConfig(footprint=t.footprint, policy="bear")
    obs.reset(um=False)
    with obs.assert_no_retrace() as guard:
        simulate(t, cfg)                   # fresh fingerprint: compiles
        simulate(t, cfg)                   # warm: cache hit
    assert guard.compiles_during() >= 1
    simulate(t, cfg)
    with obs.assert_no_retrace():
        obs.reset(um=False)                # blessed invalidation
        simulate(t, cfg)                   # recompile is expected


def test_cache_stats_and_reset_scoping():
    t = _trace(seed=23)
    simulate(t, HMSConfig(footprint=t.footprint))
    um.simulate_um(t, HMSConfig(footprint=t.footprint, organization="hbm",
                                r_hbm=0.5))
    s = obs.cache_stats()
    assert s["hms_engines"] >= 1 and s["um_engines"] >= 1
    assert s["engine_runs"] >= s["engine_compiles"] >= 1
    obs.reset(hms=False)                   # UM-only reset
    s2 = obs.cache_stats()
    assert s2["um_engines"] == 0 and s2["um_results_cached"] == 0
    assert s2["hms_engines"] == s["hms_engines"]


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------

def test_span_trace_exports_perfetto_json(ledger):
    t = make_trace("moe_expert", n=4000)
    simulate(t, HMSConfig(footprint=t.footprint))
    names = {e[0] for e in obs.events()}
    assert {"preprocess", "scan", "postprocess"} <= names
    path = obs.export_trace(str(ledger))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert evs and all(e["ph"] == "X" for e in evs)
    assert all(e["dur"] >= 0 and "ts" in e and "pid" in e for e in evs)
    scan = next(e for e in evs if e["name"] == "scan")
    assert scan["args"]["engine"] == "hms"


def test_spans_noop_when_disabled():
    assert not obs.enabled()
    before = len(obs.events())
    with obs.span("nothing", x=1):
        pass
    assert len(obs.events()) == before
    # the disabled path hands back a shared singleton (no allocation)
    assert obs.span("a") is obs.span("b")


# ---------------------------------------------------------------------------
# Deprecated shims: removed in PR 10 after a deprecation cycle (PR 6-9).
# The obs facade (obs.cache_stats / obs.reset) is the only cache API.
# ---------------------------------------------------------------------------

def test_deprecated_shims_are_gone():
    for name in ("engine_cache_size", "clear_engine_cache"):
        assert not hasattr(sim_mod, name), name
    for name in ("um_engine_cache_size", "um_lanes_run",
                 "clear_um_caches", "clear_um_results"):
        assert not hasattr(um, name), name
    # the facade the shims delegated to still covers every removed name
    stats = obs.cache_stats()
    assert {"hms_engines", "um_engines", "um_lanes_run"} <= set(stats)


# ---------------------------------------------------------------------------
# Phase-summary schema pin (the tabular contract downstream notebooks and
# the bench artifacts consume).
# ---------------------------------------------------------------------------

def test_phase_summary_column_schema():
    base_cols = {"requests", "hit_rate_read", "hit_rate_write",
                 "bypass_rate", "ctc_hit_rate", "fills", "dram_bytes",
                 "scm_bytes", "scm_write_cols"}
    um_cols = {"um_faults", "um_migrated_pages", "um_writeback_pages",
               "um_remote_cols", "um_link_bytes"}
    t = make_trace("moe_expert", n=4000)
    s = simulate(t, HMSConfig(footprint=t.footprint)).phase_summary()
    assert s and all(set(row) == base_cols for row in s.values())
    s_um = simulate(t, HMSConfig(footprint=t.footprint,
                                 organization="hbm", r_hbm=0.5)
                    ).phase_summary()
    assert all(set(row) == base_cols | um_cols for row in s_um.values())


# ---------------------------------------------------------------------------
# Regression gate.
# ---------------------------------------------------------------------------

ARTIFACT = {
    "n": 20000, "grid_points": 12,
    "host": {"platform": "linux-A", "jax": "0.4.0", "git_sha": "abc"},
    "workloads": {
        "bfs_tu": {
            "counter_digest": "a03eca5718cd088d",
            "point_runtime_cycles": [1.5e9, 1.4e9],
            "best_runtime": 1.4e9,
            "wall_s": 2.0, "compile_s": 10.0, "us_per_point": 166000.0,
            "grid_shards": 4, "single_depth": 5000,
            "single_shard_speedup": 2.5,
        },
    },
}


def _dump(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_compare_self_diff_is_clean(tmp_path):
    from benchmarks.compare import main
    p = _dump(tmp_path, "old.json", ARTIFACT)
    assert main([p, p]) == 0
    assert main([p, p, "--max-wall-regress", "10"]) == 0


def test_compare_flags_model_drift(tmp_path):
    from benchmarks.compare import main
    new = json.loads(json.dumps(ARTIFACT))
    new["workloads"]["bfs_tu"]["counter_digest"] = "deadbeefdeadbeef"
    assert main([_dump(tmp_path, "old.json", ARTIFACT),
                 _dump(tmp_path, "new.json", new)]) == 1
    new = json.loads(json.dumps(ARTIFACT))
    new["workloads"]["bfs_tu"]["point_runtime_cycles"][1] = 9.9e9
    assert main([_dump(tmp_path, "old2.json", ARTIFACT),
                 _dump(tmp_path, "new2.json", new)]) == 1


def test_compare_timing_and_host_rules(tmp_path):
    from benchmarks.compare import main
    new = json.loads(json.dumps(ARTIFACT))
    new["host"]["platform"] = "linux-B"            # informational
    new["workloads"]["bfs_tu"]["grid_shards"] = 8  # shard plan: info
    new["workloads"]["bfs_tu"]["single_shard_speedup"] = 1.1
    new["workloads"]["bfs_tu"]["wall_s"] = 2.2     # +10% timing
    old_p = _dump(tmp_path, "old.json", ARTIFACT)
    new_p = _dump(tmp_path, "new.json", new)
    assert main([old_p, new_p]) == 0               # timings ungated
    assert main([old_p, new_p, "--max-wall-regress", "50"]) == 0
    assert main([old_p, new_p, "--max-wall-regress", "5"]) == 2


def test_compare_usage_errors(tmp_path):
    from benchmarks.compare import main
    assert main([str(tmp_path / "missing.json"),
                 str(tmp_path / "missing2.json")]) == 3


def test_compare_classify_word_boundary_tokens():
    """The 'ts' marker must match whole tokens, not substrings: counter
    leaves like um_faults / hits / counts / points are model outputs and
    must stay in the bit-for-bit gate."""
    from benchmarks.compare import _classify

    model = ("um_faults", "hits", "counts", "points", "grid_points",
             "faults", "requests", "counter_digest", "best_runtime")
    info = ("grid_shards", "shards", "t_segments", "stitch_rounds",
            "tsplit_speedup", "replay_prefix", "partial", "ts",
            "ckpt_entries", "degradations", "single_shard_speedup",
            # calibration / plan-telemetry keys (PR 10): predicted costs,
            # regret and profile identity vary across hosts and profiles
            "plan_predicted_us", "plan_alternatives", "calib_fingerprint",
            "regret_us", "misplans", "predicted_us")
    for leaf in model:
        assert _classify(("workloads", "w", leaf)) == "model", leaf
    for leaf in info:
        assert _classify(("workloads", "w", leaf)) == "info", leaf
    assert _classify(("workloads", "w", "wall_s")) == "timing"
    assert _classify(("host", "platform")) == "info"


def test_compare_um_faults_drift_exits_1(tmp_path):
    """Regression for the substring bug: an um_faults counter drifting
    between two artifacts is model drift (exit 1), not informational."""
    from benchmarks.compare import main

    art = {
        "n": 1000,
        "host": {"platform": "linux", "git_sha": "a" * 40},
        "workloads": {"bfs_tu": {
            "n": 1000, "trace_fp": "f" * 16,
            "points": [{
                "rel_footprint": 2.0, "nvlink": False,
                "spec_key": "F8:c16:nv0:h4",
                "counters": {"um_faults": [3.0, 1.0],
                             "um_migrated": [2.0, 0.0],
                             "um_writebacks": [1.0, 0.0],
                             "um_remote_cols": [0.0, 0.0]},
                "faults": 4.0,
            }],
        }},
    }
    old_p = _dump(tmp_path, "old.json", art)
    assert main([old_p, old_p]) == 0
    drift = json.loads(json.dumps(art))
    drift["workloads"]["bfs_tu"]["points"][0]["counters"]["um_faults"][0] \
        = 99.0
    assert main([old_p, _dump(tmp_path, "new.json", drift)]) == 1


def test_compare_frontier_flag_self_and_regression(tmp_path):
    from benchmarks.compare import main

    art = {
        "host": {"platform": "linux", "git_sha": "a" * 40},
        "workloads": {"bfs_tu": {
            "n": 1000, "points": 2, "trace_fp": "f" * 16,
            "point_config_digests": ["d0" * 8, "d1" * 8],
            "point_counters": [
                {"demand_dram_rd": 10.0, "demand_dram_wr": 1.0,
                 "demand_scm_rd": 2.0, "demand_scm_wr": 0.0,
                 "probe_cols": 1.0},
                {"demand_dram_rd": 20.0, "demand_dram_wr": 1.0,
                 "demand_scm_rd": 2.0, "demand_scm_wr": 0.0,
                 "probe_cols": 1.0},
            ],
            "point_runtime_cycles": [100.0, 50.0],
        }},
    }
    old_p = _dump(tmp_path, "old.json", art)
    assert main([old_p, old_p, "--frontier", "--quiet"]) == 0
    # d1 (fast, heavy traffic) regresses on runtime: frontier moves
    new = json.loads(json.dumps(art))
    new["workloads"]["bfs_tu"]["point_runtime_cycles"][1] = 500.0
    assert main([old_p, _dump(tmp_path, "new.json", new),
                 "--frontier", "--quiet"]) == 1


# ---------------------------------------------------------------------------
# Ledger robustness + design-space-store fields (schema 3).
# ---------------------------------------------------------------------------

def test_load_ledger_skips_torn_lines(ledger):
    t = _trace()
    simulate(t, HMSConfig(footprint=t.footprint))
    n_good = len(obs.records())
    path = ledger / "ledger.jsonl"
    with open(path, "a") as f:
        f.write('{"schema": 3, "engine": "hms", "tr')   # torn tail
    with pytest.warns(RuntimeWarning, match="torn/corrupt"):
        loaded = obs.load_ledger(str(ledger))
    assert len(loaded) == n_good
    # valid JSON that isn't a record dict is skipped too, not crashed on
    # (the unterminated torn tail swallows the first appended line)
    with open(path, "a") as f:
        f.write('"not a record"\n{"schema": 3}\n')
    with pytest.warns(RuntimeWarning, match="2 torn/corrupt"):
        assert len(obs.load_ledger(str(ledger))) == n_good


def test_ledger_carries_full_counters(ledger):
    """Schema 3: every HMS/UM record carries the silver-store identity
    (trace fingerprint, per-lane config keys) and the full per-lane
    counters — decode-exact against the engine's own outputs."""
    from repro.resilience import sweepckpt

    t = _trace()
    cfg = HMSConfig(footprint=t.footprint)
    cfgs = [cfg, dataclasses.replace(cfg, scm_mode="slc")]
    rs = simulate_many(t, cfgs)
    specs = [um.um_spec(HMSConfig(footprint=t.footprint,
                                  organization="hbm", r_hbm=0.5),
                        nvlink=nv) for nv in (False, True)]
    um.simulate_um_many(t, specs)

    recs = obs.load_ledger(str(ledger))
    hms = [r for r in recs if r.engine == "hms"][-1]
    assert hms.trace_fp == sweepckpt.trace_fingerprint(t)
    assert hms.config_digests == [sweepckpt.config_digest(c) for c in cfgs]
    assert len(hms.counters) == len(cfgs)
    for lane, r in zip(hms.counters, rs):
        dec = sweepckpt.decode_counters(lane)
        for k, v in r.counters.items():
            np.testing.assert_array_equal(dec[k], np.asarray(v, np.float64))

    umr = [r for r in recs if r.engine == "um"][-1]
    assert umr.trace_fp == sweepckpt.trace_fingerprint(t)
    assert umr.config_digests == [sweepckpt.um_spec_key(s) for s in specs]
    assert {k for lane in umr.counters for k in lane} \
        == {"um_faults", "um_migrated", "um_writebacks", "um_remote_cols"}


def test_old_schema_ledger_loads_with_none_fields(tmp_path):
    """A schema-2 line (no trace_fp / config_digests / counters) still
    loads; the new fields come back None."""
    rec = obs.RunRecord(engine="hms", entry="simulate", trace="t", n=10,
                        phases=1, engine_key="hms:x", batch=1, shards=1,
                        depth=10, t_segments=1, stitch_rounds=1,
                        load_imbalance=1.0, compiled=True, wall_s=0.1,
                        counter_digest="0" * 16)
    d = rec.to_dict()
    for k in ("trace_fp", "config_digests", "counters"):
        d.pop(k)
    d["schema"] = 2
    p = tmp_path / "ledger.jsonl"
    p.write_text(json.dumps(d) + "\n")
    (r,) = obs.load_ledger(str(tmp_path))
    assert r.trace_fp is None and r.config_digests is None \
        and r.counters is None


@pytest.mark.parametrize("schema,dropped", [
    (4, ("input_bytes", "score_streams", "overflow_points")),
    (5, ("score_streams", "overflow_points")),
    (6, ("overflow_points",)),
])
def test_pre_schema_6_ledger_loads_and_ingests(tmp_path, schema, dropped):
    """A schema-4, -5 or -6 line, written before the staged bytes, the
    score stream count or the overflow count existed, loads with them None
    and still lands its lanes in the silver store."""
    from repro.obs.store import SilverStore

    lane = {k: 0.0 for k in ("demand_dram_rd", "demand_dram_wr",
                             "demand_scm_rd", "demand_scm_wr")}
    rec = obs.RunRecord(engine="hms", entry="simulate_many", trace="t",
                        n=10, phases=1, engine_key="hms:hms:n10", batch=2,
                        compiled=False, wall_s=0.1, counter_digest="0" * 16,
                        trace_fp="f" * 16, config_digests=["a", "b"],
                        counters=[dict(lane, demand_dram_rd=v)
                                  for v in (1.0, 2.0)],
                        input_bytes=64, score_streams=1, overflow_points=1)
    assert rec.schema == 7
    d = rec.to_dict()
    for k in dropped:
        d.pop(k)
    d["schema"] = schema
    p = tmp_path / "ledger.jsonl"
    p.write_text(json.dumps(d) + "\n")
    (r,) = obs.load_ledger(str(tmp_path))
    assert r.schema == schema
    for k in dropped:
        assert getattr(r, k) is None, k
    stats = SilverStore().ingest_ledger(str(p))
    assert stats.added == 2 and stats.skipped == 0

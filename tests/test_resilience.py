"""Engine resilience layer: validation, degradation ladder, fault
injection, and resumable sweep checkpoints.

Four layers of ``repro.resilience`` under test:

* structured validation — :class:`ValidationError` units (field paths,
  fix hints, ``python -O`` survival) for configs, traces, scenarios, and
  the packed-word engine invariants that used to be bare asserts;
* the guard — failure classification, retry/bisect/degrade walking, and
  the exhaustion error;
* the fault-parity battery — the load-bearing property: under EVERY
  injected fault class, both engines complete through the degradation
  ladder with counter digests bit-identical to the unfaulted run, and the
  ledger records each degradation event.  Runs under hypothesis when the
  library is present, else over a fixed seed battery;
* sweep checkpoints — JSON round-trip bit-exactness and the
  kill-and-resume contract ``benchmarks.run --resume`` is built on.
"""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs, um
from repro.core import HMSConfig, costmodel, simulate, simulate_many, tsplit
from repro.core.traces import Trace, make_trace
from repro.resilience import faults, guard, sweepckpt, validate
from repro.resilience import (CounterInvalidError, EngineInvariantError,
                              InjectedFault, ResilienceError, ValidationError)

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # container ships without hypothesis
    HAVE_HYPOTHESIS = False

SEEDS = [0, 1, 2]
ENGINE_FAULTS = ["oom", "deadline", "stitch", "nan"]


@pytest.fixture(autouse=True)
def _fast_guard(monkeypatch):
    """No backoff sleeps in tests; leave retry budget at the default."""
    monkeypatch.setattr(guard, "_BACKOFF_S", 0.0)


@contextlib.contextmanager
def forced(shards=None, t_segments=None, replay=0):
    old_s = costmodel.set_forced_shards(shards)
    old_t = costmodel.set_forced_tsplit(t_segments)
    old_r = tsplit.set_replay_prefix(replay)
    try:
        yield
    finally:
        costmodel.set_forced_shards(old_s)
        costmodel.set_forced_tsplit(old_t)
        tsplit.set_replay_prefix(old_r)


def _rand_trace(seed=0, n=4000, footprint=4 * 2**20):
    rng = np.random.default_rng(seed)
    col = rng.integers(0, footprint // 32, size=n).astype(np.int64)
    return Trace(f"resil_{seed}", col, rng.random(n) < 0.3, footprint)


# ---------------------------------------------------------------------------
# Structured validation.
# ---------------------------------------------------------------------------

def test_validation_error_carries_field_and_hint():
    e = ValidationError("HMSConfig.footprint", -1, "a positive byte count",
                        "pass bytes")
    assert e.field == "HMSConfig.footprint"
    assert e.got == -1
    assert "expected a positive byte count" in str(e)
    assert "fix: pass bytes" in str(e)
    assert isinstance(e, ValueError)            # old except clauses survive


def test_config_rejects_bad_fields():
    fp = 4 * 2**20
    with pytest.raises(ValidationError, match="footprint"):
        HMSConfig(footprint=0).validate()
    with pytest.raises(ValidationError, match="r_hbm"):
        HMSConfig(footprint=fp, r_hbm=0.0).validate()
    with pytest.raises(ValidationError, match="organization"):
        HMSConfig(footprint=fp, organization="hbm3").validate()
    with pytest.raises(ValidationError, match="ctc_sectors_per_line"):
        HMSConfig(footprint=fp, ctc_sectors_per_line=64).validate()
    with pytest.raises(ValidationError, match="n_levels"):
        HMSConfig(footprint=fp, n_levels=1000).validate()


def test_unknown_policy_message_lists_all_policies():
    from repro.core.timing import POLICIES
    assert len(POLICIES) == 8
    with pytest.raises(ValidationError) as ei:
        HMSConfig(footprint=4 * 2**20, policy="lru").validate()
    for p in POLICIES:
        assert p in str(ei.value)


def test_engine_dispatch_raises_actionable_policy_error():
    """The engine-entry dispatch (ex-``raise ValueError(policy)``) now
    names every valid policy."""
    err = validate.unknown_policy_error("clock")
    assert "clock" in str(err) and "always_cache" in str(err)
    assert "hms" in str(err)


def test_ctc_rounding_warns_only_when_heavy():
    import warnings as w
    fp = 64 * 2**20
    with w.catch_warnings():
        w.simplefilter("error", validate.ResilienceWarning)
        HMSConfig(footprint=fp).validate()          # default: quiet
    with pytest.warns(validate.ResilienceWarning, match="CTC sets"):
        # 7 ways: 54 raw sets round down to 32 (> 1.5x budget dropped)
        validate._validate_config_cached.cache_clear()
        HMSConfig(footprint=fp, ctc_ways=7).validate()


def test_trace_validation_rejects_malformed_streams():
    fp = 2**20
    col = np.arange(100, dtype=np.int64)
    wr = np.zeros(100, bool)
    with pytest.raises(ValidationError, match="at least one request"):
        Trace("empty", np.empty(0, np.int64), np.empty(0, bool), fp)
    with pytest.raises(ValidationError, match="is_write"):
        Trace("shape", col, wr[:50], fp)
    with pytest.raises(ValidationError, match="below footprint"):
        Trace("oob", col + 10**9, wr, fp)
    with pytest.raises(ValidationError, match="phase_id"):
        Trace("pid", col, wr, fp, phase_id=np.zeros(7, np.int32),
              phase_names=("a",))
    with pytest.raises(ValidationError, match="phase indices"):
        Trace("pidrange", col, wr, fp,
              phase_id=np.full(100, 3, np.int32), phase_names=("a", "b"))


def test_scenario_validation():
    from repro.workloads.ir import Phase, Scenario
    with pytest.raises(ValidationError, match="regions"):
        Scenario("over", {"a": 0.7, "b": 0.7},
                 (Phase("p", "a", "stream"),))
    with pytest.raises(ValidationError, match="pattern"):
        Scenario("pat", {"a": 1.0}, (Phase("p", "a", "hilbert"),))
    with pytest.raises(ValidationError, match="region"):
        Scenario("reg", {"a": 1.0}, (Phase("p", "b", "stream"),))
    with pytest.raises(ValidationError, match="unique phase name"):
        Scenario("dup", {"a": 1.0},
                 (Phase("p", "a", "stream"), Phase("p", "a", "random")))


def test_packing_invariants_raise_structured_errors():
    with pytest.raises(EngineInvariantError, match="2\\^21"):
        validate.check_hms_packing("t", tag_max=1 << 22)
    with pytest.raises(EngineInvariantError, match="row_group"):
        validate.check_hms_packing("t", rg_max=(1 << 23))
    validate.check_hms_packing("t", tag_max=5, n_levels=8, rg_max=7)


def test_validation_survives_python_O():
    """Unlike the bare asserts these checks replaced, ``python -O`` still
    rejects malformed inputs."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    code = (
        "from repro.core import HMSConfig\n"
        "from repro.resilience import ValidationError\n"
        "try:\n"
        "    HMSConfig(footprint=-5).validate()\n"
        "except ValidationError as e:\n"
        "    assert 'footprint' in str(e)\n"
        "    print('CAUGHT')\n"
    )
    out = subprocess.run([sys.executable, "-O", "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "CAUGHT" in out.stdout


def test_um_spec_validation():
    with pytest.raises(ValidationError, match="n_frames"):
        um.simulate_um_many(_rand_trace(5),
                            [um.UMSpec(n_frames=0, chunk=4)])


# ---------------------------------------------------------------------------
# Fault injection plumbing.
# ---------------------------------------------------------------------------

def test_fault_parse_rejects_garbage():
    with pytest.raises(ValueError, match="kind@N"):
        faults.parse("oom")
    with pytest.raises(ValueError, match="expected one of"):
        faults.parse("segv@3")
    with pytest.raises(ValueError, match="count from 1"):
        faults.parse("oom@0")
    specs = faults.parse("oom@3, stitch@7")
    assert [(s.kind, s.at) for s in specs] == [("oom", 3), ("stitch", 7)]


def test_inject_fires_once_at_exact_ordinal():
    with faults.inject("oom@2"):
        assert faults.on_call("t") == 1             # ordinal 1: clean
        with pytest.raises(InjectedFault) as ei:
            faults.on_call("t")                     # ordinal 2: fires
        assert ei.value.kind == "oom" and ei.value.seq == 2
        assert faults.on_call("t") == 3             # one-shot: clean again
        assert not faults.pending()
    assert not faults.active()                      # restored on exit


def test_nan_fault_corrupts_result_not_call():
    with faults.inject("nan@1"):
        seq = faults.on_call("t")                   # must NOT raise
        out = {"hits": np.float64(3.0), "misses": np.float64(1.0)}
        faults.corrupt("t", seq, out)
    assert np.isnan(out["hits"])                    # first sorted key
    with pytest.raises(CounterInvalidError, match="hits"):
        guard.check_finite(out)


# ---------------------------------------------------------------------------
# The guard: classification + ladder mechanics.
# ---------------------------------------------------------------------------

def test_classify_failure_mapping():
    assert guard.classify_failure(InjectedFault("oom", "s", 1)) == "oom"
    assert guard.classify_failure(tsplit.StitchError("x")) == "stitch"
    assert guard.classify_failure(CounterInvalidError("x")) == "nan"
    assert guard.classify_failure(MemoryError()) == "oom"
    assert guard.classify_failure(TimeoutError()) == "deadline"
    assert guard.classify_failure(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "oom"
    assert guard.classify_failure(
        RuntimeError("DEADLINE_EXCEEDED while compiling")) == "deadline"
    assert guard.classify_failure(KeyError("x")) is None
    assert guard.classify_failure(RuntimeError("unrelated")) is None


def test_ladder_retries_then_descends_then_exhausts():
    calls = []

    def flaky(name, fail_times):
        state = {"left": fail_times}

        def thunk():
            calls.append(name)
            if state["left"] > 0:
                state["left"] -= 1
                raise MemoryError("pressure")
            return name
        return thunk

    # one retry fixes rung A
    out, oc = guard.run_ladder("t", [("A", flaky("A", 1))], retries=1)
    assert out == "A" and oc.rung == "A" and oc.retries == 1
    assert [e["action"] for e in oc.events] == ["retry"]

    # rung A exhausts its budget, B succeeds
    out, oc = guard.run_ladder(
        "t", [("A", flaky("A", 3)), ("B", flaky("B", 0))], retries=1)
    assert out == "B" and oc.rung == "B" and oc.rung_index == 1
    assert [e["action"] for e in oc.events][-1] == "degrade"

    # everything fails -> structured exhaustion error
    with pytest.raises(ResilienceError, match="ladder exhausted") as ei:
        guard.run_ladder("t", [("A", flaky("A", 9)), ("B", flaky("B", 9))],
                         retries=0)
    assert len(ei.value.events) == 2
    assert isinstance(ei.value.__cause__, MemoryError)


def test_ladder_oom_hands_off_to_bisect():
    def boom():
        raise MemoryError("batch too wide")

    out, oc = guard.run_ladder("t", [("full", boom)],
                               bisect=lambda: "halves", retries=0)
    assert out == "halves" and oc.rung == "bisect"
    assert oc.events[0]["action"] == "bisect"


def test_ladder_passes_unclassified_and_interrupts_through():
    def keyerr():
        raise KeyError("not an engine failure")

    with pytest.raises(KeyError):
        guard.run_ladder("t", [("A", keyerr)])
    with faults.inject("kill@1"):
        with pytest.raises(KeyboardInterrupt):
            guard.run_ladder("t", [("A", lambda: 1)])


def test_guarded_call_checks_finiteness():
    with pytest.raises(ResilienceError):
        guard.guarded_call("t", lambda: {"c": np.float64("nan")},
                           retries=0)


# ---------------------------------------------------------------------------
# Fault parity: both engines, every fault class, digest-for-digest.
# ---------------------------------------------------------------------------

def _hms_digest_run(t, cfg, spec=None):
    obs.enable()
    try:
        obs.clear_records()
        ctx = faults.inject(spec) if spec else contextlib.nullcontext()
        with ctx, forced(2, 2, 16):
            r = simulate(t, cfg)
        rec = [x for x in obs.records() if x.engine == "hms"][-1]
    finally:
        obs.disable()
    return r, rec


@pytest.mark.parametrize("kind", ENGINE_FAULTS)
def test_hms_fault_parity(kind):
    """Every injected fault class degrades; counters never move."""
    t = _rand_trace(1)
    cfg = HMSConfig(footprint=t.footprint)
    base, brec = _hms_digest_run(t, cfg)
    got, rec = _hms_digest_run(t, cfg, f"{kind}@1")
    assert rec.counter_digest == brec.counter_digest
    assert rec.degradations, "ledger must record the degradation walk"
    assert rec.degradations[0]["kind"] == kind
    for k in base.counters:
        np.testing.assert_array_equal(got.counters[k], base.counters[k], k)


def test_hms_ladder_reaches_reference(monkeypatch):
    """With retries off and OOM on every engine rung, the scan lands on
    the frozen reference — still bit-identical."""
    monkeypatch.setenv("REPRO_RETRY", "0")
    t = _rand_trace(2)
    cfg = HMSConfig(footprint=t.footprint)
    base, brec = _hms_digest_run(t, cfg)
    # rungs under forced(2,2): S2T2, S2T1, S1T1, reference
    got, rec = _hms_digest_run(t, cfg, "oom@1,oom@2,oom@3")
    assert rec.ladder_rung == "reference"
    assert rec.counter_digest == brec.counter_digest
    assert [e["action"] for e in rec.degradations] == ["degrade"] * 3


def _um_digest_run(t, specs, spec=None):
    from repro.um.engine import _RESULT_CACHE
    _RESULT_CACHE.pop(t, None)                  # memoized results bypass
    obs.enable()
    try:
        obs.clear_records()
        ctx = faults.inject(spec) if spec else contextlib.nullcontext()
        with ctx, forced(None, 2, 16):
            rs = um.simulate_um_many(t, specs)
        rec = [x for x in obs.records() if x.engine == "um"][-1]
    finally:
        obs.disable()
    return rs, rec


@pytest.mark.parametrize("kind", ENGINE_FAULTS)
def test_um_fault_parity(kind):
    t = _rand_trace(3)
    specs = [um.UMSpec(n_frames=48, chunk=4),
             um.UMSpec(n_frames=48, chunk=4, nvlink=True)]
    base, brec = _um_digest_run(t, specs)
    got, rec = _um_digest_run(t, specs, f"{kind}@1")
    assert rec.counter_digest == brec.counter_digest
    assert rec.degradations and rec.degradations[0]["kind"] == kind
    for b, g in zip(base, got):
        np.testing.assert_array_equal(g.phase_faults, b.phase_faults)
        np.testing.assert_array_equal(g.phase_migrated, b.phase_migrated)


def test_um_ladder_reaches_reference(monkeypatch):
    monkeypatch.setenv("REPRO_RETRY", "0")
    t = _rand_trace(4)
    specs = [um.UMSpec(n_frames=48, chunk=4)]    # single lane: no bisect
    base, brec = _um_digest_run(t, specs)
    got, rec = _um_digest_run(t, specs, "oom@1,oom@2")
    assert rec.ladder_rung == "reference"
    assert rec.counter_digest == brec.counter_digest


def test_um_bisect_leaves_the_process_32_bit():
    """A bisecting UM batch re-enters its own entry point; the engines'
    64-bit scope must still close when the outer call returns."""
    import jax
    t = _rand_trace(5)
    specs = [um.UMSpec(n_frames=48, chunk=4),
             um.UMSpec(n_frames=48, chunk=4, nvlink=True)]
    base, brec = _um_digest_run(t, specs)
    got, rec = _um_digest_run(t, specs, "oom@1,oom@2")   # retry, bisect
    assert rec.ladder_rung == "bisect"
    assert rec.counter_digest == brec.counter_digest
    assert not jax.config.jax_enable_x64


def test_hms_batch_bisects_on_oom_bit_exact():
    t = _rand_trace(6)
    cfgs = [HMSConfig(footprint=t.footprint, ctc_ways=w)
            for w in (2, 4, 8, 16)]
    with forced(2, 1):
        base = simulate_many(t, cfgs)
        with faults.inject("oom@1,oom@2"):       # retry, then bisect
            got = simulate_many(t, cfgs)
    for b, g in zip(base, got):
        for k in b.counters:
            np.testing.assert_array_equal(g.counters[k], b.counters[k], k)


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 10**6),
           kind=st.sampled_from(ENGINE_FAULTS),
           at=st.integers(1, 2))
    def test_fault_parity_property(seed, kind, at):
        t = _rand_trace(seed % 7, n=3000)
        cfg = HMSConfig(footprint=t.footprint)
        base, brec = _hms_digest_run(t, cfg)
        got, rec = _hms_digest_run(t, cfg, f"{kind}@{at}")
        assert rec.counter_digest == brec.counter_digest
else:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_fault_parity_property(seed):
        t = _rand_trace(seed % 7, n=3000)
        cfg = HMSConfig(footprint=t.footprint)
        kind = ENGINE_FAULTS[seed % len(ENGINE_FAULTS)]
        base, brec = _hms_digest_run(t, cfg)
        got, rec = _hms_digest_run(t, cfg, f"{kind}@{seed % 2 + 1}")
        assert rec.counter_digest == brec.counter_digest


# ---------------------------------------------------------------------------
# Resumable sweep checkpoints.
# ---------------------------------------------------------------------------

def test_counter_encoding_round_trips_float64_bit_exact():
    C = {"a": np.float64(1.0) / 3.0,
         "b": np.asarray([1e-300, 7.1, np.pi], np.float64),
         "c": np.float64(2**53 + 1.0)}
    enc = json.loads(json.dumps(sweepckpt.encode_counters(C)))
    dec = sweepckpt.decode_counters(enc)
    for k in C:
        a = np.asarray(C[k], np.float64)
        b = np.asarray(dec[k], np.float64)
        assert a.tobytes() == b.tobytes(), k    # bitwise, not approx


def test_checkpoint_journal_and_resume(tmp_path):
    t = _rand_trace(7)
    cfg = HMSConfig(footprint=t.footprint)
    tfp = sweepckpt.trace_fingerprint(t)
    ck = sweepckpt.SweepCheckpoint(str(tmp_path))
    C = {"hits": np.float64(10.0), "misses": np.float64(2.0)}
    assert ck.get_hms(tfp, cfg, False) is None
    ck.put_hms(tfp, cfg, False, C)
    ck.close()
    # torn tail line from a mid-write kill is skipped on load
    with open(os.path.join(str(tmp_path), "sweep_ckpt.jsonl"), "a") as f:
        f.write('{"kind": "hms", "trace": "x"')
    ck2 = sweepckpt.SweepCheckpoint(str(tmp_path))
    got = ck2.get_hms(tfp, cfg, False)
    assert got is not None
    assert np.asarray(got["hits"]).tobytes() == \
        np.asarray(C["hits"]).tobytes()
    assert ck2.get_hms(tfp, cfg, True) is None   # nvlink flips the digest
    ck2.close()


def test_kill_and_resume_sweep_is_bit_exact(tmp_path):
    """The CI chaos contract in miniature: a killed sweep journals its
    finished groups; resuming against the same checkpoint dir replays
    them and completes with counters bit-identical to an uninterrupted
    run."""
    t = _rand_trace(8)
    cfgs = [HMSConfig(footprint=t.footprint),
            HMSConfig(footprint=t.footprint, tag_layout="tad"),
            HMSConfig(footprint=t.footprint, policy="mccache"),
            HMSConfig(footprint=t.footprint, policy="always_cache")]
    with forced(1, 1):
        base = simulate_many(t, cfgs)            # uninterrupted reference

        sweepckpt.enable(str(tmp_path))
        try:
            with faults.inject("kill@3"):        # dies in the third group
                with pytest.raises(KeyboardInterrupt):
                    simulate_many(t, cfgs)
            journaled = sweepckpt.active().stats()["entries"]
            assert 0 < journaled < len(cfgs)
            resumed = sweepckpt.enable(str(tmp_path))   # reload journal
            got = simulate_many(t, cfgs)
            assert resumed.stats()["hits"] == journaled
        finally:
            sweepckpt.disable()
    for b, g in zip(base, got):
        for k in b.counters:
            np.testing.assert_array_equal(g.counters[k], b.counters[k], k)


def test_um_checkpoint_replays_specs(tmp_path):
    from repro.um.engine import _RESULT_CACHE
    t = _rand_trace(9)
    spec = um.UMSpec(n_frames=48, chunk=4)
    sweepckpt.enable(str(tmp_path))
    try:
        _RESULT_CACHE.pop(t, None)
        base = um.simulate_um_many(t, [spec])[0]
        assert sweepckpt.active().stats()["puts"] == 1
        ck = sweepckpt.enable(str(tmp_path))     # fresh journal load
        _RESULT_CACHE.pop(t, None)               # drop in-process memo too
        got = um.simulate_um_many(t, [spec])[0]
        assert ck.stats()["hits"] == 1           # served from disk
    finally:
        sweepckpt.disable()
    np.testing.assert_array_equal(got.phase_faults, base.phase_faults)
    np.testing.assert_array_equal(got.phase_writebacks,
                                  base.phase_writebacks)


# ---------------------------------------------------------------------------
# Ledger + benchmark plumbing.
# ---------------------------------------------------------------------------

def test_run_record_round_trips_resilience_fields():
    rec = obs.RunRecord(
        entry="simulate", engine="hms", trace="t", n=10, phases=1,
        engine_key="k", compiled=False, wall_s=0.1, batch=1,
        counter_digest="d", ladder_rung="S1T1", retries=2,
        degradations=[{"site": "hms", "kind": "oom", "rung": "S2T2",
                       "attempt": 0, "action": "degrade", "error": "x"}])
    d = json.loads(json.dumps(rec.to_dict()))
    back = obs.RunRecord.from_dict(d)
    assert back.ladder_rung == "S1T1" and back.retries == 2
    assert back.degradations[0]["kind"] == "oom"
    # schema-1 ledgers (and future fields) load with the new fields None
    old = {k: v for k, v in d.items()
           if k not in ("ladder_rung", "retries", "degradations")}
    old["future_field"] = 1
    assert obs.RunRecord.from_dict(old).ladder_rung is None


def test_partial_registry_flushes_best_effort(tmp_path):
    from benchmarks import common
    p1 = str(tmp_path / "a.json")

    def good():
        with open(p1, "w") as f:
            json.dump({"partial": True}, f)
        return p1

    def bad():
        raise OSError("disk gone")

    common.register_partial("good", good)
    common.register_partial("bad", bad)
    try:
        written = common.flush_partials()
    finally:
        common.unregister_partial("good")
        common.unregister_partial("bad")
    assert written == [p1]
    assert json.load(open(p1))["partial"] is True


def test_compare_treats_resilience_keys_as_info():
    from benchmarks.compare import diff_artifacts
    old = {"w": {"counter_digest": "abc", "ladder_rung": "S4T2",
                 "retries": 0}}
    new = {"w": {"counter_digest": "abc", "ladder_rung": "reference",
                 "retries": 2, "partial": True}}
    model, timing, info = diff_artifacts(old, new)
    assert model == []                           # rung/retry drift is info
    assert len(info) == 3
    new["w"]["counter_digest"] = "xyz"
    model, _, _ = diff_artifacts(old, new)
    assert model and "counter_digest" in model[0]

"""The per-layer metrics that read the engine-call spans and the staged
bytes, and the spans' place on the profiler's host timeline."""

from __future__ import annotations

import time
import types

import pytest

import _chipbench_util as u
from chipbench import devtrace, harness

MS = 1_000_000
NEW = ("request_stream_share", "engine_host_share", "obs_record_share",
       "engine_input_mb_per_study")


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def _ctx(spans, records=(), studies=2, window_s=0.5):
    return harness.Context(cell=None, window_s=window_s,
                           studies=[None] * studies, spans=list(spans),
                           records=list(records))


def _rec(**kw):
    return types.SimpleNamespace(ladder_rung="S1T1", **kw)


def test_span_shares_on_a_synthetic_window():
    spans = [("preprocess", 0, 200 * MS), ("request_stream", 10 * MS,
                                           190 * MS),
             ("engine_inputs", 200 * MS, 210 * MS),
             ("scan", 210 * MS, 400 * MS),
             ("engine_dispatch", 210 * MS, 215 * MS),
             ("engine_wait", 215 * MS, 395 * MS),
             ("engine_readback", 395 * MS, 400 * MS),
             ("reduce_counters", 400 * MS, 420 * MS),
             ("obs_record", 420 * MS, 421 * MS),
             ("request_stream", 500 * MS, 680 * MS)]
    ctx = _ctx(spans, window_s=1.0)
    assert _read("request_stream_share", ctx) == pytest.approx(36.0)
    # inputs + dispatch + read-back + reduction; the wait is left out
    assert _read("engine_host_share", ctx) == pytest.approx(4.0)
    assert _read("obs_record_share", ctx) == pytest.approx(0.1)


def test_input_mb_per_study_sums_engine_records():
    recs = [_rec(input_bytes=3_000_000), _rec(input_bytes=1_000_000),
            # a memoized UM call ran no engine and carries no rung
            types.SimpleNamespace(ladder_rung=None, input_bytes=7)]
    assert _read("engine_input_mb_per_study",
                 _ctx([], recs, studies=2)) == pytest.approx(2.0)


def test_a_program_without_the_spans_reads_nothing():
    """The parent program opens none of these spans and its records have
    no ``input_bytes``: every new reader returns None and raises
    nothing."""
    old = [("preprocess", 0, 100 * MS), ("scan", 100 * MS, 300 * MS)]
    recs = [_rec(), types.SimpleNamespace(ladder_rung="S1T1",
                                          input_bytes=None)]
    ctx = _ctx(old, recs)
    assert [_read(n, ctx) for n in NEW] == [None] * len(NEW)


def test_spans_sit_on_the_profiler_host_timeline():
    """Under ``jax.profiler`` on the CPU backend the host plane holds one
    annotation per obs span, of the same name; moved by the harness's
    study-start offset, each obs span lies within 1 ms of it."""
    import glob
    import shutil
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from repro import obs
    from repro.core.traces import Trace

    cell = harness.load_cell(u.UM)
    entry = harness.load_module("entries", cell.config["entry"])
    gen = harness.load_module("generators", cell.traffic["generator"])
    params = {**cell.traffic["params"], "n": 2000}
    col, is_write = gen.generate(5, **params)

    def study():
        entry.study(Trace("bfs_tu", col, is_write, int(params["footprint"])),
                    cell.config["base"], cell.points)

    study()                                 # compile outside the trace
    log_dir = tempfile.mkdtemp(prefix="spans_trace_")
    opts = jax.profiler.ProfileOptions()    # the harness's options
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.clear_events()
    obs.enable()
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.STUDY):
            t0 = time.perf_counter_ns()
            study()
        jax.profiler.stop_trace()
        evs = [(n, s, s + d) for n, s, d, _, _ in obs.events()]
    finally:
        obs.disable()
        obs.clear_events()
    names = {n for n, _, _ in evs}
    assert {"engine_inputs", "um_scan", "engine_dispatch", "engine_wait",
            "engine_readback", "obs_record"} <= names

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    host = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name in names | {devtrace.STUDY}]
    shutil.rmtree(log_dir, ignore_errors=True)
    (st,) = [e for e in host if e[0] == devtrace.STUDY]
    off = st[1] - t0
    for name in names:
        obs_side = sorted((s + off, e + off) for n, s, e in evs if n == name)
        ann = sorted((s, e) for n, s, e in host if n == name)
        assert len(ann) == len(obs_side), name
        for (s0, e0), (s1, e1) in zip(obs_side, ann):
            assert abs(s0 - s1) < MS and abs(e0 - e1) < MS, name


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_traced_run_reports_the_engine_span_metrics(cell):
    r, _, _ = u.run_small(cell, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    want = set(NEW) if cell == u.HMS else set(NEW) - {"request_stream_share"}
    assert want <= set(m)
    for name in want - {"engine_input_mb_per_study"}:
        assert 0 < m[name]["value"] < 100, name
    if cell == u.HMS:
        assert m["request_stream_share"]["value"] <= \
            m["preprocess_share"]["value"]
    else:
        assert "request_stream_share" not in m
    # the issued traffic at the test size: 12 HMS lanes of slot, meta and
    # pos words (the UM call: its page, write and phase streams, once)
    lo = (12 * u.SMALL_N[cell] * 16 if cell == u.HMS
          else u.SMALL_N[cell] * 9)
    assert lo <= m["engine_input_mb_per_study"]["value"] * 1e6 < 2 * lo

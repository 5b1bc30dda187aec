"""The trace reduction: busy union, module time, top operations and idle
gaps labelled by the host span open in them."""

from __future__ import annotations

import json
import os

import pytest

import _chipbench_util  # noqa: F401  (puts chipbench on sys.path)
from chipbench import devtrace

MS = 1_000_000


def _synthetic():
    dev = "/device:TPU:0"
    ops = [("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 15 * MS, 30 * MS),
           ("while.3", 60 * MS, 90 * MS), ("fusion.1", 95 * MS, 100 * MS)]
    modules = [("jit_fn(12)", 10 * MS, 30 * MS),
               ("jit_counting(7)", 60 * MS, 90 * MS),
               ("jit_fn(12)", 95 * MS, 100 * MS)]
    studies = [(devtrace.STUDY, 0, 50 * MS), (devtrace.STUDY, 55 * MS,
                                              120 * MS)]
    return devtrace.DeviceTrace({dev: ops}, {dev: modules}, studies)


def test_merge_and_clip():
    assert devtrace.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]
    assert devtrace.clip([(0, 4), (5, 10)], 3, 6) == [(3, 4), (5, 6)]


def test_window_busy_and_modules():
    t = _synthetic()
    w0, w1 = devtrace.window(t)
    assert (w0, w1) == (0, 120 * MS)
    # union of [10,30], [60,90], [95,100] ms
    assert devtrace.busy_seconds(t, w0, w1) == pytest.approx(0.055)
    assert devtrace.module_seconds(t, r"^jit_fn(\W|$)", w0, w1) == \
        pytest.approx(0.025)
    assert devtrace.module_seconds(t, r"^jit_counting(\W|$)", w0, w1) == \
        pytest.approx(0.030)
    assert devtrace.module_seconds(t, r"^jit_fn(\W|$)", 0, 20 * MS) == \
        pytest.approx(0.010)


def test_module_time_leaves_out_idle_inside_the_module():
    dev = "/device:TPU:0"
    ops = [("while.3", 60 * MS, 70 * MS), ("fusion.4", 80 * MS, 90 * MS)]
    t = devtrace.DeviceTrace({dev: ops}, {dev: [("jit_counting(7)", 55 * MS,
                                                 90 * MS)]}, [])
    assert devtrace.module_seconds(t, r"^jit_counting", 0, 120 * MS) == \
        pytest.approx(0.020)
    assert devtrace.module_seconds(t, r"^jit_counting", 65 * MS,
                                   85 * MS) == pytest.approx(0.010)
    # a plane that records no operations: the module's own span
    t.ops = {}
    assert devtrace.module_seconds(t, r"^jit_counting", 0, 120 * MS) == \
        pytest.approx(0.035)


def test_top_ops():
    top = devtrace.top_ops(_synthetic(), 0, 120 * MS, k=2)
    assert top == [["while.3", pytest.approx(0.030)],
                   ["fusion.1", pytest.approx(0.015)]]


def test_idle_gaps_are_labelled_by_the_open_span():
    t = _synthetic()
    spans = [("preprocess", 0, 9 * MS), ("scan", 9 * MS, 31 * MS),
             ("postprocess", 31 * MS, 50 * MS),
             ("um_scan", 58 * MS, 94 * MS)]
    gaps = devtrace.idle_gaps(t, 0, 120 * MS, spans)
    # gaps: [0,10] [30,60] [90,95] [100,120]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010,
                                                  0.005])
    assert [g[0] for g in gaps] == ["postprocess", "harness", "preprocess",
                                    "um_scan"]
    t.studies = [(devtrace.STUDY, 0, 20 * MS)]
    assert devtrace.idle_gaps(t, 0, 120 * MS, [], k=1) == [
        ["between_studies", pytest.approx(0.030)]]


def test_an_empty_trace_reads_nothing():
    t = devtrace.DeviceTrace({}, {}, [])
    assert devtrace.window(t) is None
    assert devtrace.busy_seconds(t, 0, 1) == 0.0
    assert devtrace.idle_gaps(t, 0, 1, []) == []


# A profiler trace recorded on a TPU v5 lite: one study of the HMS cell at
# 400 requests, with the obs spans of that study (ns from its start).
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "hms_n400.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE.replace(".xplane.pb.gz", ".spans.json")) as f:
        spans = json.load(f)["spans_from_study_start_ns"]
    return devtrace.load(FIXTURE), spans


def test_recorded_trace_reduces(recorded):
    t, spans = recorded
    assert [s[0] for s in t.studies] == [devtrace.STUDY]
    (plane,) = t.ops
    assert plane.startswith("/device:TPU:")
    w0, w1 = devtrace.window(t)
    busy = devtrace.busy_seconds(t, w0, w1)
    engine = devtrace.module_seconds(t, r"^jit_fn(\W|$)", w0, w1)
    # the study's one device program: its module spans its operations
    assert engine == pytest.approx(busy, rel=1e-3)
    assert 0 < busy < (w1 - w0) / 1e9
    names = [n for n, _ in devtrace.top_ops(t, w0, w1)]
    assert names and all(" = " not in n for n in names)
    on_trace = [(n, w0 + s, w0 + e) for n, s, e in spans]
    gaps = devtrace.idle_gaps(t, w0, w1, on_trace)
    assert gaps and sum(g for _, g in gaps) <= (w1 - w0) / 1e9 - busy + 1e-9
    assert {label for label, _ in gaps} <= {n for n, _, _ in spans} | {
        "harness"}


def test_metric_readers_on_the_recorded_trace(recorded):
    import types

    from chipbench import harness

    t, _ = recorded
    rec = types.SimpleNamespace(engine="hms", n=400, depth=400, t_segments=1,
                                replay_prefix=0, stitch_rounds=1,
                                ladder_rung="S1T1")
    ctx = harness.Context(cell=None, window_s=0.017, studies=[None],
                          spans=[], records=[rec], device=t,
                          device_window=devtrace.window(t))
    w0, w1 = ctx.device_window
    us = harness.load_module("metrics", "hms_scan_us_per_step").read(ctx)
    assert us == pytest.approx(
        devtrace.module_seconds(t, r"^jit_fn", w0, w1) / 400 * 1e6)
    idle = harness.load_module("metrics", "device_idle_share").read(ctx)
    assert idle == pytest.approx(
        100 * (1 - devtrace.busy_seconds(t, w0, w1) / ((w1 - w0) / 1e9)))
    assert 0 < idle < 100
    assert harness.load_module("metrics", "um_scan_us_per_step").read(
        ctx) is None

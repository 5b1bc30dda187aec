"""One run of one benchmark cell: set-up, the timed window, the traced
reading of the per-layer metrics, and the check against the reference.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic mix in ``traffic/<traffic>.json``, the generator the mix names in
``generators/<generator>.py``, the program entry the configuration names
in ``entries/<entry>.py`` and each per-layer metric in
``metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import itertools
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import List, Optional

from chipbench import devtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def points(self) -> List[dict]:
        """The design points of one study: the product of the traffic's
        grid axes, in the order the file gives them."""
        axes = self.traffic["grid"]
        return [dict(zip(axes, vals))
                for vals in itertools.product(*axes.values())]

    @property
    def n_requests(self) -> int:
        return int(self.traffic["params"]["n"])

    @property
    def footprint(self) -> int:
        return int(self.traffic["params"]["footprint"])


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, imported by path (names may hold
    dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == name]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(os.path.join(HERE, "configs",
                                       w["config"] + ".json")),
        traffic=_read_json(os.path.join(HERE, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if name in m["workloads"]])


@dataclasses.dataclass
class Study:
    start_ns: int                       # host perf_counter_ns
    end_ns: int
    answers: Optional[list]             # per design point, None if raised
    error: Optional[str] = None


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads."""

    cell: Cell
    window_s: float                     # host clock, window start to end
    studies: List[Study]
    spans: list                         # obs spans in the window (host ns)
    records: list                       # obs run records of the window
    device: Optional[devtrace.DeviceTrace] = None
    device_window: Optional[tuple] = None   # (w0, w1) on the trace clock


def lane_steps(rec) -> int:
    """Sequential device steps one engine record executed: its per-lane
    scan length (shard depth for HMS, trace length for UM, cut into
    ``t_segments`` temporal segments plus their replay prefix) times its
    stitch rounds."""
    length = rec.depth if rec.depth else rec.n
    t = rec.t_segments or 1
    lane = -(-length // t) + ((rec.replay_prefix or 0) if t > 1 else 0)
    return lane * (rec.stitch_rounds or 1)


def engine_records(records) -> list:
    """Records of calls that ran the degradation ladder (a UM call served
    from memoized results carries no rung)."""
    return [r for r in records if r.ladder_rung is not None]


def failed_points(records) -> int:
    """Design points whose engine record left its planned rung, degraded,
    retried or bisected."""
    bad = 0
    for r in engine_records(records):
        if (r.ladder_rung in ("reference", "bisect") or r.degradations
                or r.retries):
            bad += r.um_lanes_requested if r.engine == "um" else r.batch
    return bad


def _gap(a: float, b: float) -> float:
    if a == b or (math.isnan(a) and math.isnan(b)):
        return 0.0
    if b == 0.0:
        return math.inf
    return abs(a - b) / abs(b)


def compare(studies: List[Study], ref: list):
    """``(values_compared, values_differing, max_rel_gap)`` of every
    answer of every study against the reference's.  A study that raised
    counts every value of its points as differing, and so does a study
    that answered another number of points than the reference."""
    n_vals = sum(len(p) for p in ref)
    compared = differing = 0
    worst = 0.0
    for st in studies:
        compared += n_vals
        if st.answers is None or len(st.answers) != len(ref):
            differing += n_vals
            worst = math.inf
            continue
        for got, want in zip(st.answers, ref):
            if set(got) != set(want):
                differing += len(want)
                worst = math.inf
                continue
            for k, v in want.items():
                g = _gap(got[k], v)
                differing += g != 0.0
                worst = max(worst, g)
    return compared, differing, worst


# limits of the numbers compared (see PERF.md, "How correct is decided")
LIMITS = {"values_differing": 0}


@contextlib.contextmanager
def host_cpu():
    """Run JAX's work on the host's CPU backend, in every thread (the
    reference spreads its design points over threads, which do not see a
    thread's ``jax.default_device``)."""
    import jax

    old = jax.config.jax_default_device
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        yield
    finally:
        jax.config.update("jax_default_device", old)


def _say(out, **kv):
    print("[chipbench] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=out, flush=True)


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _read_trace(ctx: Context, log_dir: str, device: dict):
    """Load the window's profiler trace into ``ctx``, put the device's busy
    and window seconds into ``device`` and return the breakdown.  The obs
    spans are moved onto the profiler's clock by the offset between each
    study's start and its annotation."""
    dt = devtrace.load(devtrace.find_xplane(log_dir))
    ctx.device = dt
    win = devtrace.window(dt)
    if win is None or len(dt.studies) != len(ctx.studies):
        return None
    ctx.device_window = win
    off = statistics.median(
        a[1] - st.start_ns for a, st in zip(dt.studies, ctx.studies))
    spans = [(n, s + off, e + off) for n, s, e in ctx.spans]
    device["busy_s"] = devtrace.busy_seconds(dt, *win)
    device["window_s"] = (win[1] - win[0]) / 1e9
    return {"device_ops": devtrace.top_ops(dt, *win),
            "idle_gaps": devtrace.idle_gaps(dt, *win, spans)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start_ns: int, root: str = ROOT, out=sys.stdout,
             err=sys.stderr, devices=None,
             traffic_params: Optional[dict] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``t_start_ns`` is the process start on the ``perf_counter_ns`` clock.
    ``devices`` are the chips the cell runs on (JAX's default devices when
    None).  ``traffic_params`` override the traffic's sizes; the tests
    shrink them to what a test run holds."""
    import jax

    from repro import obs
    from repro.core.traces import Trace

    devices = devices or jax.devices()[:1]
    cell = load_cell(name, root)
    if traffic_params:
        cell.traffic["params"] = {**cell.traffic["params"], **traffic_params}
    cfg = cell.config
    entry = load_module("entries", cfg["entry"])
    gen = load_module("generators", cell.traffic["generator"])
    col, is_write = gen.generate(seed, **cell.traffic["params"])
    base = cfg["base"]
    points = cell.points

    def fresh():
        return Trace(cell.traffic["trace_name"], col, is_write,
                     cell.footprint)

    def one_study() -> Study:
        t0 = time.perf_counter_ns()
        try:
            ans = entry.answers(entry.study(fresh(), base, points))
            e = None
        except Exception as ex:         # a study that raised has no answer
            ans, e = None, f"{type(ex).__name__}: {ex}"
        return Study(t0, time.perf_counter_ns(), ans, e)

    # ---- set-up: one study compiles (or loads) every engine shape --------
    obs.enable()
    setup = one_study()
    _say(out, setup_study_s=(setup.end_ns - setup.start_ns) / 1e9,
         setup_compiles=sum(1 for r in obs.records() if r.compiled))
    if setup.error:
        raise RuntimeError(f"set-up study raised: {setup.error}")
    obs.clear_records()
    obs.clear_events()

    # ---- the window: whole studies, back to back --------------------------
    # A traced run traces one study: the device records every operation of
    # the scan loop, and one study of the HMS cell (about 4 M events) is
    # what its buffers hold without dropping any.
    studies: List[Study] = []
    w0 = time.perf_counter_ns()
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        w0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(devtrace.STUDY):
            studies.append(one_study())
        jax.profiler.stop_trace()
    else:
        while time.perf_counter_ns() - w0 < seconds * 1e9:
            studies.append(one_study())
    w1 = studies[-1].end_ns
    peak = _peak_bytes(devices)
    records = obs.records()
    spans = [(n, t0, t0 + d) for n, t0, d, _, _ in obs.events()]
    obs.disable()
    obs.clear_records()
    obs.clear_events()

    window_s = (w1 - w0) / 1e9
    attempted = len(points) * len(studies)
    # design points answered: a study that raised, or answered another
    # number of points than it was given, completed none
    completed = sum(len(points) for st in studies
                    if st.answers is not None
                    and len(st.answers) == len(points))
    failed = failed_points(records) + sum(
        len(points) for st in studies if st.error)
    compiles = sum(1 for r in records if r.compiled)
    dev0 = devices[0]
    _say(out, device_platform=dev0.platform, device_kind=repr(dev0.device_kind),
         device_count=len(devices))
    _say(out, compiles_in_window=compiles)
    for r in engine_records(records)[:len(engine_records(records))
                                     // max(1, len(studies))]:
        _say(out, engine=r.engine, planned_S=r.shards,
             planned_T=r.t_segments, depth=r.depth, batch=r.batch,
             rung=r.ladder_rung)
    _say(out, studies_completed=len(studies), window_s=window_s,
         setup_s=(w0 - t_start_ns) / 1e9, peak_bytes_in_use=peak)
    for st in studies:
        if st.error:
            _say(out, study_error=repr(st.error))

    # ---- metrics -----------------------------------------------------------
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": attempted, "failed": failed}
    breakdown = None
    if trace:
        ctx = Context(cell, window_s, studies, spans, records)
        breakdown = _read_trace(ctx, log_dir, device)
        shutil.rmtree(log_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "sim_req_per_s": completed * cell.n_requests / window_s,
            "setup_s": (w0 - t_start_ns) / 1e9,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # ---- correctness: every study's answers against the reference ---------
    # the program's device state is no longer needed; the reference runs
    # on the host's CPU backend, after the peak was read
    t_ref = time.perf_counter()
    with host_cpu():
        ref = entry.reference(col, is_write, cell.footprint, base, points)
    compared, differing, worst = compare(studies, ref)
    _say(out, reference_s=time.perf_counter() - t_ref,
         values_compared=compared, max_rel_gap=worst)
    checks = {"values_differing": {"value": differing,
                                   "limit": LIMITS["values_differing"]}}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}={c['value']} limit={c['limit']}", file=err,
              flush=True)
    return result

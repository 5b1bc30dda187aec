"""The harness finds each cell's files by name, computes its metrics from
a tiny study on the CPU, and refuses to measure without a chip."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

import _chipbench_util as u
from chipbench import harness


def _bench():
    with open(os.path.join(u.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_loads_by_name():
    bench = _bench()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.points, w["name"]
        harness.load_module("entries", cell.config["entry"])
        harness.load_module("generators", cell.traffic["generator"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(u.ROOT, c["file"]))
    for m in bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_grids_are_the_issued_design_points():
    hms = harness.load_cell(u.HMS).points
    assert len(hms) == 12
    assert {(p["tag_layout"], p["ctc_fraction"], p["scm_mode"])
            for p in hms} == {(a, c, m) for a in ("amil", "tad")
                              for c in (0.25, 0.0625)
                              for m in ("slc", "mlc", "tlc")}
    um = harness.load_cell(u.UM).points
    assert len(um) == 8
    assert {(p["rel_footprint"], p["nvlink"]) for p in um} == {
        (r, nv) for r in (1.25, 1.5, 2.0, 4.0) for nv in (False, True)}


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_untraced_run_reports_end_to_end_metrics(cell):
    r, out, err = u.run_small(cell)
    assert r["correct"] is True, out
    assert r["failed"] == 0
    points = len(harness.load_cell(cell).points)
    assert r["attempted"] > 0 and r["attempted"] % points == 0
    m = r["metrics"]
    assert set(m) == {"sim_req_per_s", "setup_s"}
    studies = r["attempted"] // points
    window = studies * points * u.SMALL_N[cell] / m["sim_req_per_s"]["value"]
    assert 0.05 <= window < 60
    assert m["setup_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["values_differing"] == {"value": 0, "limit": 0}
    assert "compiles_in_window=0" in out
    assert "check values_differing=0 limit=0" in err


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_traced_run_reports_per_layer_counts(cell):
    """A traced run traces one study.  On the CPU the trace has no device
    plane: the device metrics stay out of the line, the span and counter
    metrics are read."""
    r, out, _ = u.run_small(cell, trace=True)
    assert r["correct"] is True
    assert r["attempted"] == len(harness.load_cell(cell).points)
    m = r["metrics"]
    # one engine record, unsplit: its shard depth (the trace length when
    # it has none)
    (rec,) = re.findall(r"planned_T=1 depth=(\w+)", out)
    depth = u.SMALL_N[cell] if rec == "None" else int(rec)
    assert m["scan_steps_per_study"]["value"] == depth
    assert "device_idle_share" not in m
    assert not any(k.endswith("_us_per_step") for k in m)
    if cell == u.HMS:
        assert 0 < m["preprocess_share"]["value"] < 100
    else:
        assert "preprocess_share" not in m
    assert r["device"]["window_s"] > 0
    assert r["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_lane_steps_follow_the_plan():
    class Rec:
        engine, n, depth, t_segments, replay_prefix, stitch_rounds = (
            "hms", 1000, 250, 4, 16, 3)
    assert harness.lane_steps(Rec) == (63 + 16) * 3
    Rec.depth, Rec.t_segments, Rec.stitch_rounds = None, 1, None
    assert harness.lane_steps(Rec) == 1000


def test_without_a_chip_it_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", u.HMS,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=u.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr

"""``score_streams_per_study``: the distinct HMS score streams of the
window's engine records, per study."""

from __future__ import annotations

import types

import pytest

import _chipbench_util as u
from chipbench import harness


def _read(records, studies):
    ctx = harness.Context(cell=None, window_s=1.0, studies=[None] * studies,
                          spans=[], records=list(records))
    return harness.load_module("metrics", "score_streams_per_study").read(ctx)


def _rec(rung="S1T1", **kw):
    return types.SimpleNamespace(ladder_rung=rung, **kw)


@pytest.mark.parametrize("counts,studies,want", [
    ([3], 1, 3.0),
    ([3, 3, 3], 3, 3.0),
    ([1, 1, 1, 1, 1], 1, 5.0),
    ([2, 1], 2, 1.5),
])
def test_reads_the_records_sum_over_studies(counts, studies, want):
    recs = [_rec(score_streams=c) for c in counts]
    assert _read(recs, studies) == pytest.approx(want)


def test_records_without_a_count_are_left_out():
    recs = [_rec(score_streams=3),
            # a bisected batch and the reference rung ran no engine
            _rec(rung="bisect", score_streams=None),
            _rec(rung="reference", score_streams=None),
            # a memoized UM call carries no rung
            types.SimpleNamespace(ladder_rung=None, score_streams=9)]
    assert _read(recs, 1) == pytest.approx(3.0)


@pytest.mark.parametrize("recs", [
    [_rec(), _rec()],                              # the parent's records
    [_rec(score_streams=None)],                    # UM records
    [],
], ids=["no_field", "none", "no_records"])
def test_reads_nothing_where_the_records_lack_the_count(recs):
    assert _read(recs, 2) is None


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_traced_run_reports_score_streams_per_study(cell):
    r, _, _ = u.run_small(cell, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    if cell == u.HMS:
        # one score stream per SCM mode of the 12-point grid
        assert m["score_streams_per_study"]["value"] == 3
    else:
        # the UM engine computes no HMS request stream
        assert "score_streams_per_study" not in m

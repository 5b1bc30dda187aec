"""Spans and counters on the two engine call paths.

The contracts under test:

  * a batched ``simulate_many`` and a ``simulate_um_many`` call emit the
    named spans with their nesting (by containment on one thread):
    ``request_stream`` inside ``preprocess``; ``engine_dispatch``,
    ``engine_wait`` and ``engine_readback`` inside ``scan`` / ``um_scan``;
    ``engine_inputs``, ``reduce_counters`` and ``obs_record`` beside them,
  * a record's ``input_bytes`` is the ``nbytes`` of the arrays its engine
    call staged,
  * the engines compile as modules ``jit_fn`` (HMS) and ``jit_counting``
    (UM), the names the device-trace readers match.
"""

import re

import jax
import numpy as np
import pytest

from repro import obs, um
from repro.core import HMSConfig, make_trace, simulate, simulate_many
from repro.core import simulator as sim_mod
from repro.um import engine as um_mod

N = 3000


@pytest.fixture
def spans():
    obs.clear_records()
    obs.clear_events()
    obs.enable()
    yield
    obs.disable()
    obs.clear_records()
    obs.clear_events()


def _study(trace):
    cfgs = [HMSConfig(footprint=trace.footprint, ctc_fraction=f)
            for f in (0.25, 0.0625)]
    simulate_many(trace, cfgs)
    specs = [um.um_spec(HMSConfig(footprint=trace.footprint, r_hbm=1 / r,
                                  organization="hbm"), nvlink=nv)
             for r in (1.5, 2.0) for nv in (False, True)]
    um.simulate_um_many(trace, specs)


def _inside(child, parent) -> bool:
    _, c0, cd, ctid, _ = child
    _, p0, pd, ptid, _ = parent
    return ctid == ptid and p0 <= c0 and c0 + cd <= p0 + pd


def _parents(ev, events, names):
    return [p for p in events if p[0] in names and _inside(ev, p)]


def test_engine_calls_emit_nested_spans(spans):
    _study(make_trace("bfs_tu", n=N))
    evs = obs.events()
    by = {}
    for e in evs:
        by.setdefault((e[0], e[4].get("engine")), []).append(e)
    for name in ("request_stream", "engine_inputs", "engine_dispatch",
                 "engine_wait", "engine_readback", "reduce_counters",
                 "obs_record"):
        assert (name, "hms") in by, name
    for name in ("engine_inputs", "engine_dispatch", "engine_wait",
                 "engine_readback", "obs_record"):
        assert (name, "um") in by, name
    (rs,) = by[("request_stream", "hms")]
    assert _parents(rs, evs, {"preprocess"})
    for eng, scan in (("hms", "scan"), ("um", "um_scan")):
        for name in ("engine_dispatch", "engine_wait", "engine_readback"):
            for e in by[(name, eng)]:
                assert _parents(e, evs, {scan}), (name, eng)
        # staging, reduction and the record sit outside the scan span
        for name in ("engine_inputs", "reduce_counters", "obs_record"):
            for e in by.get((name, eng), []):
                assert not _parents(e, evs, {scan}), (name, eng)
    # dispatch, wait and read-back follow each other within one scan
    d, w, r = (by[(n, "hms")][0] for n in (
        "engine_dispatch", "engine_wait", "engine_readback"))
    assert d[1] + d[2] <= w[1] and w[1] + w[2] <= r[1]


def _capture(monkeypatch, module, factory):
    """Wrap ``module.factory`` so each engine call records its jitted
    function and arguments."""
    calls = []
    real_factory = getattr(module, factory)

    def wrapped_factory(key):
        real = real_factory(key)

        def call(*args):
            calls.append((real, args))
            return real(*args)
        return call

    monkeypatch.setattr(module, factory, wrapped_factory)
    return calls


def _nbytes(args) -> int:
    return sum(np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(args))


def test_input_bytes_is_what_the_call_staged(spans, monkeypatch):
    hms_calls = _capture(monkeypatch, sim_mod, "_engine_for")
    um_calls = _capture(monkeypatch, um_mod, "_engine_for")
    _study(make_trace("bfs_tu", n=N))
    (h,) = hms_calls
    (u,) = um_calls
    recs = {r.engine: r for r in obs.records()}
    assert recs["hms"].input_bytes == _nbytes(h[1]) > 0
    assert recs["um"].input_bytes == _nbytes(u[1]) > 0
    # one engine call's arrays: per HMS lane at least slot, meta, pos
    assert recs["hms"].input_bytes >= 2 * N * (4 + 8 + 4)


def _module_name(fn, args) -> str:
    with jax.enable_x64(True):
        text = fn.lower(*args).as_text(dialect="hlo")
    return re.match(r"HloModule (\w+)", text).group(1)


def test_engine_module_names_are_pinned(monkeypatch):
    """The device-trace readers find the engines by module name
    (``hms_scan_us_per_step``: ``jit_fn``, ``um_scan_us_per_step``:
    ``jit_counting``); a rename would silence them."""
    hms_calls = _capture(monkeypatch, sim_mod, "_engine_for")
    um_calls = _capture(monkeypatch, um_mod, "_engine_for")
    trace = make_trace("bfs_tu", n=N)
    _study(trace)
    simulate(trace, HMSConfig(footprint=trace.footprint, ctc_fraction=0.5))
    many, single = hms_calls
    assert _module_name(*many) == "jit_fn"
    assert _module_name(*single) == "jit_fn"
    assert _module_name(*um_calls[0]) == "jit_counting"

"""``correct`` comes out false when the reference's control or a fault of
the timed path takes the program's place."""

from __future__ import annotations

import types

import pytest

import _chipbench_util as u
from chipbench import harness


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_control_in_lower_precision_is_not_correct(cell):
    """The reference with float32 counters (HMS) or 8-bit access counters
    (UM) in the program's place, at the test size, on three seeds."""
    c = harness.load_cell(cell)
    entry = harness.load_module("entries", c.config["entry"])
    gen = harness.load_module("generators", c.traffic["generator"])
    for seed in (1, 2, 3):
        col, wr = gen.generate(seed, **{**c.traffic["params"],
                                        "n": u.SMALL_N[cell]})
        args = (col, wr, c.footprint, c.config["base"], c.points)
        ref = entry.reference(*args)
        ctl = entry.reference(*args, control=True)
        _, differing, _ = harness.compare([harness.Study(0, 0, ctl)], ref)
        assert differing > harness.LIMITS["values_differing"], seed


def _frozen_scan(real_scan):
    """``lax.scan`` whose step hands its carry on unchanged."""
    def scan(f, init, xs=None, length=None, **kw):
        return real_scan(lambda c, x: (c, f(c, x)[1]), init, xs, length, **kw)
    return scan


@pytest.fixture
def fresh_engines(monkeypatch):
    from repro.core import simulator
    from repro.um import engine

    for mod, names in ((simulator, ("_ENGINE_CACHE", "_BATCHED_CACHE")),
                       (engine, ("_UM_ENGINE_CACHE",))):
        for n in names:
            monkeypatch.setattr(mod, n, {})
    return simulator, engine


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_state_left_unchanged_is_not_correct(cell, fresh_engines,
                                             monkeypatch):
    import jax

    lax = types.SimpleNamespace(**{k: getattr(jax.lax, k)
                                   for k in dir(jax.lax)
                                   if not k.startswith("__")})
    lax.scan = _frozen_scan(jax.lax.scan)
    proxy = types.SimpleNamespace(**{k: getattr(jax, k) for k in dir(jax)
                                     if not k.startswith("__")})
    proxy.lax = lax
    mod = fresh_engines[0] if cell == u.HMS else fresh_engines[1]
    monkeypatch.setattr(mod, "jax", proxy)
    assert u.run_small(cell)[0]["correct"] is False


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_half_the_design_points_left_out_is_not_correct(cell, monkeypatch):
    """Once with the rest duplicated in place of the half left out, once
    with only the half computed returned."""
    import repro.core
    import repro.um

    mod, name = ((repro.core, "simulate_many") if cell == u.HMS
                 else (repro.um, "simulate_um_many"))
    real = getattr(mod, name)

    def duplicated(trace, points, *a, **kw):
        h = len(points) // 2
        done = real(trace, points[:h], *a, **kw)
        return done + done[:len(points) - h]

    def dropped(trace, points, *a, **kw):
        return real(trace, points[:len(points) // 2], *a, **kw)

    for half in (duplicated, dropped):
        monkeypatch.setattr(mod, name, half)
        r, _, _ = u.run_small(cell)
        assert r["correct"] is False, half.__name__
        assert r["checks"]["values_differing"]["value"] > 0
    # a study that answered half its points completed none of them
    assert r["metrics"]["sim_req_per_s"]["value"] == 0


@pytest.mark.parametrize("cell", [u.HMS, u.UM])
def test_answer_altered_where_produced_is_not_correct(cell, fresh_engines,
                                                      monkeypatch):
    import numpy as np

    simulator, engine = fresh_engines
    if cell == u.HMS:
        real = simulator._reduce_counters

        def reduce(trace, cfg, rs, y):
            C = real(trace, cfg, rs, y)
            return {**C, "fills": C["fills"] + np.float64(1.0)}

        monkeypatch.setattr(simulator, "_reduce_counters", reduce)
    else:
        real = engine._engine_for

        def engine_for(key):
            fn = real(key)

            def altered(*a):
                C = dict(fn(*a))
                C["um_faults"] = C["um_faults"] + 1
                return C
            return altered

        monkeypatch.setattr(engine, "_engine_for", engine_for)
    r, _, _ = u.run_small(cell)
    assert r["correct"] is False

"""Entry ``simulate_many``: one batched HMS study per call.

A design point is a dict of ``HMSConfig`` fields over the configuration's
``base``; the trace's footprint sets the capacities.  The answers of a
point are everything a user reads off its ``SimResult``: the counters, the
runtime and its bottleneck terms, traffic, hit rates, energy and power.
The reference is the frozen seed engine (``chipbench.reference.hms``),
with the counters in float64; its control runs it in float32.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.reference import hms as ref_hms
from chipbench.reference.timing import RefConfig

# the reference runs one design point per thread; XLA's CPU backend runs
# the scans side by side
_REF_THREADS = 12


def study(trace, base: dict, points: list) -> list:
    from repro.core import HMSConfig, simulate_many

    return simulate_many(trace, [
        HMSConfig(**{**base, **pt}, footprint=trace.footprint) for pt in points])


def _flat(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update({f"{k}.{kk}": float(vv) for kk, vv in v.items()})
        else:
            out[k] = float(v)
    return out


def answers(results: list) -> list:
    return [_flat({
        "runtime_cycles": r.runtime_cycles, "terms": r.terms,
        "counters": r.counters, "traffic_bytes": r.traffic_bytes,
        "hit_rate_read": r.hit_rate_read, "hit_rate_write": r.hit_rate_write,
        "ctc_hit_rate": r.ctc_hit_rate, "bypass_l1_frac": r.bypass_l1_frac,
        "energy_pj": r.energy_pj, "power_w": r.power_w,
    }) for r in results]


def reference(col, is_write, footprint: int, base: dict, points: list,
              control: bool = False) -> list:
    cfgs = [RefConfig(**{**base, **pt}, footprint=footprint) for pt in points]
    fdtype = np.float32 if control else np.float64
    with ThreadPoolExecutor(min(_REF_THREADS, len(cfgs))) as pool:
        per_point = list(pool.map(
            lambda c: ref_hms.counters(col, is_write, [c], fdtype)[0], cfgs))
    return [_flat(ref_hms.finish(c, C, col.shape[0]))
            for c, C in zip(cfgs, per_point)]

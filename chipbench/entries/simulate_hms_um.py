"""Entry ``simulate_hms_um``: one batched HMS study whose design points the
footprint may oversubscribe.

The study is ``simulate_many``'s (``entries/simulate_many.py``) over the
PCIe link: the program pages the overflowing points' excess in one UM
call before the HMS scan.  The answers of a point are ``simulate_many``'s
51 values with the HMS counters alone under ``counters``, and beside them
its four paging counters (0 where the stack holds the footprint).  The
reference composes the frozen HMS scan with the frozen paging scan
(``chipbench.reference.oversub``); its control sizes the paging frames by
the HBM capacity, not the stack's.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

from chipbench.harness import load_module
from chipbench.reference import oversub
from chipbench.reference.timing import RefConfig

_many = load_module("entries", "simulate_many")
study = _many.study

# one design point per thread, as in ``simulate_many``'s reference
_REF_THREADS = 12


def answers(results: list) -> list:
    out = []
    for a in _many.answers(results):
        um = {k: a.pop(f"counters.{k}", 0.0) for k in oversub.UM_KEYS}
        out.append({**a, **um})
    return out


def reference(col, is_write, footprint: int, base: dict, points: list,
              control: bool = False) -> list:
    cfgs = [RefConfig(**{**base, **pt}, footprint=footprint) for pt in points]
    with ThreadPoolExecutor(min(_REF_THREADS, len(cfgs))) as pool:
        per_point = list(pool.map(
            lambda c: oversub.point(col, is_write, c, control), cfgs))
    return [_many._flat(p) for p in per_point]

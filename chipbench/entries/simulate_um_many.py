"""Entry ``simulate_um_many``: one batched UM paging study per call.

A design point is a relative footprint (footprint / HBM capacity) and a
link mode: fault-driven chunked migration, or NVLink access-counter
migration.  The answers of a point are the four paging counters the
program returns.  The reference is the frozen seed paging scan
(``chipbench.reference.um``) with int32 access counters; its control
holds them in 8 bits.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.reference import um as ref_um
from chipbench.reference.timing import UM_PAGE_BYTES, RefConfig

_REF_THREADS = 8
_KEYS = ("um_faults", "um_migrated", "um_writebacks", "um_remote_cols")


def _hbm(base: dict, pt: dict, footprint: int) -> dict:
    return {**base, "r_hbm": 1.0 / pt["rel_footprint"],
            "footprint": footprint}


def study(trace, base: dict, points: list) -> list:
    from repro import um
    from repro.core import HMSConfig

    return um.simulate_um_many(trace, [
        um.um_spec(HMSConfig(**_hbm(base, pt, trace.footprint)),
                   nvlink=pt["nvlink"]) for pt in points])


def answers(results: list) -> list:
    return [{"um_faults": r.faults, "um_migrated": r.migrated,
             "um_writebacks": r.writebacks, "um_remote_cols": r.remote_cols}
            for r in results]


def reference(col, is_write, footprint: int, base: dict, points: list,
              control: bool = False) -> list:
    hot_dtype = np.int8 if control else np.int32

    def one(pt):
        cfg = RefConfig(**_hbm(base, pt, footprint))
        out = ref_um.counters(
            col, is_write, max(1, cfg.hbm_capacity // UM_PAGE_BYTES),
            1 if pt["nvlink"] else cfg.um_prefetch_pages, pt["nvlink"],
            cfg.um_hot_threshold, hot_dtype)
        return {k: float(v) for k, v in zip(_KEYS, out)}

    with ThreadPoolExecutor(min(_REF_THREADS, len(points))) as pool:
        return list(pool.map(one, points))

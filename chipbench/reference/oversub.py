"""Plain reference of an HMS stack that the footprint oversubscribes.

Where the trace's footprint exceeds what the stack holds (the DRAM cache
plus the SCM in its effective cell mode), the excess lives in host memory
and Unified Memory pages it in over the host link: fault-driven migration
of ``um_prefetch_pages``-page chunks into as many 4 KiB frames as the stack
holds.  The paging adds link bytes and serialized fault cycles to the HMS
point's runtime, traffic and energy.

Copies of the program's overflow rule (``simulator._um_overflow_config``),
its frame and chunk mapping (``um.um_spec``) and its link-byte and
fault-cycle arithmetic (``um.UMResult.link_bytes``,
``simulator._um_fault_cycles``), composed with the frozen HMS scan
(``reference.hms``) and the frozen paging scan (``reference.um``).  It
imports nothing of the program.

The control sizes the frames by the HBM capacity alone, as the plain
HBM + UM baseline does, instead of by the stack's capacity.
"""

from __future__ import annotations

import numpy as np

from . import hms as ref_hms
from . import um as ref_um
from .timing import COLUMN_BYTES, UM_PAGE_BYTES, RefConfig

UM_KEYS = ("um_faults", "um_migrated", "um_writebacks", "um_remote_cols")


def capacity(cfg: RefConfig) -> int:
    """Bytes the stack holds: the DRAM cache plus the SCM in the cell mode
    that sets its density."""
    return cfg.scm_capacity + cfg.dram_cache_capacity


def um_frames(cfg: RefConfig, control: bool = False) -> int:
    """Resident 4 KiB frames of the paging scan: the stack's capacity,
    taken as the program takes it (an HBM ratio of capacity over
    footprint, times the footprint); the HBM capacity with ``control``."""
    if control:
        return max(1, cfg.hbm_capacity // UM_PAGE_BYTES)
    r_hbm = capacity(cfg) / cfg.footprint
    return max(1, int(cfg.footprint * r_hbm) // UM_PAGE_BYTES)


def paging(col: np.ndarray, is_write: np.ndarray, cfg: RefConfig,
           control: bool = False):
    """``(um counters, link bytes, fault cycles)`` of one design point over
    PCIe; zeros where the stack holds the footprint."""
    if cfg.footprint <= capacity(cfg):
        return dict.fromkeys(UM_KEYS, 0.0), 0.0, 0.0
    faults, migrated, writebacks, remote = (float(v) for v in ref_um.counters(
        col, is_write, um_frames(cfg, control), cfg.um_prefetch_pages,
        False))
    link_bytes = ((migrated + writebacks) * UM_PAGE_BYTES
                  + remote * COLUMN_BYTES)
    fault_cycles = faults * cfg.fault_latency_ns / cfg.fault_overlap
    return (dict(zip(UM_KEYS, (faults, migrated, writebacks, remote))),
            link_bytes, fault_cycles)


def point(col: np.ndarray, is_write: np.ndarray, cfg: RefConfig,
          control: bool = False) -> dict:
    """What a user reads off one design point (``hms.finish``) with its
    paging counters beside it."""
    C = ref_hms.counters(col, is_write, [cfg])[0]
    um, link_bytes, fault_cycles = paging(col, is_write, cfg, control)
    return {**ref_hms.finish(cfg, C, col.shape[0], link_bytes, fault_cycles),
            **um}

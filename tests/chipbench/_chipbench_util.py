"""Shared set-up of the chip benchmark's tests: the repository root on
``sys.path`` (for ``chipbench``) and a CPU run of one cell at a size a
test run holds."""

from __future__ import annotations

import io
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

HMS = "hms.sweep12.bfs_tu"
UM = "hbm_um.grid8.bfs_tu"
# requests per trace in a test run: enough that the control's float32
# counters (HMS) and 8-bit access counters (UM) move the answers
SMALL_N = {HMS: 10000, UM: 12000}


def run_small(cell: str, *, seed: int = 20251017,
              seconds: float = 0.05, trace: bool = False):
    """One run of ``cell`` on the CPU at ``SMALL_N`` requests, without the
    harness's look for a chip, planning from the committed cost constants
    as ``run.py`` does: ``(result, stdout, stderr)``."""
    import jax

    from repro.core import costmodel

    out, err = io.StringIO(), io.StringIO()
    old = costmodel.set_calib_mode("off")
    try:
        result = harness.run_cell(
            cell, seed, seconds, trace, t_start_ns=time.perf_counter_ns(),
            root=ROOT, out=out, err=err, devices=jax.devices("cpu")[:1],
            traffic_params={"n": SMALL_N[cell]})
    finally:
        costmodel.set_calib_mode(old)
    return result, out.getvalue(), err.getvalue()

"""Arithmetic shared by the per-layer metric readers."""

from chipbench import devtrace
from chipbench.harness import engine_records, lane_steps


def scan_us_per_step(ctx, engine: str, module: str):
    """Device seconds of the modules matching ``module`` in the traced
    window, per sequential step the window's ``engine`` records ran, in
    microseconds.  None where the trace or the records have none."""
    if ctx.device is None or ctx.device_window is None:
        return None
    steps = sum(lane_steps(r) for r in engine_records(ctx.records)
                if r.engine == engine)
    secs = devtrace.module_seconds(ctx.device, module, *ctx.device_window)
    if steps == 0 or secs <= 0.0:
        return None
    return secs / steps * 1e6

"""Device time of the HMS engine's compiled program per sequential scan
step, from the profiler trace of the window."""

from chipbench.metrics_common import scan_us_per_step

# the jitted, vmapped HMS engine (``simulator._batched_engine_for`` /
# ``_engine_for``) compiles as module ``jit_fn``
MODULE = r"^jit_fn(\W|$)"


def read(ctx):
    return scan_us_per_step(ctx, "hms", MODULE)

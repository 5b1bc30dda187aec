"""Device time of the UM paging engine's compiled program per sequential
scan step, from the profiler trace of the window."""

from chipbench.metrics_common import scan_us_per_step

# the jitted, vmapped UM engine (``um.engine._engine_for``) compiles as
# module ``jit_counting``
MODULE = r"^jit_counting(\W|$)"


def read(ctx):
    return scan_us_per_step(ctx, "um", MODULE)

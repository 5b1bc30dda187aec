"""Share of the window the host spent staging an engine call and reading
it back: building the device inputs (``engine_inputs``), dispatching the
jitted call (``engine_dispatch``), copying its outputs to the host
(``engine_readback``) and reducing them to counters
(``reduce_counters``).  The wait on the device (``engine_wait``) is left
out."""

from chipbench.metrics_spans import span_share

SPANS = ("engine_inputs", "engine_dispatch", "engine_readback",
         "reduce_counters")


def read(ctx):
    return span_share(ctx, SPANS)

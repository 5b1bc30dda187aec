"""Share of the window the host spent in the program's ``request_stream``
spans: the per-config policy stream (scores, the Python float64 penalty
EMA, levels) ahead of the HMS scan.  The part of ``preprocess_share`` that
is not ``traces.preprocess``."""

from chipbench.metrics_spans import span_share


def read(ctx):
    return span_share(ctx, ("request_stream",))

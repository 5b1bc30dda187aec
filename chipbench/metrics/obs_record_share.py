"""Share of the window the host spent building and emitting the
program's run records (``obs_record`` spans: the counter digest, the
trace fingerprint, the encoded counters): what observability itself costs
on the path the window times."""

from chipbench.metrics_spans import span_share


def read(ctx):
    return span_share(ctx, ("obs_record",))

"""Share of the window the host spent in the program's ``preprocess``
spans: trace preprocessing and the per-config request stream (scores, the
penalty EMA, levels) ahead of the HMS scan."""


def read(ctx):
    spans = [e - s for n, s, e in ctx.spans if n == "preprocess"]
    if not spans or ctx.window_s <= 0:
        return None
    return 100.0 * sum(spans) / 1e9 / ctx.window_s

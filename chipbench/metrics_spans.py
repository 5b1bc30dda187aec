"""Shares of the window that the program's own spans held, for the
per-layer metrics that read them."""


def span_share(ctx, names):
    """Host time of the obs spans named in ``names``, summed, as a
    percentage of the window.  None where the window holds no such span
    (a program that does not open them)."""
    secs = [e - s for n, s, e in ctx.spans if n in names]
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * sum(secs) / 1e9 / ctx.window_s

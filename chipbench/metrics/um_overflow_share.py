"""Share of the window the host spent in the program's ``um_overflow``
spans: paging in, in one UM call ahead of the HMS scan, the design points
whose footprint overflows the stack.  None where the program opens no
such span (no point overflowed, or a program without it)."""

from chipbench.metrics_spans import span_share


def read(ctx):
    return span_share(ctx, ("um_overflow",))

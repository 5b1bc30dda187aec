"""HMS design points per study whose footprint overflowed the stack into
UM paging: the run records' ``overflow_points`` (the points one UM call
paged for ``simulate_many``), summed over the window and divided by the
studies.  None where the records carry no such count."""


def read(ctx):
    counts = [getattr(r, "overflow_points", None) for r in ctx.records]
    counts = [c for c in counts if c is not None]
    if not counts or not ctx.studies:
        return None
    return sum(counts) / len(ctx.studies)

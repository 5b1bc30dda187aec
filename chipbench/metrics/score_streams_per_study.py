"""Distinct HMS score streams the host computed per study: the run
records' ``score_streams`` (the streams one engine call's configs needed,
configs with equal score inputs sharing one), summed over the window and
divided by the studies.  None where the records carry no such count."""

from chipbench.harness import engine_records


def read(ctx):
    counts = [getattr(r, "score_streams", None)
              for r in engine_records(ctx.records)]
    counts = [c for c in counts if c is not None]
    if not counts or not ctx.studies:
        return None
    return sum(counts) / len(ctx.studies)

"""Sequential device scan steps per study: for each engine record, its
per-lane scan length times its stitch rounds, summed over the window and
divided by the studies.  Set by the planner's (S, T) choice and the shard
plan's depth."""

from chipbench.harness import engine_records, lane_steps


def read(ctx):
    recs = engine_records(ctx.records)
    if not recs or not ctx.studies:
        return None
    return sum(lane_steps(r) for r in recs) / len(ctx.studies)

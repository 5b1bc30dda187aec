"""Megabytes of host arrays the engine calls of one study stage to the
device: the run records' ``input_bytes`` (inputs plus runtime parameters
of one engine call), summed over the window and divided by the studies."""

from chipbench.harness import engine_records


def read(ctx):
    staged = [getattr(r, "input_bytes", None)
              for r in engine_records(ctx.records)]
    staged = [b for b in staged if b is not None]
    if not staged or not ctx.studies:
        return None
    return sum(staged) / len(ctx.studies) / 1e6

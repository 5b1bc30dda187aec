"""Readings behind the limits of the correctness check, for one cell.

    python chipbench/readings.py --workload <cell> --seeds 1 2 3

For each seed: the program's answers (one study through the cell's entry
on the chip, at the cell's size) and the control's (the reference in the
lower precision) are each compared with the reference.  Prints one JSON
line per seed with ``values_differing`` and ``max_rel_gap`` of both.  The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    os.environ["REPRO_CALIB"] = "off"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from chipbench import harness
    from repro.core.traces import Trace
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = harness.load_cell(args.workload, ROOT)
    entry = harness.load_module("entries", cell.config["entry"])
    gen = harness.load_module("generators", cell.traffic["generator"])
    base, points = cell.config["base"], cell.points
    for seed in args.seeds:
        col, wr = gen.generate(seed, **cell.traffic["params"])
        trace = Trace(cell.traffic["trace_name"], col, wr, cell.footprint)
        prog = harness.Study(0, 0, entry.answers(
            entry.study(trace, base, points)))
        with harness.host_cpu():
            ref = entry.reference(col, wr, cell.footprint, base, points)
            ctl = harness.Study(0, 0, entry.reference(
                col, wr, cell.footprint, base, points, control=True))
        row = {"seed": seed}
        for who, st in (("program", prog), ("control", ctl)):
            _, differing, gap = harness.compare([st], ref)
            row[who] = {"values_differing": differing, "max_rel_gap": gap}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

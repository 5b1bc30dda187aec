"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  The last line of standard output is one JSON object: whether
the answers matched the reference (``correct``), the design points
attempted and failed, the metrics (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), the device, and,
last, each number compared beside its limit (``checks``).  Exits non-zero,
and prints no such line, when JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()     # set-up is timed from here

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the reference runs on the host's CPU backend beside the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    # the planner prices (S, T) from committed constants only
    os.environ["REPRO_CALIB"] = "off"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from chipbench import harness

    cell = harness.load_cell(args.workload, ROOT)

    import jax

    devices = jax.devices()
    print(f"[chipbench] jax_ready_s={(time.perf_counter_ns() - T_START_NS) / 1e9}",
          flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(f"[chipbench] compile_cache={enable_compile_cache()}", flush=True)
    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start_ns=T_START_NS, root=ROOT, devices=devices[:cell.chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

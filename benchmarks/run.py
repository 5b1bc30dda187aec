"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call is simulator/kernel
wall time where meaningful, 0.0 for derived-metric rows) and writes the full
detail to benchmarks/artifacts/results.json.

Every suite runs under ``obs.assert_no_retrace()`` — a warm engine
silently recompiling mid-suite fails the run.  With ``REPRO_OBS_DIR`` set
(or ``obs.enable``), the run also streams a per-engine-invocation JSONL
ledger and exports a Chrome/Perfetto span trace next to it.

Interruption is a first-class outcome: SIGINT/SIGTERM (or an injected
``kill`` fault, see ``repro.resilience.faults``) flushes every suite's
in-progress BENCH_*.json (marked ``"partial": true``), a partial
results.json, and the obs ledger, then exits 130.  ``--resume`` activates
the sweep checkpoint (``REPRO_SWEEP_CKPT`` or
``benchmarks/artifacts/ckpt``), so re-running after an interruption
replays journaled engine results from disk and produces artifacts
bit-identical to an uninterrupted run.

Usage: PYTHONPATH=src python -m benchmarks.run [--resume] [figure ...]
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time


def _install_sigterm() -> None:
    """Route SIGTERM through KeyboardInterrupt so kill(1) and ctrl-C walk
    the same flush path (main thread only; harmless to skip elsewhere)."""

    def handler(signum, frame):
        raise KeyboardInterrupt(f"signal {signum}")

    try:
        signal.signal(signal.SIGTERM, handler)
    except (ValueError, OSError):
        pass


def main() -> int:
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.resilience import sweepckpt

    enable_compile_cache()

    from . import figures, kernel_bench, roofline, scenarios
    from . import um as um_bench
    from .common import emit, flush_partials

    args = sys.argv[1:]
    resume = "--resume" in args
    args = [a for a in args if a != "--resume"]
    if resume and sweepckpt.active() is None:
        ckpt_dir = os.environ.get("REPRO_SWEEP_CKPT") or os.path.join(
            os.path.dirname(__file__), "artifacts", "ckpt")
        sweepckpt.enable(ckpt_dir)
    ck = sweepckpt.active()
    if ck is not None:
        print(f"# ckpt: {ck.path} ({ck.stats()['entries']} journaled)")

    suites = {
        "fig11": figures.fig11_runtime,
        "fig12": figures.fig12_hitrate,
        "fig13": figures.fig13_traffic,
        "fig14": figures.fig14_bypass,
        "fig16": figures.fig16_linesize,
        "fig17": figures.fig17_footprint,
        "fig18": figures.fig18_ctc_ways,
        "fig19": figures.fig19_energy,
        "fig20": figures.fig20_throttle,
        "prior": figures.prior_traffic,
        "sweep": figures.sweep_design_space,
        "scenarios": scenarios.run,
        "um": um_bench.run,
        "kernels": kernel_bench.run,
        "roofline": roofline.run,
    }
    want = args or list(suites)
    results = {}
    t0 = time.time()
    art = os.path.join(os.path.dirname(__file__), "artifacts")
    _install_sigterm()
    print("name,us_per_call,derived")
    try:
        for name in want:
            with obs.assert_no_retrace(), obs.span("suite", suite=name):
                rows = suites[name](results)
            emit(rows)
    except KeyboardInterrupt as e:
        # flush what every in-flight suite has so far, then the partial
        # top-level artifact and the obs ledger — an interrupted run must
        # leave resumable state behind, not nothing
        results["partial"] = True
        written = flush_partials()
        os.makedirs(art, exist_ok=True)
        with open(os.path.join(art, "results.json"), "w") as f:
            json.dump(results, f, indent=1, default=str)
        written.append(os.path.join(art, "results.json"))
        print(f"# interrupted ({e}); partial artifacts: "
              + ", ".join(written))
        if obs.enabled() and obs.obs_dir():
            print(f"# obs: trace -> {obs.export_trace(obs.obs_dir())}")
        if ck is not None:
            st = ck.stats()
            print(f"# ckpt: {st['entries']} journaled "
                  f"({st['puts']} new) — rerun with --resume")
        return 130
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "results.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# total {time.time() - t0:.0f}s; "
          f"detail -> benchmarks/artifacts/results.json")
    if ck is not None:
        st = ck.stats()
        print(f"# ckpt: {st['hits']} replayed, {st['puts']} journaled")
    if obs.enabled():
        split = obs.compile_split()
        print(f"# obs: {split['runs']} engine runs "
              f"({split['compiled_runs']} compiled, "
              f"{split['compile_wall_s']:.1f}s compile / "
              f"{split['warm_wall_s']:.1f}s warm)"
              + (f"; ledger -> {obs.ledger_path()}"
                 if obs.ledger_path() else ""))
        out_dir = obs.obs_dir()
        if out_dir:
            print(f"# obs: trace -> {obs.export_trace(out_dir)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""§Perf hillclimb driver: compare lowering variants of one cell, or
execution variants of the HMS sweep engine.

Each named variant re-lowers the cell with different framework options and
reports the three roofline terms; the hypothesis -> change -> before/after
log lives in EXPERIMENTS.md §Perf.

    PYTHONPATH=src python -m benchmarks.perf_iterate \
        --arch grok-1-314b --shape train_4k \
        --variants baseline ep_moe no_sp naive_attn

``--hms-sweep`` instead hillclimbs the Track-A simulator: it runs the same
design-space sweep sequentially (per-config ``simulate``; any engine
compiles the sweep needs happen inside this timed leg, as they would for a
user iterating configs) and batched (``simulate_many``, one vmapped device
loop) and reports per-point wall time plus engine retrace counts.

    PYTHONPATH=src python -m benchmarks.perf_iterate \
        --hms-sweep --workload zipf --n 60000
"""

import argparse
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

VARIANTS = {
    "baseline": {},
    "ep_moe": {"moe_impl": "ep"},
    "no_sp": {"sequence_parallel": False},
    "no_remat": {"remat": False},
    "sp_barrier": {"sp_barrier": True},
    "grad_barrier": {"grad_barrier": True},
    "sp_prenorm": {"sp_prenorm": True},
    "pure_fsdp": {"pure_fsdp": True},
    "grad_shard": {"grad_shard": True},
    "pure_fsdp_gs": {"pure_fsdp": True, "grad_shard": True},
    "pure_fsdp_noremat": {"pure_fsdp": True, "remat": False},
    "sp_prenorm_gb": {"sp_prenorm": True, "grad_barrier": True},
    "ep_prenorm": {"sp_prenorm": True, "moe_impl": "ep"},
    "all_barriers": {"grad_barrier": True, "sp_barrier": True},
    "ep_sp_barrier": {"moe_impl": "ep", "sp_barrier": True},
    "kv_replicate": {"kv_mode": "replicate"},
    "kv_heads": {"kv_mode": "heads"},
    "kv_head_dim": {"kv_mode": "head_dim"},
    "no_moe_shard_map": {"moe_shard_map": False},
}


def terms(src):
    """Kernel-adjusted memory term (attention score intermediates live in
    VMEM under the Pallas kernels).  Raw (uncorrected) numbers — the
    bf16-wire correction is applied once, in the roofline report."""
    t_c = src["flops"] / PEAK_FLOPS
    bytes_k = max(src["bytes"] - src.get("attn_score_bytes", 0.0),
                  0.02 * src["bytes"])
    t_m = bytes_k / HBM_BW
    t_x = sum(src["collective_bytes"].values()) / LINK_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    return t_c, t_m, t_x, dom[0]


def hms_sweep(args):
    """Sequential vs batched execution of one design-space sweep."""
    import time

    from repro import obs
    from repro.core import (HMSConfig, costmodel, make_trace, simulate,
                            simulate_many)
    from repro.core.simulator import engine_trace_count, group_engine_key

    t = make_trace(args.workload, n=args.n)
    grid = [{"tag_layout": lay, "ctc_fraction": frac, "scm_mode": mode}
            for lay in ("amil", "tad")
            for frac in (0.25, 0.125, 0.0625)
            for mode in ("slc", "mlc", "tlc")]
    cfgs = [HMSConfig(footprint=t.footprint, **kw).validate() for kw in grid]

    out = {"points": len(grid), "workload": args.workload, "n": args.n}
    t0 = time.time()
    seq = [simulate(t, c) for c in cfgs]
    out["sequential_s"] = time.time() - t0
    t0 = time.time()
    bat = simulate_many(t, cfgs)
    out["batched_s"] = time.time() - t0
    out["speedup"] = out["sequential_s"] / max(out["batched_s"], 1e-9)
    out["engines_compiled"] = obs.cache_stats()["hms_engines"]
    out["traces_for_sweep_key"] = engine_trace_count(group_engine_key(t, cfgs))
    drift = max(abs(a.runtime_cycles - b.runtime_cycles)
                / max(a.runtime_cycles, 1.0) for a, b in zip(seq, bat))
    out["max_runtime_drift"] = drift
    # shard speedup: one warm config point, auto shard count vs the forced
    # S=1 sequential scan (the PR 2 execution shape)
    base = cfgs[0]
    key = group_engine_key(t, [base])
    simulate(t, base)
    t0 = time.time()
    simulate(t, base)
    out["single_auto_s"] = time.time() - t0
    old = costmodel.set_max_shards(1)
    try:
        simulate(t, base)
        t0 = time.time()
        simulate(t, base)
        out["single_s1_s"] = time.time() - t0
    finally:
        costmodel.set_max_shards(old)
    out["shards"] = key.shards
    out["shard_speedup"] = out["single_s1_s"] / max(out["single_auto_s"], 1e-9)
    print(f"hms-sweep {args.workload} n={args.n} points={len(grid)}: "
          f"sequential {out['sequential_s']:.1f}s "
          f"({out['sequential_s']/len(grid)*1e3:.0f}ms/pt), "
          f"batched {out['batched_s']:.1f}s "
          f"({out['batched_s']/len(grid)*1e3:.0f}ms/pt), "
          f"{out['speedup']:.1f}x, drift={drift:.2e}, "
          f"shards={out['shards']} "
          f"shard_speedup={out['shard_speedup']:.1f}x", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variants", nargs="+", default=["baseline"])
    ap.add_argument("--hms-sweep", action="store_true")
    ap.add_argument("--workload", default="zipf")
    ap.add_argument("--n", type=int, default=60_000)
    ap.add_argument("--json")
    args = ap.parse_args()

    if args.hms_sweep:
        hms_sweep(args)
        return
    if not (args.arch and args.shape):
        ap.error("--arch/--shape are required unless --hms-sweep is given")

    # fake-device mesh only matters for the lowering path; setting it for
    # --hms-sweep would skew the simulator timings vs benchmarks.run/tests
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

    from repro.launch.dryrun import lower_cell

    out = {}
    for name in args.variants:
        kw = VARIANTS[name]
        try:
            r = lower_cell(args.arch, args.shape, multi_pod=False,
                           probe=True, verbose=False, **kw)
            src = r["probe"]
            t_c, t_m, t_x, dom = terms(src)
            live = r["deploy"]["per_device_bytes"]["total_live"] / 2**30
            out[name] = {"t_compute": t_c, "t_memory": t_m,
                         "t_collective": t_x, "dominant": dom,
                         "live_gib": live,
                         "roofline_frac": t_c / max(t_c, t_m, t_x),
                         "collective_bytes": src["collective_bytes"]}
            print(f"{name:18s} tc={t_c:7.3f}s tm={t_m:7.3f}s "
                  f"tx={t_x:7.3f}s dom={dom:10s} live={live:6.1f}GiB "
                  f"roofline={t_c/max(t_c, t_m, t_x):.2f}", flush=True)
        except Exception as e:  # noqa: BLE001
            out[name] = {"error": repr(e)}
            print(f"{name:18s} FAILED: {e}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

"""One benchmark per paper table/figure (Track A simulator).

Each ``fig*`` function returns CSV rows (name, us_per_call, derived) where
``derived`` carries the figure's headline quantity, and appends full detail
to the shared results dict.
"""

from __future__ import annotations

import json
from typing import Dict, List

import numpy as np

from .common import WORKLOADS, emit, geomean, sim, sim_many


def fig11_runtime(results: Dict) -> List[tuple]:
    """Fig. 11: runtime of HBM(oversub) / SCM / HMS normalized to InfHBM."""
    rows = []
    detail = {}
    speedups = []
    for w in WORKLOADS:
        sim_many(w, [{"organization": o}
                     for o in ("inf_hbm", "hbm", "scm", "hms")])
        inf = sim(w, organization="inf_hbm")
        hbm = sim(w, organization="hbm")
        scm = sim(w, organization="scm")
        hms = sim(w, organization="hms")
        rel = {k: r.runtime_cycles / inf.runtime_cycles
               for k, r in [("hbm", hbm), ("scm", scm), ("hms", hms)]}
        detail[w] = rel
        speedups.append(rel["hbm"] / rel["hms"])
        rows.append((f"fig11.{w}", hms.wall_s * 1e6,
                     f"hms_rel={rel['hms']:.2f}|hbm_rel={rel['hbm']:.2f}"
                     f"|scm_rel={rel['scm']:.2f}"))
    results["fig11"] = detail
    rows.append(("fig11.overall", 0.0,
                 f"hms_over_hbm_speedup_geomean={geomean(speedups):.2f}x"
                 f"|max={max(speedups):.1f}x"))
    return rows


def fig12_hitrate(results: Dict) -> List[tuple]:
    rows = []
    detail = {}
    for w in WORKLOADS:
        sim_many(w, [{"policy": pol}
                     for pol in ("hms", "bear", "redcache", "mccache")])
        d = {}
        for pol in ("hms", "bear", "redcache", "mccache"):
            r = sim(w, policy=pol)
            d[pol] = {"read": r.hit_rate_read, "write": r.hit_rate_write}
        detail[w] = d
        rows.append((f"fig12.{w}", 0.0,
                     f"hms_w={d['hms']['write']:.2f}"
                     f"|bear_w={d['bear']['write']:.2f}"
                     f"|red_w={d['redcache']['write']:.2f}"))
    results["fig12"] = detail
    return rows


def fig13_traffic(results: Dict) -> List[tuple]:
    """Fig. 13: memory traffic rel. InfHBM for HMS / HMS-BP / HMS-BP-CTC."""
    rows = []
    detail = {}
    for w in WORKLOADS:
        sim_many(w, [{"organization": "inf_hbm"}, {},
                     {"policy": "no_bypass"},
                     {"policy": "no_bypass_no_ctc"}])
        base = sim(w, organization="inf_hbm").total_traffic
        t = {
            "hms": sim(w).total_traffic / base,
            "no_bypass": sim(w, policy="no_bypass").total_traffic / base,
            "no_bypass_no_ctc": sim(
                w, policy="no_bypass_no_ctc").total_traffic / base,
        }
        detail[w] = t
        rows.append((f"fig13.{w}", 0.0,
                     f"hms={t['hms']:.2f}|noBP={t['no_bypass']:.2f}"
                     f"|noBPnoCTC={t['no_bypass_no_ctc']:.2f}"))
    results["fig13"] = detail
    ov = {k: geomean(d[k] for d in detail.values())
          for k in ("hms", "no_bypass", "no_bypass_no_ctc")}
    rows.append(("fig13.overall", 0.0,
                 f"traffic_rel_geomean hms={ov['hms']:.2f}"
                 f"|noBP={ov['no_bypass']:.2f}"))
    return rows


def fig14_bypass(results: Dict) -> List[tuple]:
    rows = []
    detail = {}
    for w in WORKLOADS:
        r = sim(w)
        c = r.counters
        tot = max(1.0, c["bypass_l1"] + c["bypass_l2"] + c["fills"])
        detail[w] = {"l1_frac": r.bypass_l1_frac,
                     "bypass_frac": (c["bypass_l1"] + c["bypass_l2"]) / tot}
        rows.append((f"fig14.{w}", 0.0,
                     f"l1_frac={r.bypass_l1_frac:.2f}"))
    results["fig14"] = detail
    rows.append(("fig14.overall", 0.0,
                 f"l1_frac_mean="
                 f"{np.mean([d['l1_frac'] for d in detail.values()]):.2f}"))
    return rows


def fig16_linesize(results: Dict) -> List[tuple]:
    rows = []
    detail = {}
    for w in WORKLOADS:
        sim_many(w, [{"line_bytes": line}
                     for line in (64, 128, 256, 512, 1024)])
    for line in (64, 128, 256, 512, 1024):
        rel = []
        for w in WORKLOADS:
            r = sim(w, line_bytes=line)
            inf = sim(w, organization="inf_hbm")
            rel.append(r.runtime_cycles / inf.runtime_cycles)
        detail[str(line)] = geomean(rel)
        rows.append((f"fig16.line{line}", 0.0,
                     f"runtime_rel_infhbm={detail[str(line)]:.3f}"))
    results["fig16"] = detail
    return rows


def fig17_footprint(results: Dict) -> List[tuple]:
    """Fig. 17: HMS/HBM speedup vs relative footprint; SLC for small."""
    rows = []
    detail = {}
    grid = ((1.5, "slc"), (1.0, "slc"), (0.75, "mlc"),
            (0.5, "mlc"), (0.25, "tlc"))
    for w in WORKLOADS[:4]:
        sim_many(w, [{"r_hbm": r, "scm_mode": m} for r, m in grid]
                 + [{"r_hbm": r, "organization": "hbm"} for r, _ in grid])
    for r_hbm, mode in grid:
        sp = []
        for w in WORKLOADS[:4]:
            hms = sim(w, r_hbm=r_hbm, scm_mode=mode)
            hbm = sim(w, r_hbm=r_hbm, organization="hbm")
            sp.append(hbm.runtime_cycles / hms.runtime_cycles)
        detail[f"{r_hbm}:{mode}"] = geomean(sp)
        rows.append((f"fig17.rhbm{r_hbm}", 0.0,
                     f"mode={mode}|hms_speedup={geomean(sp):.2f}x"))
    results["fig17"] = detail
    return rows


def fig18_ctc_ways(results: Dict) -> List[tuple]:
    """Fig. 18: CTC capacity sweep, AMIL vs TAD probe traffic + runtime."""
    rows = []
    detail = {}
    for w in WORKLOADS[:5]:
        sim_many(w, [{"tag_layout": layout, "ctc_fraction": frac}
                     for layout in ("amil", "tad")
                     for frac in (0.25, 0.125, 0.0625)])
    for layout in ("amil", "tad"):
        for frac in (0.25, 0.125, 0.0625):
            rel, probes = [], []
            for w in WORKLOADS[:5]:
                r = sim(w, tag_layout=layout, ctc_fraction=frac)
                inf = sim(w, organization="inf_hbm")
                rel.append(r.runtime_cycles / inf.runtime_cycles)
                probes.append(r.traffic_bytes["dram_probe"])
            key = f"{layout}@{frac}"
            detail[key] = {"runtime_rel": geomean(rel),
                           "probe_bytes": float(np.mean(probes))}
            rows.append((f"fig18.{key}", 0.0,
                         f"runtime_rel={geomean(rel):.3f}"
                         f"|probeMiB={np.mean(probes)/2**20:.1f}"))
    amil1 = detail["amil@0.0625"]["probe_bytes"]
    tad1 = detail["tad@0.0625"]["probe_bytes"]
    rows.append(("fig18.overall", 0.0,
                 f"tad_vs_amil_probe_ratio={tad1/max(amil1,1):.1f}x"))
    results["fig18"] = detail
    return rows


def fig19_energy(results: Dict) -> List[tuple]:
    rows = []
    detail = {}
    savings = []
    for w in WORKLOADS:
        sim_many(w, [{"organization": "hbm"}, {},
                     {"organization": "scm"}])
        hbm = sum(sim(w, organization="hbm").energy_pj.values())
        hms = sum(sim(w).energy_pj.values())
        scm = sum(sim(w, organization="scm").energy_pj.values())
        detail[w] = {"hms_vs_hbm": 1 - hms / hbm, "hms_vs_scm": 1 - hms / scm}
        savings.append(1 - hms / hbm)
        rows.append((f"fig19.{w}", 0.0,
                     f"energy_saving_vs_hbm={100*(1-hms/hbm):.1f}%"))
    results["fig19"] = detail
    rows.append(("fig19.overall", 0.0,
                 f"mean_saving={100*np.mean(savings):.1f}%"
                 f"|max={100*max(savings):.1f}%"))
    return rows


def fig20_throttle(results: Dict) -> List[tuple]:
    rows = []
    detail = {}
    for w in ("stencil", "gpt_train"):
        base = sim(w)
        thr = sim(w, throttle_act=True, throttle_wr=True)
        hbm = sim(w, organization="hbm")
        detail[w] = {
            "power_base": base.power_w, "power_thr": thr.power_w,
            "runtime_ratio": thr.runtime_cycles / base.runtime_cycles,
            "still_beats_hbm": bool(thr.runtime_cycles
                                    < hbm.runtime_cycles),
        }
        rows.append((f"fig20.{w}", 0.0,
                     f"power {base.power_w:.2f}W->{thr.power_w:.2f}W"
                     f"|slowdown={detail[w]['runtime_ratio']:.2f}"
                     f"|beats_hbm={detail[w]['still_beats_hbm']}"))
    results["fig20"] = detail
    return rows


def prior_traffic(results: Dict) -> List[tuple]:
    """§IV-B / §VI: probe-traffic and SCM-write-traffic reduction vs
    BEAR_i / RedCache_i (paper: -91..93% probes, -57..75% SCM writes)."""
    rows = []
    probe_red, w_red = {}, {}
    for w in WORKLOADS:
        sim_many(w, [{}, {"policy": "no_bypass_no_ctc"}]
                 + [{"policy": p} for p in ("bear", "redcache", "mccache")])
    for prior in ("bear", "redcache", "mccache"):
        pr, wr = [], []
        for w in WORKLOADS:
            hms = sim(w)
            oth = sim(w, policy=prior)
            # prior-work ideal variants pay no probe traffic by assumption;
            # compare HMS probe traffic against the no-CTC probe volume the
            # prior design would issue through DRAM (paper's accounting).
            noctc = sim(w, policy="no_bypass_no_ctc")
            pr.append(hms.traffic_bytes["dram_probe"]
                      / max(1.0, noctc.traffic_bytes["dram_probe"]))
            hms_w = (hms.traffic_bytes["scm_demand"] * 0
                     + hms.counters["demand_scm_wr"]
                     + hms.counters["wb_scm_wr"])
            oth_w = (oth.counters["demand_scm_wr"]
                     + oth.counters["wb_scm_wr"])
            wr.append(hms_w / max(1.0, oth_w))
        probe_red[prior] = 1 - geomean(pr)
        w_red[prior] = 1 - geomean(wr)
        rows.append((f"prior.{prior}", 0.0,
                     f"probe_reduction={100*probe_red[prior]:.0f}%"
                     f"|scm_write_reduction={100*w_red[prior]:.0f}%"))
    results["prior"] = {"probe": probe_red, "writes": w_red}
    return rows


def sweep_design_space(results: Dict) -> List[tuple]:
    """Combined design-space sweep (TDRAM-style tag-organization study x
    SCM-mode sensitivity): tag layout x CTC capacity x SCM mode in ONE
    batched engine call per workload — the compile-once, shard-parallel
    path that makes Fig. 11/13/15/18-scale exploration cheap.

    Benchmarks the paper's irregular workloads (the HMS stress cases) and
    writes ``benchmarks/artifacts/BENCH_sweep.json`` with, per workload:
    steady-state vs compile wall time for the full grid, plus the
    single-config shard speedup (auto shard count vs the S=1 sequential
    scan) — the perf trajectory CI tracks from PR 3 onward.  A ``tsplit``
    section adds the temporal-split scaling curve in the shard-starved
    regime (S capped at 1, forced T in {1,2,4,8} on the zipf trace): per-T
    warm wall, stitch rounds, and one shared counter digest — the stitch
    is bit-exact, so the digest must not move across T.
    """
    import os
    import time

    from repro import obs
    from repro.core import HMSConfig, costmodel, simulate, simulate_many
    from repro.core import tsplit as tsplit_mod
    from repro.core.simulator import group_engine_key
    from repro.resilience import sweepckpt as _sweepckpt

    from .common import (bench_n, host_metadata, register_partial, trace,
                         unregister_partial)

    grid = [{"tag_layout": lay, "ctc_fraction": frac, "scm_mode": mode}
            for lay in ("amil", "tad")
            for frac in (0.25, 0.0625)
            for mode in ("slc", "mlc", "tlc")]
    sweep_workloads = ["bfs_tu", "sssp_ttc", "kcore"]
    rows = []
    detail = {}

    art = os.path.join(os.path.dirname(__file__), "artifacts")

    def _write_partial():
        os.makedirs(art, exist_ok=True)
        path = os.path.join(art, "BENCH_sweep.json")
        with open(path, "w") as f:
            json.dump({"partial": True, "n": bench_n(),
                       "grid_points": len(grid), "host": host_metadata(),
                       "workloads": dict(detail)}, f, indent=1)
        return path

    register_partial("sweep", _write_partial)

    def timed(fn, reps=1):
        best = None
        for _ in range(reps):
            t0 = time.time()
            r = fn()
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        return r, best

    for w in sweep_workloads:
        t = trace(w)
        cfgs = [HMSConfig(footprint=t.footprint, **kw).validate()
                for kw in grid]
        base = HMSConfig(footprint=t.footprint).validate()
        gkey = group_engine_key(t, cfgs)
        skey = group_engine_key(t, [base])

        # full grid: cold (compile + run) then steady-state (best of 2 —
        # single timed calls are noisy on small shared hosts)
        rs, cold_s = timed(lambda: simulate_many(t, cfgs))
        rs, wall_s = timed(lambda: simulate_many(t, cfgs), reps=2)
        # single config: auto shards vs forced sequential scan
        _, _ = timed(lambda: simulate(t, base))
        _, single_s = timed(lambda: simulate(t, base), reps=2)
        old = costmodel.set_max_shards(1)
        try:
            _, _ = timed(lambda: simulate(t, base))
            _, single_s1_s = timed(lambda: simulate(t, base), reps=2)
            _, grid_s1_s = timed(lambda: simulate_many(t, cfgs), reps=2)
        finally:
            costmodel.set_max_shards(old)

        bi = min(range(len(rs)), key=lambda i: rs[i].runtime_cycles)
        bkw = grid[bi]
        detail[w] = {
            "points": len(grid),
            "n": bench_n(),
            # bit-exact identity of the whole grid's counter output (stable
            # across shard counts and hosts) + the per-point model outputs —
            # what benchmarks.compare gates on
            "counter_digest": obs.counter_digest([r.counters for r in rs]),
            # design-space-store identity + full per-point model counters:
            # what repro.obs.store joins this artifact with ledger /
            # checkpoint rows on, and what the gold frontiers derive their
            # traffic axes from
            "trace_fp": _sweepckpt.trace_fingerprint(t),
            "point_config_digests": [_sweepckpt.config_digest(c)
                                     for c in cfgs],
            "point_counters": [_sweepckpt.encode_counters(r.counters)
                               for r in rs],
            "point_runtime_cycles": [r.runtime_cycles for r in rs],
            "wall_s": wall_s,
            "compile_s": max(0.0, cold_s - wall_s),
            "us_per_point": wall_s / len(grid) * 1e6,
            "grid_shards": gkey.shards,
            "grid_s1_wall_s": grid_s1_s,
            "grid_shard_speedup": grid_s1_s / max(wall_s, 1e-9),
            "single_shards": skey.shards,
            "single_depth": skey.depth,
            "single_wall_s": single_s,
            "single_s1_wall_s": single_s1_s,
            "single_shard_speedup": single_s1_s / max(single_s, 1e-9),
            "best": bkw,
            "best_runtime": rs[bi].runtime_cycles,
        }
        rows.append((f"sweep.{w}", wall_s / len(grid) * 1e6,
                     f"points={len(grid)}|best={bkw['tag_layout']}"
                     f"@{bkw['ctc_fraction']}/{bkw['scm_mode']}"
                     f"|wall={wall_s:.1f}s"
                     f"|shard_speedup={detail[w]['single_shard_speedup']:.1f}x"))
    # --- temporal-split scaling: the regime spatial shards can't reach ----
    # zipf-skewed trace with S capped at 1 (the LPT wall: the hottest CTC
    # set bounds the padded depth, so extra shards stop helping) — the only
    # remaining depth lever is T.  Counters must not move: one digest.
    w = "bfs_tu"
    t = trace(w)
    base_cfg = HMSConfig(footprint=t.footprint).validate()
    t_grid = [1, 2, 4, 8]
    t_replay = 64
    was_enabled = obs.enabled()
    if not was_enabled:
        obs.enable()                       # in-memory: stitch_rounds per T
    old_cap = costmodel.set_max_shards(1)
    curve = {}
    try:
        for tv in t_grid:
            old_t = costmodel.set_forced_tsplit(tv)
            old_r = tsplit_mod.set_replay_prefix(t_replay if tv > 1 else 0)
            try:
                _, _ = timed(lambda: simulate(t, base_cfg))
                r, wall = timed(lambda: simulate(t, base_cfg), reps=2)
                rec = [x for x in obs.records() if x.engine == "hms"][-1]
                curve[str(tv)] = {
                    "wall_s": wall,
                    "stitch_rounds": rec.stitch_rounds,
                    "counter_digest": obs.counter_digest(r.counters),
                }
            finally:
                costmodel.set_forced_tsplit(old_t)
                tsplit_mod.set_replay_prefix(old_r)
    finally:
        costmodel.set_max_shards(old_cap)
        if not was_enabled:
            obs.disable()
    digests = {c["counter_digest"] for c in curve.values()}
    assert len(digests) == 1, f"temporal split moved counters: {digests}"
    best_t = min(t_grid, key=lambda tv: curve[str(tv)]["wall_s"])
    tsec = {
        "workload": w,
        "n": bench_n(),
        "replay_prefix": t_replay,
        "t_grid": t_grid,
        "curve": curve,
        "best_t_segments": best_t,
        "tsplit_speedup": (curve["1"]["wall_s"]
                           / max(curve[str(best_t)]["wall_s"], 1e-9)),
        "counter_digest": curve["1"]["counter_digest"],
    }
    rows.append((f"sweep.tsplit.{w}", curve[str(best_t)]["wall_s"] * 1e6,
                 f"bestT={best_t}"
                 f"|speedup={tsec['tsplit_speedup']:.2f}x"
                 f"|rounds={curve[str(best_t)]['stitch_rounds']}"))
    results["sweep"] = detail
    results["sweep_tsplit"] = tsec

    unregister_partial("sweep")
    os.makedirs(art, exist_ok=True)
    figs = _tsplit_figure(tsec, art)
    with open(os.path.join(art, "BENCH_sweep.json"), "w") as f:
        json.dump({"n": bench_n(), "grid_points": len(grid), "grid": grid,
                   "host": host_metadata(), "workloads": detail,
                   "tsplit": tsec, "figures": figs}, f, indent=1)
    return rows


def _tsplit_figure(tsec: Dict, art: str) -> List[str]:
    """Render the temporal-split scaling curve (wall vs T, stitch rounds on
    the twin axis).  Import-gated: the JSON artifact is the contract."""
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:
        return []
    import os

    figs_dir = os.path.join(art, "figs")
    os.makedirs(figs_dir, exist_ok=True)
    ts = tsec["t_grid"]
    wall = [tsec["curve"][str(t)]["wall_s"] * 1e3 for t in ts]
    rounds = [tsec["curve"][str(t)]["stitch_rounds"] for t in ts]
    fig, ax = plt.subplots(figsize=(5.2, 3.6), dpi=150)
    ax.grid(True, axis="y", color="#e5e4df", linewidth=0.8, zorder=0)
    for side in ("top", "right"):
        ax.spines[side].set_visible(False)
    ax.plot(ts, wall, color="#2a78d6", linewidth=2, marker="o",
            markersize=4, zorder=3, label="warm wall (ms)")
    ax.set_xscale("log", base=2)
    ax.set_xticks(ts)
    ax.set_xticklabels([str(t) for t in ts])
    ax.set_xlabel("temporal segments T (S capped at 1)", color="#3d3d38")
    ax.set_ylabel("warm wall per call (ms)", color="#3d3d38")
    ax2 = ax.twinx()
    ax2.spines["top"].set_visible(False)
    ax2.plot(ts, rounds, color="#eb6834", linewidth=1.5, marker="s",
             markersize=3, linestyle="--", zorder=3, label="stitch rounds")
    ax2.set_ylabel("stitch rounds", color="#eb6834")
    ax.set_title(f"Temporal-split scaling — {tsec['workload']} "
                 f"(n={tsec['n']})", fontsize=10, loc="left",
                 color="#1a1a19")
    path = os.path.join(figs_dir, "sweep_tsplit.png")
    fig.tight_layout()
    fig.savefig(path)
    plt.close(fig)
    return [path]

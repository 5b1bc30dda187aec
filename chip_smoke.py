"""Smoke test of both main paths on one TPU chip.

    python chip_smoke.py

Phase A drives the simulator: the ``sweep`` suite's 12-point design grid
through ``repro.core.simulate_many``, the UM paging grid through
``repro.um.simulate_um_many`` and one forced temporal split (T = 4), at
the benchmarks' trace length.  Every engine run must finish on its planned
rung with no degradation, and every counter digest must equal the one the
CPU backend computes for the same traces and configs in this process.

Phase B serves ``qwen2.5-3b`` at its published widths (random weights from
a fixed seed) through ``repro.launch.serve.main``: four requests of eight
new tokens, checked for finite logits, in-vocabulary tokens and the same
greedy tokens on a second pass.

Readings are printed per phase; they are smoke readings, not benchmark
numbers.  The last line of stdout is one JSON object naming the device.
Exits non-zero, and prints no such line, when JAX finds no TPU or any
check fails.
"""

from __future__ import annotations

import json
import os
import sys
import time

N_REQUESTS = 120_000          # the benchmarks' default trace length


class SmokeFailure(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_a(jax, dev) -> None:
    from repro import obs, um
    from repro.core import HMSConfig, costmodel, make_trace, simulate_many
    from repro.core import simulator

    bfs = make_trace("bfs_tu", n=N_REQUESTS)
    zipf = make_trace("zipf", n=N_REQUESTS)
    grid = [HMSConfig(footprint=bfs.footprint, tag_layout=lay,
                      ctc_fraction=frac, scm_mode=mode)
            for lay in ("amil", "tad")
            for frac in (0.25, 0.0625)
            for mode in ("slc", "mlc", "tlc")]
    specs = [um.um_spec(HMSConfig(footprint=bfs.footprint,
                                  organization="hbm", r_hbm=1.0 / rel),
                        nvlink=nv)
             for rel in (1.25, 1.5, 2.0, 4.0) for nv in (False, True)]
    split_cfg = HMSConfig(footprint=zipf.footprint)

    def run_all():
        """One pass over the three workloads; digests per config."""
        obs.reset(keep_compiled=True)        # forget memoized UM results
        simulator._dice_chain.cache_clear()  # regenerate the dice stream
        simulator._DICE_F32.clear()
        obs.clear_records()
        t0 = time.perf_counter()
        sweep = simulate_many(bfs, grid)
        paging = um.simulate_um_many(bfs, specs)
        old = costmodel.set_forced_tsplit(4)
        try:
            split = simulate_many(zipf, [split_cfg])
        finally:
            costmodel.set_forced_tsplit(old)
        wall = time.perf_counter() - t0
        digests = {
            "sweep": [obs.counter_digest(r.counters) for r in sweep],
            "um": [obs.counter_digest(r.counter_arrays()) for r in paging],
            "tsplit": [obs.counter_digest(split[0].counters)],
        }
        return wall, digests, list(obs.records())

    def check_rungs(records, where):
        ran = [r for r in records if r.ladder_rung is not None]
        _check(ran, f"{where}: no engine ran")
        for r in ran:
            _check(r.ladder_rung not in ("reference", "bisect")
                   and not r.degradations and not r.retries,
                   f"{where}: {r.engine_key} left its planned rung "
                   f"({r.ladder_rung}, {r.degradations})")
        split_runs = [r for r in ran if r.trace == zipf.name]
        _check(split_runs and all(r.t_segments == 4 for r in split_runs),
               f"{where}: the forced T=4 split did not run")

    obs.enable()
    cold, on_chip, records = run_all()
    check_rungs(records, "tpu cold")
    warm, again, records = run_all()
    check_rungs(records, "tpu warm")
    _check(again == on_chip, "tpu: digests moved between passes")

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        cpu_wall, on_cpu, records = run_all()
    check_rungs(records, "cpu")
    obs.disable()

    mismatched = {k: [i for i, (a, b) in enumerate(zip(on_chip[k], on_cpu[k]))
                      if a != b]
                  for k in on_chip}
    n_configs = sum(len(v) for v in on_chip.values())
    n_match = n_configs - sum(len(v) for v in mismatched.values())
    requests = N_REQUESTS * (len(grid) + len(specs) + 1)
    _say("A", warm_wall_s=f"{warm:.3f}", compile_s=f"{cold - warm:.3f}",
         cpu_wall_s=f"{cpu_wall:.3f}", simulated_requests=requests,
         digests_match=f"{n_match}/{n_configs}", peak_bytes=_peak_bytes(dev))
    _check(n_match == n_configs,
           f"chip digests differ from the CPU's: {mismatched}")


def phase_b(jax, dev) -> None:
    from repro.launch import serve
    from repro.serving import Request

    t0 = time.perf_counter()
    eng = serve.main(["--arch", "qwen2.5-3b", "--requests", "4",
                      "--max-new", "8"])
    cold = time.perf_counter() - t0
    first = dict(eng.done)
    vocab = eng.cfg.vocab
    for rid, r in sorted(first.items()):
        eng.submit(Request(rid + len(first), r.prompt, max_new=r.max_new))
    t0 = time.perf_counter()
    eng.run()
    warm = time.perf_counter() - t0

    tokens = 0
    for rid, r in first.items():
        again = eng.done[rid + len(first)].out
        _check(r.out.shape == (r.max_new,),
               f"request {rid}: {r.out.shape[0]} tokens, not {r.max_new}")
        _check(bool(((r.out >= 0) & (r.out < vocab)).all()),
               f"request {rid}: token outside [0, {vocab})")
        _check(bool((again == r.out).all()),
               f"request {rid}: second pass gave {again}, first {r.out}")
        tokens += 2 * r.out.shape[0]
    _check(eng.nonfinite_logits == 0,
           f"{eng.nonfinite_logits} non-finite logits")
    _say("B", arch=eng.cfg.name, layers=eng.cfg.n_layers,
         d_model=eng.cfg.d_model, warm_wall_s=f"{warm:.3f}",
         cold_wall_s=f"{cold:.3f}", requests=2 * len(first),
         tokens=tokens, greedy_repeat="match",
         peak_bytes=_peak_bytes(dev))


def main() -> int:
    # Phase A's reference run needs the CPU backend next to the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    # plan from committed files only, never from a host-local profile
    os.environ["REPRO_CALIB"] = "off"

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: "
              f"{dev.platform})", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    _say("setup", device_kind=dev.device_kind,
         device_count=len(jax.devices()),
         compile_cache=enable_compile_cache())
    try:
        phase_a(jax, dev)
        phase_b(jax, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

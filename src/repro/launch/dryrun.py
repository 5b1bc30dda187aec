"""Multi-pod dry-run driver.

For one (arch x shape x mesh) cell:
  * builds the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  * lowers + compiles the cell's step (train / prefill / serve) with the
    framework's sharding rules over ShapeDtypeStruct inputs,
  * records per-device memory analysis, cost analysis and the collective
    schedule (op kinds + per-device operand bytes parsed from the SPMD HLO).

Because XLA's cost analysis counts a while-loop body once (ignoring the trip
count), layer-scanned "deploy" compiles under-report FLOPs/bytes.  The
``--probe`` mode therefore re-lowers the model at 1 and 2 layers per stack
dimension with fully-unrolled scans and extrapolates exact per-layer costs:
cost(L) = cost(1) + (L-1) * (cost(2) - cost(1)) per stack dim.  The deploy
compile still provides memory_analysis (while-loop buffers are sized
correctly) and proves the sharding is coherent.

Usage:
    python -m repro.launch.dryrun --arch granite-8b --shape train_4k \
        [--multi-pod] [--probe] [--json out.json]
    python -m repro.launch.dryrun --all [--multi-pod] [--probe]
"""

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from functools import partial

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str):
    """Sum per-device *result* bytes of every collective op, by kind.

    Post-optimization HLO prints operands without types, so the result type
    (always printed, including tuple results) is the robust measure.  The
    HLO is SPMD (one program per device), so these are per-device bytes:
    all-gather result = bytes a device receives; all-reduce result = the
    tensor a device reduces (ring moves ~2x this, noted in EXPERIMENTS.md);
    all-to-all / collective-permute result = bytes exchanged.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            m = re.search(rf"= (.*?) {kind}(?:-start)?\(", line)
            if m is None:
                continue
            result_types = m.group(1)
            total = 0.0
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", result_types):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += n * _DTYPE_BYTES[dt]
            out[kind] += total
            counts[kind] += 1
            break
    return out, counts


def _score_dims(cfg, shape):
    """Trailing dims of attention-score intermediates in probe compiles
    (q/kv chunks are S/2, T/2 in unroll mode)."""
    dims = set()
    if shape.kind in ("train", "prefill"):
        S = shape.seq_len
        dims.add((S // 2, S // 2))
        if cfg.family == "vlm":
            S_txt = S - cfg.n_patches
            dims.add((S_txt // 2, S_txt // 2))
            dims.add((cfg.n_patches // 2, cfg.n_patches // 2))
        if cfg.family == "encdec":
            e = cfg.enc_seq
            dims.add((e // 2, e // 2))
            dims.add((S // 2, e // 2))
    else:
        dims.add((1, shape.seq_len))
        if cfg.family == "encdec":
            dims.add((1, cfg.enc_seq))
    return tuple(sorted(dims))


def _probe_dims(cfg):
    """(field, unit_count, unit_size) per independently-scaled stack dim."""
    dims = []
    if cfg.family == "hybrid":
        dims.append(("n_layers", cfg.n_layers // cfg.attn_every,
                     cfg.attn_every))
    else:
        dims.append(("n_layers", cfg.n_layers, 1))
    if cfg.family == "encdec":
        dims.append(("n_enc_layers", cfg.n_enc_layers, 1))
    if cfg.family == "vlm":
        dims.append(("n_vision_layers", cfg.n_vision_layers, 1))
    return dims


def _with_units(cfg, units):
    kw = {}
    for (field, _, unit), u in zip(_probe_dims(cfg), units):
        kw[field] = unit * u
    return dataclasses.replace(cfg, **kw)


def parse_score_tensor_bytes(hlo_text: str, score_dims):
    """Sum result bytes of attention-score-shaped tensors (trailing dims in
    ``score_dims``, rank >= 3).  These intermediates live in VMEM under the
    flash kernel; the XLA path spills them to HBM, so the roofline reports
    both the raw and the kernel-adjusted memory term."""
    if not score_dims:
        return 0.0
    want = {tuple(d) for d in score_dims}
    total = 0.0
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]", hlo_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        parts = [int(d) for d in dims.split(",")]
        if len(parts) >= 3 and tuple(parts[-2:]) in want:
            n = 1
            for d in parts:
                n *= d
            total += n * _DTYPE_BYTES[dt]
    return total


def _extract_costs(compiled, score_dims=()):
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    coll, counts = parse_collective_bytes(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "attn_score_bytes": parse_score_tensor_bytes(txt, score_dims),
        "collective_bytes": coll,
        "collective_counts": counts,
        "hlo_chars": len(txt),
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               probe: bool = False, verbose: bool = True,
               kv_mode: str = "auto", remat: bool = True,
               moe_shard_map: bool = True, sequence_parallel: bool = True,
               moe_impl: str = "tp", attention_impl: str = "blocked",
               sp_barrier: bool = False, grad_barrier: bool = False,
               sp_prenorm: bool = False, pure_fsdp: bool = False,
               grad_shard: bool = False):
    import jax
    import jax.numpy as jnp

    from ..configs import SHAPES, cell_is_valid, get_config
    from ..launch import steps as S
    from ..launch.mesh import make_production_mesh
    from ..parallel import sharding as shard_rules
    from ..parallel.mesh_ctx import MeshCtx

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_valid(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    pcfg = shard_rules.make_parallel_cfg(mesh, kv_mode=kv_mode,
                                         pure_fsdp=pure_fsdp)
    if pure_fsdp:
        dp_axes = tuple(mesh.axis_names)
        sequence_parallel = False
    ctx = MeshCtx(mesh=mesh, dp=dp_axes, tp="model", pure_dp=pure_fsdp,
                  remat=remat and shape.kind == "train",
                  use_shard_map_moe=moe_shard_map,
                  moe_impl=moe_impl, sp_barrier=sp_barrier,
                  sp_prenorm=sp_prenorm,
                  sequence_parallel=(sequence_parallel
                                     and shape.kind != "decode"))

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names,
                         [int(mesh.shape[a]) for a in mesh.axis_names])),
        "n_devices": int(len(mesh.devices.flat)),
        "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }

    def build(cfg_k, ctx_k):
        specs = S.input_specs(cfg_k, shape)
        in_sh, out_sh = S.shardings_for(cfg_k, shape, mesh, pcfg)
        if shape.kind == "train":
            gsh = in_sh[0] if grad_shard else None
            fn = S.make_train_step(cfg_k, ctx_k, grad_barrier=grad_barrier,
                                   grad_shardings=gsh)
            args = (specs["params"], specs["opt_state"], specs["batch"])
            donate = (0, 1)
        elif shape.kind == "prefill":
            fn = S.make_prefill_step(cfg_k, ctx_k)
            args = (specs["params"], specs["batch"])
            donate = ()
        else:
            fn = S.make_serve_step(cfg_k, ctx_k)
            args = (specs["params"], specs["tokens"], specs["cache"],
                    specs["pos"])
            donate = (2,)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        return jitted, args

    # ---- deploy compile: full depth, scanned ------------------------------
    t0 = time.time()
    jitted, args = build(cfg, ctx)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    result["deploy"] = {
        "compile_s": round(time.time() - t0, 1),
        "per_device_bytes": {
            "arguments": int(ma.argument_size_in_bytes),
            "outputs": int(ma.output_size_in_bytes),
            "temps": int(ma.temp_size_in_bytes),
            "aliased": int(ma.alias_size_in_bytes),
            "total_live": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
        },
        **_extract_costs(compiled),
    }
    if verbose:
        d = result["deploy"]
        print(f"[{arch} x {shape_name} x {'2pod' if multi_pod else '1pod'}] "
              f"compiled in {d['compile_s']}s; "
              f"live/device = {d['per_device_bytes']['total_live']/2**30:.2f} GiB",
              flush=True)

    # ---- probe compiles: unrolled 2- and 3-layer variants ------------------
    # (2/3 rather than 1/2: stack-size-1 scans hit XLA pathologies — a
    # single-layer whisper compile reported 2.4x the flops of a 2-layer one)
    if probe:
        dims = _probe_dims(cfg)
        ctx_p = dataclasses.replace(ctx, unroll=True, remat=False)
        base_units = [min(2, count) for (_, count, _) in dims]
        compiles = {}

        sdims = _score_dims(cfg, shape)

        def cost_at(units):
            key = tuple(units)
            if key in compiles:
                return compiles[key]
            cfg_k = _with_units(cfg, units)
            jitted_k, args_k = build(cfg_k, ctx_p)
            c = jitted_k.lower(*args_k).compile()
            compiles[key] = _extract_costs(c, score_dims=sdims)
            return compiles[key]

        t0 = time.time()
        base = cost_at(base_units)
        full = {k: (dict(base[k]) if isinstance(base[k], dict) else base[k])
                for k in ("flops", "bytes", "attn_score_bytes",
                          "collective_bytes", "collective_counts")}
        for i, (field, count, unit) in enumerate(dims):
            up = list(base_units)
            up[i] = min(base_units[i] + 1, count)
            if up[i] == base_units[i]:
                continue
            c2 = cost_at(up)
            scale = count - base_units[i]
            full["flops"] += scale * (c2["flops"] - base["flops"])
            full["bytes"] += scale * (c2["bytes"] - base["bytes"])
            full["attn_score_bytes"] += scale * (
                c2["attn_score_bytes"] - base["attn_score_bytes"])
            for kk in _COLLECTIVES:
                full["collective_bytes"][kk] += scale * (
                    c2["collective_bytes"][kk]
                    - base["collective_bytes"][kk])
                full["collective_counts"][kk] += scale * (
                    c2["collective_counts"][kk]
                    - base["collective_counts"][kk])
        full["probe_compile_s"] = round(time.time() - t0, 1)
        result["probe"] = full
        if verbose:
            tot_coll = sum(full["collective_bytes"].values())
            print(f"    probe: {full['flops']/1e12:.2f} TFLOP/dev, "
                  f"{full['bytes']/2**30:.2f} GiB/dev, "
                  f"coll {tot_coll/2**30:.3f} GiB/dev "
                  f"({full['probe_compile_s']}s)", flush=True)
    return result


def main(argv=None):
    # 512 virtual host devices back the production meshes; XLA reads the
    # flag when the first backend starts, so this precedes any JAX use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--kv-mode", default="auto")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-moe-shard-map", action="store_true")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence parallelism (perf baseline)")
    ap.add_argument("--moe-impl", default="tp", choices=["tp", "ep"])
    ap.add_argument("--json")
    args = ap.parse_args(argv)

    from ..configs import all_cells

    cells = (all_cells() if args.all
             else [(args.arch, args.shape)])
    results = []
    for arch, shape in cells:
        try:
            r = lower_cell(arch, shape, args.multi_pod, probe=args.probe,
                           kv_mode=args.kv_mode, remat=not args.no_remat,
                           moe_shard_map=not args.no_moe_shard_map,
                           sequence_parallel=not args.no_sp,
                           moe_impl=args.moe_impl)
        except Exception as e:  # noqa: BLE001 — a cell failure is a bug report
            r = {"arch": arch, "shape": shape, "error": repr(e)}
            print(f"[{arch} x {shape}] FAILED: {e}", flush=True)
        results.append(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    n_err = sum(1 for r in results if "error" in r)
    print(f"dry-run: {len(results)} cells, {n_err} failures", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())

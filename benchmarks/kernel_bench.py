"""Kernel microbenchmarks: interpret-mode wall time (CPU overhead sanity,
not TPU perf) + analytic FLOP/byte intensity per kernel tile config."""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, reps=3):
    fn(*args)                      # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def run(results: Dict) -> List[tuple]:
    rng = np.random.default_rng(0)
    rows = []

    from repro.kernels.flash_attention.ops import flash_attention
    B, S, H, hd = 1, 256, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    us = _timeit(lambda a: flash_attention(a, q, q, causal=True,
                                           block_q=128, block_k=128,
                                           interpret=True), q)
    flops = 4 * B * H * S * S * hd
    # VMEM working set per grid step: q,k,v tiles + f32 scores + acc
    vmem = (128 * hd * 4 * 2 + 128 * hd * 4 + 128 * 128 * 4
            + 128 * hd * 4)
    rows.append(("kernel.flash_256", us,
                 f"flops={flops:.2e}|vmem_tile_KiB={vmem/1024:.0f}"))

    from repro.kernels.paged_attention.ops import paged_decode_attention
    B, H, KV, hd, ps, npg, pool = 4, 8, 2, 64, 16, 8, 64
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pool, KV, ps, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, pool, (B, npg)), jnp.int32)
    ln = jnp.full((B,), npg * ps, jnp.int32)
    us = _timeit(lambda a: paged_decode_attention(a, kp, kp, bt, ln,
                                                  interpret=True), q)
    bytes_moved = 2 * npg * ps * KV * hd * 4 * B
    rows.append(("kernel.paged_decode", us,
                 f"kv_bytes={bytes_moved:.2e}|pages={npg}"))

    from repro.kernels.ssd_scan.ops import ssd
    b, l, h, p, g, n, chunk = 1, 256, 2, 32, 1, 32, 64
    x = jnp.asarray(rng.standard_normal((b, l, h, p)) * .3, jnp.float32)
    dt = jnp.asarray(rng.random((b, l, h)) * .4 + .1, jnp.float32)
    A = -jnp.asarray(rng.random((h,)) + .5, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, l, g, n)) * .3, jnp.float32)
    us = _timeit(lambda a: ssd(a, dt, A, Bm, Bm, chunk=chunk,
                               interpret=True), x)
    flops = b * h * (l // chunk) * (2 * chunk * chunk * (n + p)
                                    + 2 * chunk * p * n * 2)
    rows.append(("kernel.ssd_256", us, f"flops={flops:.2e}|chunk={chunk}"))

    from repro.kernels.amil_probe.ops import probe
    meta = jnp.asarray(rng.integers(0, 64, (4096,)), jnp.int32)
    slots = jnp.asarray(rng.integers(0, 4096, (2048,)), jnp.int32)
    tags = jnp.asarray(rng.integers(0, 4, (2048,)), jnp.int32)
    us = _timeit(lambda s: probe(meta, s, tags, interpret=True), slots)
    rows.append(("kernel.amil_probe_2k", us,
                 "resolves=2048 blocks|table_KiB=16"))

    results["kernels"] = {name: us for name, us, _ in rows}
    return rows

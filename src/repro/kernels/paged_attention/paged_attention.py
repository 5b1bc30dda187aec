"""Paged decode attention kernel (TPU Pallas) — the Track-B "DRAM cache"
read path.

One new token per sequence attends over a KV cache stored as fixed-size
pages in a global page pool; a per-sequence block table (the AMIL-backed
page table of the memtier runtime) maps logical page index -> pool slot.
The block table and sequence lengths ride the scalar-prefetch channel
(`pltpu.PrefetchScalarGridSpec`), so the page -> HBM address indirection is
resolved by the DMA engine ahead of compute — the kernel core never touches
addresses, exactly like the paper's tag-in-last-column fetch resolving a
whole row of residency in one access.

Pool layout: ``(pool_size, KV, page_size, hd)`` — the kv-head axis sits
ahead of the page axis, so one (page_size x hd) tile of one kv head is a
contiguous block whose last two dims meet the TPU's (8, 128) tiling rule.

Grid: (batch, kv_heads, n_pages).  The page dimension iterates sequentially
on TPU, carrying the online-softmax state in VMEM scratch.  Per-step the
kernel pulls one (page_size x hd) K tile + V tile per kv head, multiplies
against the G = H/KV query heads of that kv head ((G x hd) @ (hd x page)),
and masks tokens beyond the sequence length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_kernel(block_table_ref, lengths_ref,         # scalar prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  page_size: int, scale: float, softcap: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    page_live = pi * page_size < length

    @pl.when(page_live)
    def _compute():
        q = q_ref[0, 0]                                  # (G, hd)
        k = k_ref[0, 0]                                  # (page, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, page)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        tok = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(tok < length, s, -1e30)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (G, hd)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    softcap: float = 0.0, interpret: bool = False):
    """q: (B, KV, G, hd) — one token's query heads grouped by kv head.
    k_pages/v_pages: (pool_size, KV, page_size, hd) global page pool.
    block_table: (B, n_pages) int32 pool-slot per logical page.
    lengths: (B,) int32 tokens valid per sequence.
    Returns (B, KV, G, hd).
    """
    B, KV, G, hd = q.shape
    pool, KV2, page_size, hd2 = k_pages.shape
    assert (KV2, hd2) == (KV, hd)
    n_pages = block_table.shape[1]
    scale = float(1.0 / np.sqrt(hd))

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, scale=scale, softcap=softcap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, p, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, p, bt, ln: (bt[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, p, bt, ln: (bt[b, p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, p, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(block_table, lengths, q, k_pages, v_pages)
    return out

"""Pure-jnp oracle for the paged decode attention kernel."""

from __future__ import annotations

import jax.numpy as jnp
import jax
import numpy as np


def paged_attention_reference(q, k_pages, v_pages, block_table, lengths, *,
                              softcap: float = 0.0):
    """Gather pages into dense (B, T, KV, hd), then masked attention.

    Shapes as in ``paged_attention``.
    """
    B, KV, G, hd = q.shape
    pool, _, page_size, _ = k_pages.shape
    n_pages = block_table.shape[1]
    T = n_pages * page_size

    # (B, n_pages, KV, page, hd) -> (B, T, KV, hd)
    k = jnp.swapaxes(k_pages[block_table], 2, 3).reshape(B, T, KV, hd)
    v = jnp.swapaxes(v_pages[block_table], 2, 3).reshape(B, T, KV, hd)

    logits = jnp.einsum("bkgh,btkh->bkgt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * float(1.0 / np.sqrt(hd))
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = jnp.arange(T)[None, :] < lengths[:, None]     # (B, T)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", w, v.astype(jnp.float32))
    return out.astype(q.dtype)

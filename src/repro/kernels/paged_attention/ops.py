"""Public jit'd wrapper: paged decode attention."""

from __future__ import annotations

from .paged_attention import paged_attention as _kernel


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           softcap=0.0, interpret=False):
    """q: (B, 1, H, hd) one token; k/v pages (pool, KV, page, hd); returns
    (B, 1, H, hd).  ``interpret=True`` runs the kernel in the Pallas
    interpreter (CPU)."""
    B, one, H, hd = q.shape
    KV = k_pages.shape[1]
    G = H // KV
    qg = q[:, 0].reshape(B, KV, G, hd)
    out = _kernel(qg, k_pages, v_pages, block_table, lengths,
                  softcap=softcap, interpret=interpret)
    return out.reshape(B, 1, H, hd)

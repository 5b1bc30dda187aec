"""Public jit'd wrapper: model-layout flash attention."""

from __future__ import annotations

import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd


def flash_attention(q, k, v, *, causal=True, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """q: (B, S, H, hd); k/v: (B, T, KV, hd) (GQA expanded here).

    Pads S/T to block multiples, flattens heads, runs the kernel.
    ``interpret=True`` runs it in the Pallas interpreter (CPU).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    pad_s = (-S) % block_q
    pad_t = (-T) % block_k
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    if pad_t:
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S + pad_s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T + pad_t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T + pad_t, hd)
    out = flash_attention_bhsd(
        qf, kf, vf, causal=causal, softcap=softcap,
        block_q=block_q, block_k=block_k, kv_real=T, q_real=S,
        interpret=interpret)
    out = out.reshape(B, H, S + pad_s, hd).transpose(0, 2, 1, 3)
    return out[:, :S]

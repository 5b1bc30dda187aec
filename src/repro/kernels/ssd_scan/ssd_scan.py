"""Mamba2 SSD chunked-scan kernel (TPU Pallas).

TPU-native decomposition of the state-space dual form: the grid is
(batch, heads, n_chunks); the chunk dimension iterates sequentially so the
(head_dim x state) recurrent state lives in VMEM scratch and is carried
across chunks — no HBM round-trip for the recurrence, unlike a lax.scan
whose carry is an HBM buffer.  Per (b, h, chunk) step the kernel does three
MXU matmuls on (chunk x state)/(chunk x head_dim) tiles:

    scores = C B^T            (chunk x chunk)
    y_diag = (scores ⊙ L) X    intra-chunk, causal-decay weighted
    y_off  = (C ⊙ decay) S_prev  inter-chunk contribution

and one rank-k update of the carried state.  All decay math (segsum) is
computed in-register from the chunk's dtA vector; its prefix sums are
lower-triangular mask matmuls (Mosaic has no cumsum), one per layout the
decay matrix needs (a column and a row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dta_ref, b_ref, c_ref, y_ref, s_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (cs, hp)
    dta = dta_ref[0, 0].astype(jnp.float32)        # (cs, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)           # (cs, n)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (cs, n)

    # tri[i, j] = i >= j: cum = tri @ dta is the inclusive prefix sum
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    trif = tri.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(
        trif, dta, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)        # (cs, 1)
    cum_row = jax.lax.dot_general(
        dta, trif, (((0,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)        # (1, cs)
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0
    L = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (cs, cs)
    y_diag = jax.lax.dot_general(
        scores * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (cs, hp)

    s_prev = s_ref[...]                            # (hp, n)
    y_off = jax.lax.dot_general(
        Cm * jnp.exp(cum), s_prev,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (cs, hp)

    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    total = jnp.sum(jnp.where(last, cum, 0.0))     # cum[-1], as a scalar
    decay_to_end = jnp.exp(total - cum)            # (cs, 1)
    s_new = jnp.exp(total) * s_prev + jax.lax.dot_general(
        x * decay_to_end, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (hp, n)
    s_ref[...] = s_new

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dta, Bh, Ch, *, chunk: int, interpret: bool = False):
    """x: (B, H, L, hp); dta: (B, H, L, 1); Bh/Ch: (B, H, L, n).

    ``dta`` = dt * A (already multiplied, post-softplus dt); B/C already
    expanded to H heads and pre-scaled (B rows carry the dt factor:
    B_scaled[t] = B[t] — the x input should carry dt, i.e. x = x_raw * dt,
    matching ``ssd_reference``).  Returns y: (B, H, L, hp).
    """
    B, H, Lq, hp = x.shape
    n = Bh.shape[-1]
    assert Lq % chunk == 0
    nc = Lq // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hp),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hp), x.dtype),
        scratch_shapes=[pltpu.VMEM((hp, n), jnp.float32)],
        interpret=interpret,
    )(x, dta, Bh, Ch)

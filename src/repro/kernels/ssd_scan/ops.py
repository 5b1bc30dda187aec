"""Public jit'd wrapper: SSD chunked scan in model layout."""

from __future__ import annotations

import jax.numpy as jnp

from .ssd_scan import ssd_scan as _kernel


def ssd(x, dt, A, B, C, chunk: int, *, interpret: bool = False):
    """Model layout (matches ssd_reference): x (b,l,h,p), dt (b,l,h),
    A (h,), B/C (b,l,g,n).  Returns y (b,l,h,p) (no final state).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU)."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    L = l + pad
    Bh = jnp.repeat(B, h // g, axis=2)
    Ch = jnp.repeat(C, h // g, axis=2)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(jnp.float32)
    dta = (dt * A[None, None, :]).astype(jnp.float32)
    # -> (B, H, L, *)
    tr = lambda t: jnp.moveaxis(t, 2, 1)
    y = _kernel(tr(xdt), tr(dta)[..., None], tr(Bh.astype(jnp.float32)),
                tr(Ch.astype(jnp.float32)), chunk=chunk,
                interpret=interpret)
    y = jnp.moveaxis(y, 1, 2)[:, :l]
    return y.astype(x.dtype)

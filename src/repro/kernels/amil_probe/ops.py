"""Public jit'd wrapper: batched AMIL residency probe."""

from __future__ import annotations

import jax.numpy as jnp

from .amil_probe import amil_probe as _kernel


def probe(meta, slots, tags, block: int = 256, *, interpret: bool = False):
    """meta int32[num_slots]; slots/tags int32[N] (N padded here).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU)."""
    (N,) = slots.shape
    pad = (-N) % block
    if pad:
        slots = jnp.pad(slots, (0, pad))
        tags = jnp.pad(tags, (0, pad), constant_values=-1)
    hit, dirty, aff = _kernel(meta, slots, tags, block=block,
                              interpret=interpret)
    return hit[:N], dirty[:N], aff[:N]

"""AMIL tag-probe kernel (TPU Pallas) — the paper's mechanism, vectorized.

Batched residency resolution against an AMIL-packed metadata table: the
metadata of all 8 cachelines of a DRAM row (superblock) is one packed word,
so a single table fetch resolves every line in the row (§III-B of the
paper).  The memtier runtime calls this to resolve block -> HBM-slot
residency for thousands of requests per step without host round-trips.

Layout: the table arrives as ``int32[num_slots]`` (one lane per line, so a
request's ``slot`` (= global line index % num_slots) IS the table index —
the AMIL property that tags of a row are adjacent).  Each int32 lane packs
tag[0:2] | valid[2] | dirty[3] | affinity[4:6] exactly like
``core/amil.py``; only those six bits are read.  The whole table rides in
VMEM (a 64 MiB HBM cache at 256 KiB blocks needs 256 slots = 1 KiB; even a
16 GiB pool at 2 MiB blocks is 8 K lanes = 32 KiB), matching the paper's
CTC sizing argument.

The TPU has no 1-D vector gather, so the lookup is two exact selections:
the table is folded to ``(128, rows)`` (slot = row * 128 + lane) and one
one-hot matmul on the MXU fetches each request's table row, then a lane
compare-and-reduce picks its lane.  Six-bit values times a one-hot are
exact at any matmul precision.

Grid: (n_requests // block,), requests lane-major as ``(1, N)``.  Per step:
resolve ``block`` metadata lanes, unpack bits, compare tags, emit
hit/dirty/affinity lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TAG_MASK = 0b11
VALID_SHIFT = 2
DIRTY_SHIFT = 3
AFF_SHIFT = 4
AFF_MASK = 0b11
META_BITS = 0b111111          # tag | valid | dirty | affinity
LANES = 128


def _probe_kernel(table_ref, slot_ref, tag_ref, hit_ref, dirty_ref, aff_ref):
    slots = slot_ref[...]                       # (1, blk) int32
    want = tag_ref[...] & TAG_MASK              # (1, blk)
    rows = table_ref.shape[1]
    blk = slots.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0)
              == slots // LANES).astype(table_ref.dtype)        # (rows, blk)
    row_meta = jax.lax.dot_general(
        table_ref[...], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (LANES, blk): table rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, blk), 0)
    meta = jnp.sum(jnp.where(lane == slots % LANES, row_meta, 0.0),
                   axis=0, keepdims=True).astype(jnp.int32)     # (1, blk)
    tag = meta & TAG_MASK
    valid = (meta >> VALID_SHIFT) & 1
    dirty = (meta >> DIRTY_SHIFT) & 1
    aff = (meta >> AFF_SHIFT) & AFF_MASK
    hit = (valid == 1) & (tag == want)
    hit_ref[...] = hit.astype(jnp.int32)
    dirty_ref[...] = (dirty & hit).astype(jnp.int32)
    aff_ref[...] = aff.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def amil_probe(meta, slots, tags, *, block: int = 256,
               interpret: bool = False):
    """meta: int32[num_slots] packed AMIL lanes; slots/tags: int32[N].

    ``block`` is a multiple of 128 dividing N.  Returns (hit, dirty,
    affinity): int32[N] each.
    """
    (n_slots,) = meta.shape
    (N,) = slots.shape
    assert N % block == 0 and block % LANES == 0, (N, block)
    rows = -(-n_slots // LANES)
    rows += (-rows) % 8                         # f32 sublane tile
    table = jnp.pad(meta & META_BITS, (0, rows * LANES - n_slots))
    table = table.reshape(rows, LANES).T.astype(jnp.float32)

    req = pl.BlockSpec((1, block), lambda i: (0, i))
    outs = pl.pallas_call(
        _probe_kernel,
        grid=(N // block,),
        in_specs=[pl.BlockSpec((LANES, rows), lambda i: (0, 0)), req, req],
        out_specs=(req, req, req),
        out_shape=tuple(jax.ShapeDtypeStruct((1, N), jnp.int32)
                        for _ in range(3)),
        interpret=interpret,
    )(table, slots.reshape(1, N), tags.reshape(1, N))
    return tuple(o.reshape(N) for o in outs)

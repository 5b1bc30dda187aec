"""Compile every Pallas kernel for a TPU v5e at real widths, no chip needed.

The TPU compiler is installed with jaxlib, and it compiles for a topology
that is described rather than attached.  It refuses what the interpreter
accepts (1-D vector gathers, primitives Mosaic cannot lower, blocks that
break the (8, 128) tiling rule), so these tests pin the kernels to what
the chip runs.  ``repro.core`` is imported first: the simulator's 64-bit
mode must stay scoped to its engines, or the kernels would compile as
64-bit programs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (must not switch the process to x64)
from repro.kernels.amil_probe.amil_probe import amil_probe
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert not jax.config.jax_enable_x64
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_amil_probe_compiles(one_chip):
    _compile(amil_probe, one_chip,
             ((8192,), jnp.int32), ((65536,), jnp.int32),
             ((65536,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    qkv = ((32, 2048, 128), jnp.bfloat16)
    _compile(flash_attention_bhsd, one_chip, qkv, qkv, qkv)


def test_ssd_scan_compiles(one_chip):
    # mamba2-1.3b: 64 heads of head dim 64, state 128, chunk 256
    b, h, l, hp, n = 1, 64, 2048, 64, 128
    _compile(ssd_scan, one_chip,
             ((b, h, l, hp), jnp.float32), ((b, h, l, 1), jnp.float32),
             ((b, h, l, n), jnp.float32), ((b, h, l, n), jnp.float32),
             chunk=256)


def test_paged_attention_compiles(one_chip):
    # qwen2.5-3b decode: 2 kv heads x 8 query heads, head dim 128, 16-token
    # pages; 4 sequences of up to 4096 tokens
    B, KV, G, hd, page, n_pages = 4, 2, 8, 128, 16, 256
    pool = B * n_pages
    pages = ((pool, KV, page, hd), jnp.bfloat16)
    _compile(paged_attention, one_chip,
             ((B, KV, G, hd), jnp.bfloat16), pages, pages,
             ((B, n_pages), jnp.int32), ((B,), jnp.int32))

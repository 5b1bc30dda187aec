"""Per-kernel interpret-mode validation against the pure-jnp oracles:
shape/dtype sweeps + assert_allclose, plus hypothesis property tests.
Every kernel call passes ``interpret=True`` (the Pallas interpreter on the
CPU); ``test_tpu_compile.py`` compiles the same kernels for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the 'dev' extra")
from hypothesis import given, settings, strategies as st

from repro.kernels.amil_probe.ops import probe
from repro.kernels.amil_probe.ref import amil_probe_reference
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_reference
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_reference
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import (segsum, ssd_decode_step,
                                        ssd_reference)

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,T,H,KV,hd", [
    (128, 128, 4, 4, 64),
    (256, 256, 4, 2, 64),     # GQA
    (128, 384, 2, 2, 128),    # cross-length (decode-window style)
    (130, 200, 2, 1, 64),     # ragged, exercises padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(S, T, H, KV, hd, dtype, causal):
    B = 2
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, T, KV, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, T, KV, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vf = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    ref = flash_attention_reference(qf, kf, vf, causal=causal)
    ref = ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


def test_flash_softcap():
    B, S, H, hd = 1, 128, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, softcap=30.0,
                          interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ref = flash_attention_reference(qf, kf, vf, causal=True, softcap=30.0)
    ref = ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,hd,ps,npg", [
    (2, 4, 4, 64, 16, 4),
    (3, 8, 2, 64, 32, 8),
    (1, 4, 1, 128, 16, 16),   # MQA long
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_matches_reference(B, H, KV, hd, ps, npg, dtype):
    pool = npg * B + 7
    q = jnp.asarray(RNG.standard_normal((B, 1, H, hd)), dtype)
    kp = jnp.asarray(RNG.standard_normal((pool, KV, ps, hd)), dtype)
    vp = jnp.asarray(RNG.standard_normal((pool, KV, ps, hd)), dtype)
    bt = jnp.asarray(RNG.integers(0, pool, (B, npg)), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, npg * ps + 1, (B,)), jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    ref = paged_attention_reference(
        q[:, 0].reshape(B, KV, H // KV, hd), kp, vp, bt, lengths
    ).reshape(B, 1, H, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


def test_paged_ignores_out_of_length_pages():
    """Pages past `length` must not affect the output (residency masking)."""
    B, H, KV, hd, ps, npg, pool = 1, 2, 2, 64, 16, 4, 16
    q = jnp.asarray(RNG.standard_normal((B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((pool, KV, ps, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((pool, KV, ps, hd)), jnp.float32)
    bt1 = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    bt2 = jnp.asarray([[0, 1, 9, 9]], jnp.int32)   # garbage beyond length
    lengths = jnp.asarray([2 * ps], jnp.int32)
    o1 = paged_decode_attention(q, kp, vp, bt1, lengths, interpret=True)
    o2 = paged_decode_attention(q, kp, vp, bt2, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,h,p,g,n,chunk", [
    (64, 2, 16, 1, 16, 16),
    (128, 4, 32, 2, 32, 32),
    (256, 4, 64, 1, 64, 64),
])
def test_ssd_kernel_matches_reference(l, h, p, g, n, chunk):
    b = 2
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)) * 0.5 + 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random((h,)) * 0.5 + 0.5, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    yk = ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, _ = ssd_reference(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=3e-4,
                               rtol=3e-4)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact: chunk size cannot change y."""
    b, l, h, p, g, n = 1, 128, 2, 16, 1, 16
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)) * 0.4 + 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random((h,)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    y32, _ = ssd_reference(x, dt, A, B, C, 32)
    y64, _ = ssd_reference(x, dt, A, B, C, 64)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64), atol=1e-4,
                               rtol=1e-4)


def test_ssd_decode_matches_prefill():
    """Token-by-token decode must reproduce the chunked prefill outputs."""
    b, l, h, p, g, n = 1, 32, 2, 8, 1, 8
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.random((b, l, h)) * 0.4 + 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random((h,)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    y_ref, s_ref = ssd_reference(x, dt, A, B, C, 16)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(l):
        y_t, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                     B[:, t], C[:, t])
        ys.append(y_t)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_ref),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# AMIL probe
# ---------------------------------------------------------------------------

@given(st.integers(1, 500), st.integers(16, 256), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_amil_probe_property(n_req, n_slots_16, seed):
    rng = np.random.default_rng(seed)
    n_slots = n_slots_16 * 8
    meta = jnp.asarray(rng.integers(0, 64, (n_slots,)), jnp.int32)
    slots = jnp.asarray(rng.integers(0, n_slots, (n_req,)), jnp.int32)
    tags = jnp.asarray(rng.integers(0, 4, (n_req,)), jnp.int32)
    h1, d1, a1 = probe(meta, slots, tags, interpret=True)
    h2, d2, a2 = amil_probe_reference(meta, slots, tags)
    assert (np.asarray(h1) == np.asarray(h2)).all()
    assert (np.asarray(d1) == np.asarray(d2)).all()
    assert (np.asarray(a1) == np.asarray(a2)).all()


def test_amil_pack_roundtrip():
    from repro.core.amil import pack_line_meta, unpack_line_meta
    tags = jnp.arange(4)
    valid = jnp.asarray([0, 1, 1, 0], bool)
    dirty = jnp.asarray([1, 0, 1, 0], bool)
    aff = jnp.asarray([3, 2, 1, 0])
    t, v, d, a = unpack_line_meta(pack_line_meta(tags, valid, dirty, aff))
    assert (np.asarray(t) == np.asarray(tags)).all()
    assert (np.asarray(v) == np.asarray(valid)).all()
    assert (np.asarray(d) == np.asarray(dirty)).all()
    assert (np.asarray(a) == np.asarray(aff)).all()


@jax.enable_x64(True)                 # the row word is a uint64
def test_amil_row_word_roundtrip():
    from repro.core.amil import (pack_row_meta, row_meta_to_u64,
                                 u64_to_row_meta)
    rng = np.random.default_rng(0)
    tags = jnp.asarray(rng.integers(0, 4, (5, 8)))
    valid = jnp.asarray(rng.integers(0, 2, (5, 8)), bool)
    dirty = jnp.asarray(rng.integers(0, 2, (5, 8)), bool)
    aff = jnp.asarray(rng.integers(0, 4, (5, 8)))
    row = pack_row_meta(tags, valid, dirty, aff)
    back = u64_to_row_meta(row_meta_to_u64(row))
    assert (np.asarray(back) == np.asarray(row)).all()

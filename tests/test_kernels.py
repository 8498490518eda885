"""Per-kernel validation: shape/dtype sweeps + hypothesis, each Pallas kernel
(interpret mode) against its pure-jnp ref.py oracle."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.event_accum.ops import event_accum
from repro.kernels.event_accum.ref import event_accum_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.lif.ops import lif_fused
from repro.kernels.lif.ref import lif_fused_ref
from repro.kernels.spike_matmul.ops import spike_matmul
from repro.kernels.spike_matmul.ref import spike_matmul_ref
from repro.kernels.ttfs_decode.ops import ttfs_decode
from repro.kernels.ttfs_decode.ref import ttfs_decode_ref


# ------------------------------------------------------------------- LIF
@pytest.mark.parametrize("B,T,N,ls", [(1, 4, 128, 4), (3, 16, 256, 2),
                                      (2, 32, 512, 6), (5, 7, 128, 31)])
def test_lif_shapes(B, T, N, ls):
    rng = np.random.RandomState(B * 100 + T)
    cur = jnp.asarray(rng.randint(-80, 150, (B, T, N)), jnp.int32)
    thr = jnp.asarray(rng.randint(10, 500, (N,)), jnp.int32)
    f_ref, v_ref = lif_fused_ref(cur, thr, ls)
    res = lif_fused(jnp.moveaxis(cur, 1, 0), thr, ls)
    assert np.array_equal(np.asarray(f_ref), np.asarray(res.first_spike))
    assert np.array_equal(np.asarray(v_ref), np.asarray(res.v_final))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_lif_property(seed):
    rng = np.random.RandomState(seed % 2**32)
    B, T, N = rng.randint(1, 4), rng.randint(1, 24), 128 * rng.randint(1, 3)
    ls = int(rng.randint(1, 10))
    cur = jnp.asarray(rng.randint(-200, 300, (B, T, N)), jnp.int32)
    thr = jnp.asarray(rng.randint(1, 800, (N,)), jnp.int32)
    f_ref, v_ref = lif_fused_ref(cur, thr, ls)
    res = lif_fused(jnp.moveaxis(cur, 1, 0), thr, ls)
    assert np.array_equal(np.asarray(f_ref), np.asarray(res.first_spike))
    assert np.array_equal(np.asarray(v_ref), np.asarray(res.v_final))
    # sentinel semantics: never-fired lanes report exactly T
    fired = np.asarray(res.first_spike) < T
    assert np.all(np.asarray(res.first_spike)[~fired] == T)


# ----------------------------------------------------------- spike matmul
@pytest.mark.parametrize("B,T,K,N", [(1, 2, 100, 128), (2, 8, 784, 256),
                                     (1, 16, 300, 384), (4, 3, 129, 128)])
def test_spike_matmul_shapes(B, T, K, N):
    rng = np.random.RandomState(K)
    raster = jnp.asarray(rng.randint(0, 2, (B, T, K)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (K, N)), jnp.int8)
    assert np.array_equal(np.asarray(spike_matmul(raster, w)),
                          np.asarray(spike_matmul_ref(raster, w)))


# ------------------------------------------------------------ event accum
@pytest.mark.parametrize("T,E,K,N", [(4, 16, 100, 128), (8, 64, 784, 256),
                                     (2, 128, 300, 128)])
def test_event_accum_shapes(T, E, K, N):
    rng = np.random.RandomState(T * E)
    ids = jnp.asarray(rng.randint(-1, K, (T, E)), jnp.int32)
    w = jnp.asarray(rng.randint(-127, 128, (K, N)), jnp.int8)
    assert np.array_equal(np.asarray(event_accum(ids[None], w)[0]),
                          np.asarray(event_accum_ref(ids, w)))


def test_event_accum_all_padding_is_zero():
    w = jnp.asarray(np.random.RandomState(0).randint(-127, 128, (50, 128)),
                    jnp.int8)
    ids = jnp.full((2, 4, 16), -1, jnp.int32)
    assert np.all(np.asarray(event_accum(ids, w)) == 0)


# ------------------------------------------------------------ ttfs decode
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_ttfs_decode_property(seed):
    rng = np.random.RandomState(seed % 2**32)
    G, P, T = 10, 15, int(rng.randint(2, 64))
    B = int(rng.randint(1, 8))
    first = jnp.asarray(rng.randint(0, T + 1, (B, G * P)), jnp.int32)
    v = jnp.asarray(rng.randint(-500, 500, (B, G * P)), jnp.int32)
    for fb in ("membrane", "zero"):
        a = ttfs_decode(first, v, n_groups=G, per_group=P, sentinel=T,
                        fallback=fb)
        b = ttfs_decode_ref(first, v, n_groups=G, per_group=P, sentinel=T,
                            fallback=fb)
        assert np.array_equal(np.asarray(a), np.asarray(b)), fb


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window,qoff", [
    (1, 4, 4, 128, 128, 64, True, None, 0),
    (2, 8, 2, 128, 256, 64, True, None, 128),      # GQA + decode-offset
    (1, 4, 1, 256, 256, 128, True, 64, 0),         # SWA
    (1, 2, 2, 128, 384, 64, False, None, 0),       # cross-attention style
    (2, 4, 4, 8, 128, 64, True, None, 120),        # short q against cache
])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal, window, qoff,
                               dtype):
    rng = np.random.RandomState(Sq + Skv)
    q = jnp.asarray(rng.randn(B, Hq, Sq, D), dtype)
    k = jnp.asarray(rng.randn(B, Hkv, Skv, D), dtype)
    v = jnp.asarray(rng.randn(B, Hkv, Skv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, q_offset=qoff)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              q_offset=qoff)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_matches_chunked_layer():
    """The Pallas kernel and the jnp chunked attention (the dry-run path)
    agree — so the TPU kernel is a drop-in for the compiled model."""
    from repro.models.layers import chunked_attention
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 8, 256, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 256, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 256, 64), jnp.float32)
    a = flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, bq=128, bk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)

"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracles in ``repro.kernels.ref`` (interpret mode on CPU)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

KEY = jax.random.PRNGKey(0)


def _ring_mesh(n=4):
    import numpy as _np
    from jax.sharding import Mesh
    return Mesh(_np.asarray(jax.devices()[:n]), ("x",))


# ===========================================================================
# ODC comm kernels
# ===========================================================================
@pytest.mark.parametrize("shape,dtype", [
    ((4, 8), jnp.float32), ((2, 16), jnp.bfloat16), ((8, 4), jnp.float32),
    ((3, 5), jnp.float32),
])
def test_odc_gather_matches_all_gather(shape, dtype):
    mesh = _ring_mesh()
    n = 4
    x = jax.random.normal(KEY, (n * shape[0],) + shape[1:]).astype(dtype)

    def f(xs):
        return ops.odc_gather(xs, "x", interpret=True)

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                out_specs=P(None), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(x, np.float32), rtol=0, atol=0)


@pytest.mark.parametrize("c,f,dtype", [(2, 8, jnp.float32),
                                       (4, 4, jnp.bfloat16),
                                       (1, 16, jnp.float32)])
def test_odc_scatter_matches_psum_scatter(c, f, dtype):
    mesh = _ring_mesh()
    n = 4
    # per-device distinct contributions, stacked on a device axis
    y = jax.random.normal(KEY, (n, n * c, f)).astype(dtype)

    def f_odc(yd):
        return ops.odc_scatter_accumulate(yd[0], "x", interpret=True)

    def f_ref(yd):
        return jax.lax.psum_scatter(yd[0], "x", scatter_dimension=0,
                                    tiled=True)

    run = lambda fn: jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        check_vma=False))(y)
    np.testing.assert_allclose(
        np.asarray(run(f_odc), np.float32),
        np.asarray(run(f_ref), np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("L,c,f,dtype", [
    (3, 2, 5, jnp.float32), (2, 4, 8, jnp.bfloat16), (5, 1, 16, jnp.float32),
])
def test_odc_gather_layers_matches_stacked_all_gather(L, c, f, dtype):
    """Cross-layer gather: L chained rings with no inter-layer barrier
    must reproduce every layer's full tensor."""
    mesh = _ring_mesh()
    n = 4
    x = jax.random.normal(KEY, (L, n * c, f)).astype(dtype)

    def fn(xs):  # xs: (L, c, f) local
        return ops.odc_gather_layers(xs, "x", interpret=True)

    out = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(None, "x"),
                                out_specs=P(None), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(x, np.float32), rtol=0, atol=0)


@pytest.mark.parametrize("L,c,f,dtype", [
    (3, 2, 5, jnp.float32), (2, 4, 8, jnp.bfloat16),
])
def test_odc_scatter_layers_matches_per_layer_psum_scatter(L, c, f, dtype):
    mesh = _ring_mesh()
    n = 4
    # per-device distinct contributions for every layer
    y = jax.random.normal(KEY, (n, L, n * c, f)).astype(dtype)

    def f_odc(yd):
        return ops.odc_scatter_accumulate_layers(yd[0], "x", interpret=True)

    def f_ref(yd):
        return jax.lax.psum_scatter(yd[0], "x", scatter_dimension=1,
                                    tiled=True)

    run = lambda fn: jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("x"), out_specs=P(None, "x"),
        check_vma=False))(y)
    np.testing.assert_allclose(
        np.asarray(run(f_odc), np.float32),
        np.asarray(run(f_ref), np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("m,k,f", [(8, 16, 8), (4, 8, 16), (16, 32, 8)])
def test_gather_matmul_overlap(m, k, f):
    mesh = _ring_mesh()
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, f))

    def fn(x_loc, w_shard):
        return ops.gather_matmul(x_loc, w_shard, "x", interpret=True)

    out = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(None, None), P("x", None)),
        out_specs=P(None, None), check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


# ===========================================================================
# flash attention: sweep shapes / features / dtypes
# ===========================================================================
@pytest.mark.parametrize("B,S,T,H,KH,hd", [
    (2, 64, 64, 4, 2, 32),
    (1, 96, 96, 4, 4, 32),   # MHA
    (2, 64, 64, 8, 2, 64),   # GQA 4:1
    (1, 60, 60, 2, 1, 16),   # non-block-multiple lengths (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, T, H, KH, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, KH, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, KH, hd)).astype(dtype)
    out = flash_attention_pallas(q, k, v, blk_q=32, blk_k=32, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,softcap,causal", [
    (16, 0.0, True), (0, 50.0, True), (32, 30.0, True), (0, 0.0, False),
])
def test_flash_attention_features(window, softcap, causal):
    B, S, H, KH, hd = 2, 64, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KH, hd))
    v = jax.random.normal(ks[2], (B, S, KH, hd))
    seg = jnp.concatenate([jnp.zeros((B, S // 2), jnp.int32),
                           jnp.ones((B, S - S // 2), jnp.int32)], axis=1)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=softcap,
        q_segment_ids=seg, kv_segment_ids=seg, blk_q=32, blk_k=32,
        interpret=True)
    expect = ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, logit_softcap=softcap,
        q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ===========================================================================
# SSD scan: sweep (heads, groups, state, chunk) and dtypes
# ===========================================================================
@pytest.mark.parametrize("b,s,h,p,g,n,Q", [
    (2, 64, 4, 16, 1, 8, 16),
    (1, 128, 8, 32, 2, 16, 32),
    (2, 96, 6, 8, 3, 4, 32),
    (1, 64, 2, 64, 2, 64, 64),  # zamba2-like head_dim/state
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_shapes(b, s, h, p, g, n, Q, dtype):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (b, s, g, n)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (b, s, g, n)) * 0.5).astype(dtype)
    y, st = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
    y_ref, st_ref = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=Q)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=tol, atol=tol)


def test_ssd_scan_chunk_invariance():
    """The chunked duality must be chunk-size invariant."""
    b, s, h, p, g, n = 1, 64, 4, 16, 2, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    y16, st16 = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    y64, st64 = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st16), np.asarray(st64),
                               rtol=1e-4, atol=1e-4)


# ===========================================================================
# the one interpret switch
# ===========================================================================
def test_interpret_mode_follows_platform_and_is_refused_on_tpu(monkeypatch):
    from repro.kernels import interpret_mode

    assert interpret_mode(None) is True  # this host is not a TPU
    assert interpret_mode(False) is False  # compile for a described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode(None) is False
    with pytest.raises(ValueError, match="interpret mode requested on a TPU"):
        interpret_mode(True)

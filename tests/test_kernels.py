"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cin_fuse import ops as cin_ops, ref as cin_ref
from repro.kernels.decode_attention import ops as dec_ops, ref as dec_ref
from repro.kernels.embedding_bag import ops as bag_ops, ref as bag_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.maxplus_scan import ops as mp_ops, ref as mp_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-3)


# -------------------------------------------------------------------- maxplus
@pytest.mark.parametrize("shape,blk", [
    ((4, 1024), 256), ((1, 37), 512), ((2, 3, 500), 128), ((8, 4096), 512),
])
def test_maxplus_scan_sweep(shape, blk):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    arr = jnp.cumsum(jax.random.exponential(k1, shape), -1)
    svc = jax.random.exponential(k2, shape)
    oa, ob = mp_ops.maxplus_scan(arr + svc, svc, block_len=blk)
    ra, rb = mp_ref.maxplus_scan_ref(arr + svc, svc)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(ra), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(rb), rtol=1e-5)


@pytest.mark.parametrize("backend,interpret", [
    ("tpu", False), ("cpu", True), ("gpu", None)])
def test_maxplus_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Compiled on a TPU, interpreted on the CPU, refused elsewhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            mp_ops.interpret_mode()
        with pytest.raises(RuntimeError, match="neither"):
            mp_ops.resolve_scan_impl("pallas")
        assert mp_ops.resolve_scan_impl("auto") == "xla"
    else:
        assert mp_ops.interpret_mode() is interpret
        assert mp_ops.resolve_scan_impl("pallas") == "pallas"


def test_maxplus_ref_equals_sequential():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(k1, (3, 257))
    b = jax.random.exponential(k2, (3, 257))
    ra, rb = mp_ref.maxplus_scan_ref(a, b)
    sa, sb = mp_ref.maxplus_scan_sequential(a, b)
    np.testing.assert_allclose(np.asarray(ra), np.asarray(sa), rtol=1e-5)


# ----------------------------------------------------------------- flash attn
@pytest.mark.parametrize("b,s,h,kv,d", [
    (2, 256, 4, 2, 64), (1, 512, 8, 8, 128), (2, 128, 4, 1, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True)
    qr = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * kv, s, d)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * kv, s, d)
    expect = fa_ref.flash_attention_ref(qr, kr, vr, n_rep=h // kv)
    expect = jnp.moveaxis(expect.reshape(b, h, s, d), 1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype))


# ---------------------------------------------------------------- decode attn
@pytest.mark.parametrize("b,s,h,kv,d,ln", [
    (2, 1024, 8, 2, 64, 700), (1, 512, 4, 4, 128, 511),
    (2, 512, 16, 8, 64, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, s, h, kv, d, ln, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    vc = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = dec_ops.decode_attention(q, kc, vc, jnp.asarray(ln))
    g = h // kv
    qr = q.reshape(b, kv, g, d).reshape(b * kv, g, d)
    kr = jnp.moveaxis(kc, 2, 1).reshape(b * kv, s, d)
    vr = jnp.moveaxis(vc, 2, 1).reshape(b * kv, s, d)
    expect = dec_ref.decode_attention_ref(
        qr, kr, vr, jnp.asarray(ln)).reshape(b, 1, h, d)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype))


# -------------------------------------------------------------- embedding bag
@pytest.mark.parametrize("r,d,b,f,m", [
    (1000, 16, 4, 6, 3), (512, 8, 8, 2, 1), (4096, 64, 2, 4, 5),
])
def test_embedding_bag_sweep(r, d, b, f, m):
    table = jax.random.normal(jax.random.PRNGKey(4), (r, d), jnp.float32)
    rng = np.random.default_rng(0)
    counts = rng.integers(1, m + 1, (b, f))
    ids = rng.integers(0, r, (b, f, m)).astype(np.int32)
    mask = np.arange(m)[None, None, :] < counts[:, :, None]
    out = bag_ops.embedding_bag(table, jnp.asarray(ids), jnp.asarray(mask))
    expect = bag_ref.embedding_bag_ref(
        table, jnp.asarray(np.where(mask, ids, 0).reshape(b * f, m)),
        jnp.asarray(counts.reshape(-1).astype(np.int32))).reshape(b, f, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_embedding_bag_matches_model_op():
    """Kernel == the model's jnp embedding_bag (drop-in contract)."""
    from repro.models.recsys import embedding_bag as model_bag
    table = jax.random.normal(jax.random.PRNGKey(5), (256, 8), jnp.float32)
    rng = np.random.default_rng(1)
    counts = rng.integers(1, 4, (3, 5))
    ids = rng.integers(0, 256, (3, 5, 4)).astype(np.int32)
    mask = np.arange(4)[None, None, :] < counts[:, :, None]
    out_k = bag_ops.embedding_bag(table, jnp.asarray(ids),
                                  jnp.asarray(mask))
    out_m = model_bag(table, jnp.asarray(ids), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------------- cin
@pytest.mark.parametrize("b,hk,m,d,o", [
    (512, 12, 6, 10, 16), (300, 8, 8, 4, 8), (64, 39, 39, 10, 200),
])
def test_cin_fuse_sweep(b, hk, m, d, o):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    xk = jax.random.normal(ks[0], (b, hk, d), jnp.float32)
    x0 = jax.random.normal(ks[1], (b, m, d), jnp.float32)
    w = jax.random.normal(ks[2], (hk * m, o), jnp.float32) * 0.1
    out = cin_ops.cin_layer(xk, x0, w, block_b=64)
    expect = cin_ref.cin_layer_ref(xk, x0, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


# -------------------------------------------------- maxplus (segmented)
@pytest.mark.parametrize("shape,blk", [
    ((4, 1024), 256), ((1, 37), 512), ((2, 3, 500), 128), ((8, 2048), 512),
])
def test_maxplus_segment_scan_sweep(shape, blk):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    a = jnp.cumsum(jax.random.exponential(ks[0], shape), -1)
    b = jax.random.exponential(ks[1], shape)
    f = jax.random.uniform(ks[2], shape) < 0.05
    oa, ob = mp_ops.maxplus_segment_scan(a, b, f, block_len=blk)
    ra, rb = mp_ref.maxplus_segment_scan_ref(a, b, f)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(ra), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(rb), rtol=1e-5)


def test_maxplus_segment_ref_equals_sequential():
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    a = jax.random.normal(ks[0], (3, 257))
    b = jax.random.exponential(ks[1], (3, 257))
    f = jax.random.uniform(ks[2], (3, 257)) < 0.1
    ra, rb = mp_ref.maxplus_segment_scan_ref(a, b, f)
    sa, sb = mp_ref.maxplus_segment_scan_sequential(a, b, f)
    np.testing.assert_allclose(np.asarray(ra), np.asarray(sa), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(rb), np.asarray(sb), rtol=1e-5,
                               atol=1e-5)


def test_maxplus_segment_no_flags_equals_plain():
    """With zero reset flags the segmented kernel IS the plain scan."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    a = jnp.cumsum(jax.random.exponential(k1, (4, 777)), -1)
    b = jax.random.exponential(k2, (4, 777))
    f = jnp.zeros_like(a, dtype=bool)
    sa, sb = mp_ops.maxplus_segment_scan(a, b, f, block_len=256)
    pa, pb = mp_ops.maxplus_scan(a, b, block_len=256)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(pa), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sb), np.asarray(pb), rtol=1e-6)


def test_maxplus_segment_every_flag_resets():
    """All-flags input degenerates to the identity: out == (a, b)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(10))
    a = jax.random.normal(k1, (2, 300))
    b = jax.random.exponential(k2, (2, 300))
    f = jnp.ones_like(a, dtype=bool)
    sa, sb = mp_ops.maxplus_segment_scan(a, b, f, block_len=128)
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(sb), np.asarray(b))

"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
shape/dtype sweeps (the hypothesis sweep lives in test_property_sweeps.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.a2cid2_mixing.kernel import mixing_p2p
from repro.kernels.a2cid2_mixing.ref import mixing_p2p_ref
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_2d
from repro.kernels.rmsnorm.ref import rmsnorm_ref


# ------------------------------------------------------------ a2cid2_mixing

@pytest.mark.parametrize("wide", [False, True])
def test_row_gathers_equal_advanced_indexing(monkeypatch, wide):
    """The reference's row gathers give ``x[idx]`` bit for bit on both
    sides of WIDE_ROW: XLA's gather below it, one dynamic slice per row
    at or above it (the form the TPU compiler handles at model width)."""
    from repro.kernels.a2cid2_mixing import ref
    if wide:
        monkeypatch.setattr(ref, "WIDE_ROW", 1)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (2, 4, 256))
    pool = jax.random.normal(ks[1], (3, 2, 5, 256))
    idx = jnp.asarray([2, 0, 3, 3], jnp.int32)
    partner = jnp.asarray([[1, 0, 3, 2], [0, 1, 2, 3]], jnp.int32)
    hop = jnp.asarray([[0, 2, 1], [1, 1, 0]], jnp.int32)
    pos = jnp.asarray([[4, 0, 2], [3, 1, 1]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(ref.take_rows(x[0], idx)),
                                  np.asarray(x[0])[np.asarray(idx)])
    want = np.take_along_axis(np.asarray(x), np.asarray(partner)[:, :, None],
                              axis=1)
    np.testing.assert_array_equal(
        np.asarray(ref.take_partner_rows(x, partner)), want)
    want = np.asarray(pool)[np.asarray(hop), np.arange(2)[:, None],
                            np.asarray(pos)]
    np.testing.assert_array_equal(
        np.asarray(ref.take_pool_rows(pool, hop, pos)), want)


@pytest.mark.parametrize("n", [128, 4096, 70_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mixing_kernel_matches_oracle(n, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (n,), dtype)
    xt = jax.random.normal(ks[1], (n,), dtype)
    xp = jax.random.normal(ks[2], (n,), dtype)
    kw = dict(eta=0.3, alpha=0.5, alpha_t=1.8)
    ox, ot = mixing_p2p(x, xt, xp, jnp.float32(0.7), interpret=True, **kw)
    rx, rt = mixing_p2p_ref(x, xt, xp, 0.7, **kw)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ox, np.float32),
                               np.asarray(rx, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(ot, np.float32),
                               np.asarray(rt, np.float32), atol=atol)


@pytest.mark.parametrize("n,eta,dt,alpha_t", [
    (3, 0.0, 0.0, 0.1), (777, 1.3, 2.2, 1.8), (3000, 2.0, 5.0, 3.0),
])
def test_mixing_kernel_param_sweep(n, eta, dt, alpha_t):
    key = jax.random.PRNGKey(n)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (n,))
    xt = jax.random.normal(ks[1], (n,))
    xp = jax.random.normal(ks[2], (n,))
    kw = dict(eta=eta, alpha=0.5, alpha_t=alpha_t)
    ox, ot = mixing_p2p(x, xt, xp, jnp.float32(dt), interpret=True, **kw)
    rx, rt = mixing_p2p_ref(x, xt, xp, dt, **kw)
    np.testing.assert_allclose(ox, rx, atol=1e-4)
    np.testing.assert_allclose(ot, rt, atol=1e-4)


def test_mixing_kernel_preserves_buffer_sum():
    """Invariant: with alpha = alpha_t the update keeps x + x~ - (alpha+
    alpha_t) m consistent; specifically mixing alone preserves x + x~."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1000,))
    xt = jax.random.normal(jax.random.fold_in(key, 1), (1000,))
    # alpha = alpha_t = 0: pure mixing => x + x~ invariant
    ox, ot = mixing_p2p(x, xt, x, jnp.float32(1.3), eta=0.7, alpha=0.0,
                        alpha_t=0.0, interpret=True)
    np.testing.assert_allclose(ox + ot, x + xt, atol=1e-5)


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("S,T,hd,causal,window", [
    (128, 128, 64, True, None),
    (256, 256, 64, True, None),
    (256, 256, 128, False, None),
    (200, 200, 64, True, 64),       # unaligned seq + window
    (130, 384, 64, False, None),    # cross attention shape
])
def test_flash_attention_matches_oracle(S, T, hd, causal, window):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, S, hd))
    k = jax.random.normal(ks[1], (2, T, hd))
    v = jax.random.normal(ks[2], (2, T, hd))
    out = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                               interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 256, 64), dtype)
    k = jax.random.normal(ks[1], (1, 256, 64), dtype)
    v = jax.random.normal(ks[2], (1, 256, 64), dtype)
    out = flash_attention_bhsd(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_gqa_wrapper_matches_model_sdpa():
    """The ops wrapper (B,S,H,hd with GQA broadcast) must match the model's
    XLA attention path."""
    from repro.models.attention import _sdpa, causal_mask
    from repro.configs import get_config
    cfg = get_config("qwen3-14b", reduced=True)
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 2, 128, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    ref = _sdpa(q, k, v, causal_mask(S), cfg).reshape(B, S, H, hd)
    out = flash_attention(q, k, v, causal=True, force_pallas=True,
                          interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------------ rmsnorm

@pytest.mark.parametrize("T,D,dtype", [
    (64, 512, jnp.float32), (128, 1024, jnp.bfloat16),
    (130, 768, jnp.float32), (1, 256, jnp.float32),
])
def test_rmsnorm_kernel_matches_oracle(T, D, dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, D), dtype)
    sc = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (D,), dtype)
    out = rmsnorm_2d(x, sc, interpret=True)
    ref = rmsnorm_ref(x, sc)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_rmsnorm_output_is_unit_rms():
    x = 5.0 * jax.random.normal(jax.random.PRNGKey(0), (32, 512))
    out = rmsnorm_2d(x, jnp.zeros(512), interpret=True)
    rms = jnp.sqrt(jnp.mean(out * out, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)

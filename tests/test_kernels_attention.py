"""Flash-attention Pallas kernel vs oracle: masks, GQA, softcap, ragged."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(1)


def _qkv(b, h, hkv, sq, skv, d, dtype=np.float32):
    q = jnp.asarray(RNG.standard_normal((b, h, sq, d)).astype(dtype))
    k = jnp.asarray(RNG.standard_normal((b, hkv, skv, d)).astype(dtype))
    v = jnp.asarray(RNG.standard_normal((b, hkv, skv, d)).astype(dtype))
    return q, k, v


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 4, 4, 128, 64),    # MHA
    (2, 4, 2, 128, 64),    # GQA
    (1, 8, 1, 256, 32),    # MQA
    (2, 4, 2, 100, 64),    # ragged seq
    (1, 2, 1, 333, 128),   # ragged + larger head
])
def test_causal_sweep(b, h, hkv, s, d):
    q, k, v = _qkv(b, h, hkv, s, s, d)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("window", [16, 32, 100])
def test_sliding_window(window):
    q, k, v = _qkv(2, 4, 2, 160, 160, 64)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


def test_softcap_gemma2_style():
    q, k, v = _qkv(1, 4, 2, 128, 128, 64)
    out = ops.flash_attention(q, k, v, causal=True, softcap=50.0)
    want = ref.flash_attention(q, k, v, causal=True, softcap=50.0)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


def test_decode_style_right_aligned():
    """sq < skv: q positions are right-aligned (chunked prefill / decode)."""
    q, k, v = _qkv(1, 4, 4, 40, 200, 64)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


def test_bf16_inputs():
    q, k, v = _qkv(1, 4, 2, 128, 128, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.float32(out), np.float32(want),
                               rtol=3e-2, atol=3e-2)


def test_window_plus_softcap_combined():
    q, k, v = _qkv(1, 4, 2, 200, 200, 64)
    out = ops.flash_attention(q, k, v, causal=True, window=64, softcap=30.0)
    want = ref.flash_attention(q, k, v, causal=True, window=64, softcap=30.0)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


def test_chunked_xla_attention_matches_kernel_semantics(monkeypatch):
    """The XLA fallback (used inside pjit graphs) agrees with the oracle,
    in both the direct and the kv-chunked online-softmax regimes."""
    import repro.models.attention as A
    q, k, v = _qkv(2, 4, 2, 96, 96, 32)
    want = ref.flash_attention(q, k, v, causal=True, window=24)

    def xla(q, k, v):       # the kernel's (B, H, S, D) in and out
        t = lambda a: a.transpose(0, 2, 1, 3)
        return t(A._xla_attention(t(q), t(k), t(v), causal=True, window=24,
                                  softcap=None, scale=32 ** -0.5))

    direct = xla(q, k, v)
    np.testing.assert_allclose(direct, want, rtol=3e-4, atol=3e-4)
    monkeypatch.setattr(A, "_CHUNK_THRESHOLD", 32)  # force chunked path
    chunked = xla(q, k, v)
    np.testing.assert_allclose(chunked, want, rtol=3e-4, atol=3e-4)


def test_rglru_scan_kernel():
    """RG-LRU linear recurrence kernel vs lax.scan oracle."""
    rng = np.random.default_rng(9)
    for (b, s, w) in [(2, 64, 128), (1, 100, 256), (3, 7, 128)]:
        a = jnp.asarray(
            np.exp(-np.abs(rng.standard_normal((b, s, w)))).astype(np.float32))
        x = jnp.asarray(rng.standard_normal((b, s, w)).astype(np.float32))
        out = ops.rglru_scan(a, x)
        want = ref.rglru_scan(a, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_rglru_prefill_uses_kernel_and_matches():
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import model as model_lib
    cfg_x = get_config("recurrentgemma_9b").reduced()
    cfg_p = dataclasses.replace(cfg_x, gemm_backend="pallas")
    key = jax.random.PRNGKey(11)
    params = model_lib.init_params(key, cfg_x)
    tokens = jax.random.randint(key, (2, 24), 0, cfg_x.vocab)
    lx, cx = model_lib.prefill(params, {"tokens": tokens}, cfg_x,
                               cache_len=32)
    lp, cp = model_lib.prefill(params, {"tokens": tokens}, cfg_p,
                               cache_len=32)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                               rtol=3e-3, atol=3e-3)

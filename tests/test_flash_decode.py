"""Flash-decode kernels: parity vs a plain-XLA attention reference for
ragged sequence lengths, bf16 storage, and the page-table-indexed paged
variant (including pages smaller than the flat kernel's block size and
in-kernel int8 dequantization)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.flash_decode import (flash_decode_paged_pallas,
                                        flash_decode_pallas)

B, H, HKV, D = 3, 4, 2, 32
SEQ_LENS = np.array([5, 17, 25], np.int32)  # ragged: straddles pages/blocks


def _np_reference(q, k, v, seq_lens, *, window=None, softcap=None):
    """Dense per-sequence softmax attention (GQA), f64 accumulation."""
    b, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = 1.0 / np.sqrt(d)
    out = np.zeros((b, h, d), np.float64)
    for bi in range(b):
        n = int(seq_lens[bi])
        qpos = n - 1
        for hi in range(h):
            kv = hi // g
            logits = (k[bi, kv, :n].astype(np.float64)
                      @ q[bi, hi].astype(np.float64)) * scale
            if softcap is not None:
                logits = softcap * np.tanh(logits / softcap)
            pos = np.arange(n)
            mask = pos <= qpos
            if window is not None:
                mask &= pos > qpos - window
            logits = np.where(mask, logits, -np.inf)
            p = np.exp(logits - logits.max())
            p = p / p.sum()
            out[bi, hi] = p @ v[bi, kv, :n].astype(np.float64)
    return out


def _ragged_inputs(dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    s = int(SEQ_LENS.max())
    q = rng.standard_normal((B, H, D)).astype(dtype)
    k = rng.standard_normal((B, HKV, s, D)).astype(dtype)
    v = rng.standard_normal((B, HKV, s, D)).astype(dtype)
    kvpos = np.where(np.arange(s)[None] < SEQ_LENS[:, None],
                     np.arange(s)[None], -1).astype(np.int32)
    qpos = (SEQ_LENS - 1).astype(np.int32)
    return q, k, v, kvpos, qpos


def _paged_layout(k, v, seq_lens, page):
    """Pack contiguous (B, Hkv, S, D) KV into (P, page, Hkv, D) pages +
    page table, physical page 0 reserved as the null page."""
    b, hkv, s, d = k.shape
    maxp = -(-s // page)
    total = 1 + sum(-(-int(n) // page) for n in seq_lens)
    k_pages = np.zeros((total, page, hkv, d), k.dtype)
    v_pages = np.zeros((total, page, hkv, d), v.dtype)
    table = np.full((b, maxp), -1, np.int32)
    nxt = 1
    for bi in range(b):
        for lp in range(-(-int(seq_lens[bi]) // page)):
            table[bi, lp] = nxt
            sl = slice(lp * page, (lp + 1) * page)
            chunk_k = k[bi, :, sl].transpose(1, 0, 2)
            chunk_v = v[bi, :, sl].transpose(1, 0, 2)
            k_pages[nxt, : chunk_k.shape[0]] = chunk_k
            v_pages[nxt, : chunk_v.shape[0]] = chunk_v
            nxt += 1
    return k_pages, v_pages, table


def test_flat_kernel_matches_reference_ragged():
    q, k, v, kvpos, qpos = _ragged_inputs()
    out = flash_decode_pallas(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(kvpos), jnp.asarray(qpos),
                              interpret=True)
    ref = _np_reference(q, k, v, SEQ_LENS)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_flat_kernel_bf16_storage():
    """bf16 KV storage: the kernel upcasts to f32 internally, so the
    result must match the bf16-rounded reference at bf16 tolerance."""
    q, k, v, kvpos, qpos = _ragged_inputs()
    kb = jnp.asarray(k).astype(jnp.bfloat16)
    vb = jnp.asarray(v).astype(jnp.bfloat16)
    qb = jnp.asarray(q).astype(jnp.bfloat16)
    out = flash_decode_pallas(qb, kb, vb, jnp.asarray(kvpos),
                              jnp.asarray(qpos), interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _np_reference(np.asarray(qb.astype(jnp.float32)),
                        np.asarray(kb.astype(jnp.float32)),
                        np.asarray(vb.astype(jnp.float32)), SEQ_LENS)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), ref,
                               rtol=2e-2, atol=2e-2)


def test_flat_kernel_softcap_and_window():
    q, k, v, kvpos, qpos = _ragged_inputs(seed=1)
    out = flash_decode_pallas(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(kvpos), jnp.asarray(qpos),
                              window=8, softcap=30.0, interpret=True)
    ref = _np_reference(q, k, v, SEQ_LENS, window=8, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page", [8, 16])
def test_paged_kernel_matches_flat(page):
    """Paged == flat on the same logical KV, for a page smaller than the
    flat kernel's minimum block (128) and at intermediate sizes."""
    q, k, v, kvpos, qpos = _ragged_inputs(seed=2)
    flat = flash_decode_pallas(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(kvpos),
                               jnp.asarray(qpos), interpret=True)
    k_pages, v_pages, table = _paged_layout(k, v, SEQ_LENS, page)
    out = flash_decode_paged_pallas(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(table), jnp.asarray(SEQ_LENS), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_ignores_stale_page_contents():
    """Slots past seq_len inside a mapped page, and unmapped logical
    pages, must not leak into the output even when they hold garbage."""
    q, k, v, kvpos, qpos = _ragged_inputs(seed=3)
    page = 8
    k_pages, v_pages, table = _paged_layout(k, v, SEQ_LENS, page)
    # poison every slot the mask should hide (incl. the null page)
    k_bad, v_bad = k_pages.copy(), v_pages.copy()
    k_bad[0] = 1e6
    v_bad[0] = 1e6
    for bi in range(B):
        n = int(SEQ_LENS[bi])
        last = table[bi, (n - 1) // page]
        k_bad[last, n % page or page:] = 1e6
        v_bad[last, n % page or page:] = 1e6
    out = flash_decode_paged_pallas(
        jnp.asarray(q), jnp.asarray(k_bad), jnp.asarray(v_bad),
        jnp.asarray(table), jnp.asarray(SEQ_LENS), interpret=True)
    ref = _np_reference(q, k, v, SEQ_LENS)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_paged_kernel_int8_scales_in_kernel():
    """Quantized pages + in-kernel dequantization track the fp result at
    int8 tolerance (per-(token, head) scales, the int8 KV contract)."""
    q, k, v, kvpos, qpos = _ragged_inputs(seed=4)
    page = 8
    k_pages, v_pages, table = _paged_layout(k, v, SEQ_LENS, page)

    def quant(x):  # (P, page, hkv, d) -> int8 + per-(slot, head) scales
        scale = np.abs(x).max(axis=-1, keepdims=True) / 127.0
        scale = np.where(scale == 0, 1.0, scale)
        qx = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
        return qx, scale.astype(np.float32)

    kq, ks = quant(k_pages)
    vq, vs = quant(v_pages)
    out = flash_decode_paged_pallas(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(table), jnp.asarray(SEQ_LENS),
        jnp.asarray(ks), jnp.asarray(vs), interpret=True)
    ref = _np_reference(q, k, v, SEQ_LENS)
    err = np.max(np.abs(np.asarray(out) - ref))
    span = np.max(np.abs(ref)) + 1e-6
    assert err / span < 0.06, err


def test_paged_kernel_window():
    q, k, v, kvpos, qpos = _ragged_inputs(seed=5)
    page = 8
    k_pages, v_pages, table = _paged_layout(k, v, SEQ_LENS, page)
    out = flash_decode_paged_pallas(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(table), jnp.asarray(SEQ_LENS), window=6, interpret=True)
    ref = _np_reference(q, k, v, SEQ_LENS, window=6)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

"""Flash-decode Pallas kernel: single-token attention over a long KV cache.

Decode attention is the paper's degenerate-GEMM case pushed to the limit —
one query row against a 32k-524k KV cache, with ring-buffer position
semantics for sliding-window layers.  §Perf pair 2 showed GSPMD cannot
sequence-shard this well (softmax all-reduces); the kernel-level answer is
an explicit blocked pass over the cache with online softmax, positions
supplied as data (the ring cache's slot→absolute-position map), grouped
GQA so KV heads are never repeated.

Layout: q (B, H, D) one token per sequence; k/v (B, Hkv, S, D);
kv_positions (B, S) int32 (−1 ⇒ unwritten slot); q_pos (B,) int32.
Grid: (B·Hkv, gkv) — each program owns one (batch, kv-head) pair and all
its G = H/Hkv query heads; the kv axis is walked sequentially with the
online-softmax carry in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import cdiv

__all__ = ["flash_decode_pallas", "flash_decode_paged_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, kvpos_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, gkv: int, bkv: int,
            window: Optional[int], softcap: Optional[float], scale: float):
    ikv = pl.program_id(1)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (G', D)
    k = k_ref[0].astype(jnp.float32)              # (bkv, D)
    kvpos = kvpos_ref[0]                          # (bkv,)
    qpos = qpos_ref[0, 0]                         # scalar

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G', bkv)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    mask = (kvpos >= 0) & (kvpos <= qpos)
    if window is not None:
        mask = mask & (kvpos > qpos - window)
    mask = jnp.broadcast_to(mask[None, :], logits.shape)
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    v = v_ref[0].astype(jnp.float32)
    if True:  # zero ragged/unwritten V rows: 0·NaN = NaN under interpret
        vmask = (kvpos >= 0)[:, None]
        v = jnp.where(vmask, v, jnp.zeros_like(v))
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ikv == gkv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "block_kv",
                              "interpret"))
def flash_decode_pallas(q, k, v, kv_positions, q_pos, *,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        block_kv: int = 512, interpret: bool):
    """One-token attention.  q (B,H,D); k/v (B,Hkv,S,D);
    kv_positions (B,S); q_pos (B,).  Returns (B, H, D) in q.dtype."""
    b, h, d = q.shape
    _, hkv, s, _ = k.shape
    g = h // hkv
    gp = max(8, g)  # pad query-head group to the sublane minimum
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.reshape(b * hkv, gp, d)
    kr = k.reshape(b * hkv, s, d)
    vr = v.reshape(b * hkv, s, d)

    bkv = min(block_kv, max(128, cdiv(s, 128) * 128))
    gkv = cdiv(s, bkv)
    # pad position maps so OOB kv slots read as -1 (masked)
    pad = gkv * bkv - s
    kvp = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-1)
    qp = q_pos.reshape(b, 1).astype(jnp.int32)

    kernel = functools.partial(_kernel, gkv=gkv, bkv=bkv, window=window,
                               softcap=softcap, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, gkv),
        in_specs=[
            pl.BlockSpec((1, gp, d), lambda bn, ikv: (bn, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda bn, ikv: (bn, ikv, 0)),
            pl.BlockSpec((1, bkv, d), lambda bn, ikv: (bn, ikv, 0)),
            pl.BlockSpec((1, bkv), lambda bn, ikv: (bn // hkv, ikv)),
            pl.BlockSpec((1, 1), lambda bn, ikv: (bn // hkv, 0)),
        ],
        out_specs=pl.BlockSpec((1, gp, d), lambda bn, ikv: (bn, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, gp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kr, vr, kvp, qp)
    return out.reshape(b, hkv, gp, d)[:, :, :g].reshape(b, h, d)


# ---------------------------------------------------------------------------
# Paged variant: the KV cache lives in fixed-size pages of a shared pool
# ---------------------------------------------------------------------------


def _paged_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                  npages: int, page: int, hkv: int,
                  window: Optional[int], softcap: Optional[float],
                  scale: float, has_scale: bool):
    if has_scale:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    bn = pl.program_id(0)
    ip = pl.program_id(1)
    bi = bn // hkv

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (G', D)
    k = k_ref[0, 0].astype(jnp.float32)           # (page, D)
    if ks_ref is not None:
        k = k * ks_ref[0, 0]                      # (page, 1) dequant scales
    seq_len = sl_ref[bi]                          # tokens written (incl. cur)
    mapped = pt_ref[bi * npages + ip] >= 0

    # Logical positions of this page's slots: page ip covers
    # [ip·page, (ip+1)·page).  Unmapped logical pages alias physical page
    # 0 via the index map's clamp; their slots are masked here.
    kvpos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    mask = (kvpos < seq_len) & mapped
    if window is not None:
        mask = mask & (kvpos > seq_len - 1 - window)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G', page)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = jnp.broadcast_to(mask, logits.shape)
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    v = v_ref[0, 0].astype(jnp.float32)
    if vs_ref is not None:
        v = v * vs_ref[0, 0]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ip == npages - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def flash_decode_paged_pallas(q, k_pages, v_pages, page_table, seq_lens,
                              k_scale=None, v_scale=None, *,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None,
                              interpret: bool):
    """One-token attention over a **paged** KV cache.

    q (B, H, D); k_pages/v_pages (P, page, Hkv, D) — fixed-size pages
    allocated from a shared pool; page_table (B, maxp) int32 maps each
    sequence's logical page i to its physical page (−1 ⇒ unallocated);
    seq_lens (B,) int32 counts written tokens (including the current
    one, already scattered into its page).  ``k_scale``/``v_scale``
    (P, page, Hkv, 1) f32, when given, dequantize int8 pages in-kernel
    (the FormatPolicy-quantized KV route).

    The page is the kv block: grid (B·Hkv, maxp) walks one physical page
    per step through the scalar-prefetched page table, so pages smaller
    than the flat kernel's preferred ``block_kv`` simply take more grid
    steps.  Unmapped logical pages clamp to physical page 0 in the index
    map and are masked in the kernel.  Returns (B, H, D) in q.dtype.
    """
    b, h, d = q.shape
    npages_phys, page, hkv, _ = k_pages.shape
    g = h // hkv
    gp = max(8, g)  # pad query-head group to the sublane minimum
    maxp = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.reshape(b * hkv, gp, d)
    kt = k_pages.transpose(2, 0, 1, 3)            # (Hkv, P, page, D)
    vt = v_pages.transpose(2, 0, 1, 3)
    pt = page_table.reshape(-1).astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)
    has_scale = k_scale is not None

    def qmap(bn, ip, pt_ref, sl_ref):
        return (bn, 0, 0)

    def kvmap(bn, ip, pt_ref, sl_ref):
        # Physical page of sequence bn//hkv's logical page ip; unmapped
        # (−1) clamps to page 0, masked inside the kernel.
        return (bn % hkv, jnp.maximum(pt_ref[(bn // hkv) * maxp + ip], 0),
                0, 0)

    in_specs = [
        pl.BlockSpec((1, gp, d), qmap),
        pl.BlockSpec((1, 1, page, d), kvmap),
        pl.BlockSpec((1, 1, page, d), kvmap),
    ]
    operands = [qg, kt, vt]
    if has_scale:
        in_specs += [pl.BlockSpec((1, 1, page, 1), kvmap),
                     pl.BlockSpec((1, 1, page, 1), kvmap)]
        operands += [k_scale.transpose(2, 0, 1, 3).astype(jnp.float32),
                     v_scale.transpose(2, 0, 1, 3).astype(jnp.float32)]

    kernel = functools.partial(
        _paged_kernel, npages=maxp, page=page, hkv=hkv, window=window,
        softcap=softcap, scale=scale, has_scale=has_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gp, d), qmap),
        scratch_shapes=[
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, gp, d), q.dtype),
        interpret=interpret,
    )(pt, sl, *operands)
    return out.reshape(b, hkv, gp, d)[:, :, :g].reshape(b, h, d)

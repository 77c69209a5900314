"""Blocked (flash) attention Pallas kernel with MTE-solved tile geometry.

Attention's score (Q·Kᵀ) and value (P·V) products are GEMMs whose shapes
swing wildly with the serving regime — long-context prefill is tall
(Sq = Skv = 32k), decode is a degenerate GEMV — which is exactly the
geometry-sensitivity problem the paper targets.  The QK/PV block shapes
here come from the MTE solver over (Sq, Skv, D), and the online-softmax
rescale is the "vector processing mode": element-wise work on the
accumulator tile while it is VMEM-resident.

Supports causal masking, sliding windows (recurrentgemma/starcoder2/gemma2
local layers), attention logit soft-capping (gemma2), and GQA/MQA via an
index-map head fold (no KV replication in memory).

Layout: q (B, H, Sq, D); k/v (B, Hkv, Skv, D).  Grid (B·H, gq, gkv).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import cdiv

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 sq: int, skv: int, bq: int, bkv: int, gkv: int,
                 causal: bool, window: Optional[int],
                 softcap: Optional[float], scale: float):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Right-aligned q positions (decode/chunked prefill put q at the end).
    offs = skv - sq
    q_start = iq * bq + offs
    kv_start = ikv * bkv

    # Block-level reachability: skip kv blocks fully outside the mask.
    needed = jnp.bool_(True)
    if causal:
        needed &= kv_start <= q_start + bq - 1
    if window is not None:
        needed &= kv_start + bkv - 1 > q_start - window

    @pl.when(needed)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kv_pos < skv  # clip kv padding
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        logits = jnp.where(mask, logits, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)  # robust to fully-masked first blocks
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        if skv % bkv != 0:
            # Zero the ragged kv tail of V: p is 0 there but 0·NaN = NaN.
            vmask = (kv_start + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0)) < skv
            v = jnp.where(vmask, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ikv == gkv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_kv", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           block_q: int = 256, block_kv: int = 256,
                           interpret: bool):
    """Flash attention; q (B,H,Sq,D), k/v (B,Hkv,Skv,D), H % Hkv == 0."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if h % hkv != 0:
        raise ValueError(f"GQA requires H % Hkv == 0, got {h} % {hkv}")
    g = h // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    bq = min(block_q, max(8, cdiv(sq, 8) * 8))
    bkv = min(block_kv, max(128, cdiv(skv, 128) * 128))
    gq, gkv = cdiv(sq, bq), cdiv(skv, bkv)

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)

    def kv_index(bh, iq, ikv):
        # fold GQA: query head bh -> kv head (b * hkv + (bh % h) // g)
        return ((bh // h) * hkv + (bh % h) // g, ikv, 0)

    kernel = functools.partial(
        _attn_kernel, sq=sq, skv=skv, bq=bq, bkv=bkv, gkv=gkv,
        causal=causal, window=window, softcap=softcap, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ikv: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ikv: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)

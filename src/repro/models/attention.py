"""GQA/MQA attention: training forward, prefill, and cached decode.

Feature set per the assigned architectures: grouped/multi-query KV heads,
RoPE, QK-norm (chameleon, qwen3), attention logit soft-capping (gemma2),
sliding windows (gemma2 local layers, starcoder2, recurrentgemma), explicit
head_dim override (gemma family), QKV bias (qwen1.5).

Sliding-window decode uses a *ring* cache of ``window`` slots so long_500k
decode holds O(window) state, never O(S) — the sub-quadratic requirement.
Training/prefill use the flash kernel when ``cfg.gemm_backend == 'pallas'``
and an equivalent jnp formulation for pjit/dry-run graphs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (dense, init_dense, init_norm, model_format,
                                 rmsnorm, rope, use_graph)

__all__ = ["init_attention", "attention", "init_attn_cache",
           "decode_attention", "init_paged_attn_cache",
           "paged_decode_attention", "paged_prefill_attention",
           "ring_chunk_attention", "verify_paged_attention"]

_NEG_INF = -1e30


def init_attention(key, cfg):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "q": init_dense(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "k": init_dense(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "v": init_dense(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dt),
        "o": init_dense(ks[3], cfg.n_heads * hd, d, dtype=dt,
                        scale=(cfg.n_heads * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(hd, "rmsnorm", dt)
        p["k_norm"] = init_norm(hd, "rmsnorm", dt)
    return p


def _project_qkv(x, p, cfg, positions):
    b, s, _ = x.shape
    hd = cfg.hd
    if use_graph(cfg):
        q2, k2, v2 = _qkv_compiled(x.reshape(b * s, -1), p, cfg)
        q = q2.reshape(b, s, cfg.n_heads, hd)
        k = k2.reshape(b, s, cfg.n_kv_heads, hd)
        v = v2.reshape(b, s, cfg.n_kv_heads, hd)
    else:
        q = dense(x, p["q"], cfg).reshape(b, s, cfg.n_heads, hd)
        k = dense(x, p["k"], cfg).reshape(b, s, cfg.n_kv_heads, hd)
        v = dense(x, p["v"], cfg).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _qkv_compiled(x2, p, cfg):
    """The q/k/v projections as ONE compiled ``repro.graph`` program.

    Three GemmNodes sharing the input: the sibling-grouping rewrite turns
    them into a single GroupNode — one grouped kernel launch and one
    plan-cache signature per step instead of three — when the scheduler's
    program score favors it (it models the k/v zero-padding waste and the
    per-call weight-stacking traffic, so grouping is a measured choice,
    not a reflex).  Each node carries the same epilogue ``dense`` would
    fuse (QKV bias), so parity with the eager path holds per format.
    """
    import jax.numpy as jnp
    from repro.core.epilogue import Epilogue
    from repro.graph import schedule as graph_schedule
    from repro.graph.trace import GraphBuilder
    from repro.models.layers import _cdt

    cdt = _cdt(cfg)
    fmt = model_format(cfg)
    m, d = x2.shape

    def build():
        b = GraphBuilder()
        xv = b.input((m, d), x2.dtype, "x")
        outs = []
        for name in ("q", "k", "v"):
            wv = b.input(p[name]["w"].shape, p[name]["w"].dtype,
                         f"w_{name}")
            bv = (b.input((p[name]["w"].shape[1],), "float32",
                          f"b_{name}") if cfg.qkv_bias else None)
            outs.append(b.gemm(
                xv, wv, bias=bv,
                epilogue=Epilogue(has_bias=cfg.qkv_bias),
                fmt=fmt.name, out_dtype=cdt, policy=cfg.gemm_policy,
                name=name))
        b.output(*outs)
        return b.build()

    key = ("qkv", m, d, cfg.n_heads, cfg.n_kv_heads, cfg.hd, fmt.name,
           str(cdt), cfg.gemm_policy, cfg.qkv_bias, str(x2.dtype),
           str(p["q"]["w"].dtype))
    prog = graph_schedule.compile_cached(key, build)
    args = [x2]
    for name in ("q", "k", "v"):
        args.append(p[name]["w"])
        if cfg.qkv_bias:
            args.append(p[name]["b"].astype(jnp.float32))
    return prog(*args)


def _project_qkv_grouped(x, p, cfg, positions):
    """Decode q/k/v as ONE GroupNode program (G=3) through the plan cache.

    A decode step's three projection GEMVs share M=B and K=d_model and
    differ only in N; the compiled program's GroupNode batches them as a
    single grouped launch, so the plan cache sees one grouped signature
    per step instead of three GEMV signatures (and the grouped kernel's
    group-grid parallelism covers the underfilled (M, N) grid the GEMVs
    leave).  k/v columns are zero-padded up to q's width and sliced back
    off by the GroupNode.

    The stacked (3, D, Nmax) weight is pure layout
    (:func:`repro.graph.stack_group_weights`): the serving engine
    precomputes it once per layer (stored as ``p["qkv"]``) so the hot
    decode step never re-pads; the inline stack below is the fallback for
    direct ``model.decode`` calls.
    """
    from repro.graph import schedule as graph_schedule, stack_group_weights
    from repro.graph.trace import GraphBuilder
    b, s, dm = x.shape
    hd = cfg.hd
    nq = cfg.n_heads * hd
    nkv = cfg.n_kv_heads * hd

    wstack = p.get("qkv")
    if wstack is None:
        wstack = stack_group_weights([p["q"]["w"], p["k"]["w"],
                                      p["v"]["w"]])       # (3, D, Nmax)
    x2 = x.reshape(b * s, dm)
    cdt = jnp.dtype(cfg.compute_dtype)
    fmt = model_format(cfg)

    def build():
        bld = GraphBuilder()
        xv = bld.input((b * s, dm), x2.dtype, "x")
        wv = bld.input(wstack.shape, wstack.dtype, "qkv")
        outs = bld.group(xv, stacked=wv, widths=(nq, nkv, nkv),
                         fmt=fmt.name, out_dtype=cdt,
                         policy=cfg.gemm_policy)
        bld.output(*outs)
        return bld.build()

    key = ("qkv_decode", b * s, dm, nq, nkv, fmt.name, str(cdt),
           cfg.gemm_policy, str(x2.dtype), str(wstack.dtype))
    prog = graph_schedule.compile_cached(key, build)
    q, k, v = prog(x2, wstack)
    if cfg.qkv_bias:
        q = q + p["q"]["b"].astype(q.dtype)
        k = k + p["k"]["b"].astype(k.dtype)
        v = v + p["v"]["b"].astype(v.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _project_qkv_decode(x, p, cfg, positions):
    # The grouped decode projection IS a compiled graph program, so the
    # --no-graph escape hatch (use_graph=False) disables it too — eager
    # per-GEMM dispatch must stay reachable on the serving hot path.
    if (getattr(cfg, "decode_qkv_grouped", False)
            and getattr(cfg, "use_graph", True)):
        return _project_qkv_grouped(x, p, cfg, positions)
    return _project_qkv(x, p, cfg, positions)


_CHUNK_THRESHOLD = 2048  # switch to the scanned formulation above this Skv
_KV_CHUNK = 1024


def _grouped_logits(q, k, scale, softcap):
    """QK logits without materializing repeated KV heads (GQA).

    q: (B, Hkv, G, Sq, D); k: (B, Skv, Hkv, D) → (B, Hkv, G, Sq, Skv) f32.
    """
    logits = jnp.einsum("bngqd,bknd->bngqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def _mask(qp, kp, causal, window):
    m = kp >= 0
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        m = m & (kp > qp - window)
    return m


def _xla_attention(q, k, v, *, causal, window, softcap, scale,
                   kv_positions=None, q_positions=None,
                   chunk: int = _KV_CHUNK):
    """jnp attention with the same mask semantics as the flash kernel;
    used in pjit graphs where Mosaic cannot lower on CPU.

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) → (B, Sq, H, D): the axis
    order in which projections, caches and gathered pool pages hold them.
    K and V are contracted in that order; Q is regrouped to
    (B, Hkv, G, Sq, D), the order of the grouped logits.

    GQA runs as a grouped einsum (KV heads never materialized H-wide).
    Long sequences switch to a KV-chunked online-softmax scan with an
    inner rematerialization checkpoint — flash-attention memory behaviour
    expressed in XLA, which is what makes 32k-token prefill and 4k training
    of the large dense archs fit in HBM.
    """
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, hd)
    # Normalize positions to batched (B, S) form (per-sequence decode
    # positions are what continuous batching needs).
    if q_positions is None:
        q_positions = jnp.arange(sq) + (skv - sq)
    if kv_positions is None:
        kv_positions = jnp.arange(skv)
    q_positions = jnp.broadcast_to(jnp.atleast_2d(q_positions), (b, sq))
    kv_positions = jnp.broadcast_to(jnp.atleast_2d(kv_positions), (b, skv))

    if skv > _CHUNK_THRESHOLD:
        out = _chunked_attention(qg, k, v, q_positions, kv_positions,
                                 causal=causal, window=window,
                                 softcap=softcap, scale=scale, chunk=chunk)
    else:
        logits = _grouped_logits(qg, k, scale, softcap)
        mask = _mask(q_positions[:, :, None], kv_positions[:, None, :],
                     causal, window)
        logits = jnp.where(mask[:, None, None], logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bngqk,bknd->bngqd", probs, v)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


def _chunked_attention(qg, k, v, q_positions, kv_positions, *, causal,
                       window, softcap, scale, chunk: int = _KV_CHUNK):
    """Online-softmax scan over KV chunks (flash semantics in XLA).

    qg: (B, Hkv, G, Sq, D); k/v: (B, Skv, Hkv, D) → (B, Hkv, G, Sq, D).
    The chunk body is wrapped in jax.checkpoint so backward recomputes
    the (…, Sq, chunk) logits instead of storing them — O(Sq·chunk) live
    memory.
    """
    b, hkv, g, sq, hd = qg.shape
    skv = k.shape[1]
    nc = -(-skv // chunk)
    pad = nc * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-1)

    ks = k.reshape(b, nc, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nc, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    kps = kv_positions.reshape(b, nc, chunk).transpose(1, 0, 2)
    qp = q_positions[:, :, None]

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, kp_blk = xs
        logits = _grouped_logits(qg, k_blk, scale, softcap)
        mask = _mask(qp, kp_blk[:, None, :], causal, window)
        emask = mask[:, None, None]
        logits = jnp.where(emask, logits, _NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        p = jnp.where(emask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bngqk,bknd->bngqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, hkv, g, sq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, sq, 1), jnp.float32),
            jnp.zeros((b, hkv, g, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init, (ks, vs, kps))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(qg.dtype)


def attention(x, p, cfg, positions, *, window: Optional[int] = None,
              return_kv: bool = False):
    """Full-sequence causal attention (training / prefill forward)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg, positions)
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.hd ** -0.5
    if cfg.gemm_backend == "pallas":
        from repro.kernels import ops
        out = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=window,
            softcap=cfg.attn_softcap, scale=scale)
        out = out.transpose(0, 2, 1, 3)
    else:
        out = _xla_attention(
            q, k, v, causal=True, window=window,
            softcap=cfg.attn_softcap, scale=scale,
            chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))
    y = dense(out.reshape(b, s, -1), p["o"], cfg)
    if return_kv:
        return y, (k, v)
    return y


# -- decode (cached) ----------------------------------------------------------


def _quantize_kv(x, per_channel: bool = True):
    """Symmetric int8 KV quantization.  x: (..., kv, hd).

    ``per_channel=True`` (the ``int8`` contract) keeps one scale per
    (token, head) over hd; ``False`` (``int8pt``, the per-tensor-scale
    KV default) keeps ONE scale per stored token over (kv, hd), broadcast
    back to the (..., kv, 1) scale layout so both variants store and
    dequantize identically.
    """
    xf = x.astype(jnp.float32)
    axes = (-1,) if per_channel else (-2, -1)
    scale = jnp.max(jnp.abs(xf), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    scale = jnp.broadcast_to(scale, x.shape[:-1] + (1,))
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_attn_cache(cfg, batch: int, seq_len: int, window: Optional[int],
                    dtype):
    """KV cache.  Global layers hold seq_len slots; local layers hold a
    ``window``-slot ring (O(window) memory — long-context requirement).
    ``cfg.cache_quant`` stores int8 values + per-(token, head) f32 scales
    (≈ 0.56× the bf16 footprint — a serving-memory optimization)."""
    length = min(window, seq_len) if window else seq_len
    shape = (batch, length, cfg.n_kv_heads, cfg.hd)
    if getattr(cfg, "cache_quant", False):
        sshape = (batch, length, cfg.n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(x, p, cfg, cache, pos, *, window: Optional[int] = None):
    """One-token decode step.  x: (B, 1, D); pos: scalar int32 or (B,)
    per-sequence positions (continuous batching).  Returns (out, cache)."""
    b = x.shape[0]
    hd = cfg.hd
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = _project_qkv_decode(x, p, cfg, pos_b[:, None])
    length = cache["k"].shape[1]
    slot_b = pos_b % length  # == pos_b for global layers (pos < cache len)
    quant = "k_scale" in cache
    new_cache = dict(cache)
    if quant:
        kq, ks = _quantize_kv(k[:, 0])
        vq, vs = _quantize_kv(v[:, 0])
        rows = jnp.arange(b)
        new_cache["k"] = cache["k"].at[rows, slot_b].set(kq)
        new_cache["k_scale"] = cache["k_scale"].at[rows, slot_b].set(ks)
        new_cache["v"] = cache["v"].at[rows, slot_b].set(vq)
        new_cache["v_scale"] = cache["v_scale"].at[rows, slot_b].set(vs)
        cdt = jnp.dtype(cfg.compute_dtype)
        knew = _dequantize_kv(new_cache["k"], new_cache["k_scale"], cdt)
        vnew = _dequantize_kv(new_cache["v"], new_cache["v_scale"], cdt)
    else:
        knew = cache["k"].at[jnp.arange(b), slot_b].set(
            k[:, 0].astype(cache["k"].dtype))
        vnew = cache["v"].at[jnp.arange(b), slot_b].set(
            v[:, 0].astype(cache["v"].dtype))
        new_cache["k"], new_cache["v"] = knew, vnew

    idx = jnp.arange(length)[None, :]
    if window:
        # ring: slot i holds absolute position pos - ((pos - i) mod length)
        kv_positions = pos_b[:, None] - (pos_b[:, None] - idx) % length
    else:
        kv_positions = jnp.where(idx <= pos_b[:, None], idx, -1)

    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if cfg.gemm_backend == "pallas":
        from repro.kernels import ops
        out = ops.flash_decode(
            q[:, 0], knew.transpose(0, 2, 1, 3), vnew.transpose(0, 2, 1, 3),
            kv_positions, pos_b, window=window, softcap=cfg.attn_softcap,
            scale=scale)
        out = out[:, None]  # (B, 1, H, hd) layout below
        out = out.reshape(b, 1, -1)
    else:
        out = _xla_attention(
            q, knew, vnew, causal=True, window=window,
            softcap=cfg.attn_softcap, scale=scale,
            kv_positions=kv_positions,
            q_positions=pos_b[:, None],
            chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))
        out = out.reshape(b, 1, -1)
    return dense(out, p["o"], cfg), new_cache


# -- paged decode (page-table-indexed KV pool) --------------------------------


def _kv_storage_format(cfg):
    """The FormatPolicy governing paged KV storage (None ⇒ raw compute
    dtype, no scales)."""
    from repro.core.formats import resolve_format
    name = getattr(cfg, "kv_cache_format", None)
    return resolve_format(name) if name is not None else None


def init_paged_attn_cache(cfg, num_pages: int, page_size: int, dtype):
    """Paged KV storage for ONE global-attention layer.

    Pages are (num_pages, page_size, kv, hd) slabs shared by every
    sequence through the page table; ``cfg.kv_cache_format`` selects the
    stored element type (int8/int8pt add the (num_pages, page_size, kv, 1)
    f32 scale pages).  Physical page 0 is the reserved null page.
    """
    fmt = _kv_storage_format(cfg)
    shape = (num_pages, page_size, cfg.n_kv_heads, cfg.hd)
    if fmt is None:
        return {"k_pages": jnp.zeros(shape, dtype),
                "v_pages": jnp.zeros(shape, dtype)}
    if fmt.quantized:
        sshape = (num_pages, page_size, cfg.n_kv_heads, 1)
        return {"k_pages": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k_pages": jnp.zeros(shape, fmt.operand_jnp),
            "v_pages": jnp.zeros(shape, fmt.operand_jnp)}


def _store_kv(cache, layer, phys, slot, k, v, fmt):
    """Write K/V (..., kv, hd) at (physical page, slot) of a paged pool,
    quantized under ``fmt`` when the pool holds scales; with ``slot``
    None, K/V are whole pages (..., page, kv, hd) written at ``phys``.

    ``layer`` is None for one layer's (num_pages, page, kv, ·) leaves, or
    the index into the stacked (n_groups, num_pages, page, kv, ·) leaves
    the layer scan carries: the scatter then updates the stacked pool
    where it lies, and no layer slab is sliced out or written back.

    A token is written one head at a time: each update's window is the
    hd axis, which every layout of the pool keeps contiguous.  A TPU
    keeps a pool of 20 KV heads with its kv axis between the page and
    slot axes, and a (kv, hd) window there makes XLA relayout the whole
    pool around the scatter.  A whole page is contiguous in any layout.
    """
    if slot is None:
        at = (phys,)
    else:
        heads = jnp.arange(cache["k_pages"].shape[-2])
        at = (phys[..., None], slot[..., None], heads)
    at = at if layer is None else (layer,) + at
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k, per_channel=fmt.per_channel)
        vq, vs = _quantize_kv(v, per_channel=fmt.per_channel)
        vals = {"k_pages": kq, "k_scale": ks, "v_pages": vq, "v_scale": vs}
    else:
        vals = {"k_pages": k, "v_pages": v}
    new_cache = dict(cache)
    for name, val in vals.items():
        new_cache[name] = cache[name].at[at].set(val.astype(cache[name].dtype))
    return new_cache


def _gather_kv(cache, layer, table, dtype):
    """The pages ``table`` (B, n) selects, in logical order, as K/V
    (B, n·page, kv, hd): position j of row b is absolute position j.
    Unmapped (−1) entries read the null page.  Quantized pools dequantize
    to ``dtype``; raw pools keep their stored dtype."""
    idx = jnp.maximum(table, 0)

    def pages(leaf):
        g = leaf[idx] if layer is None else leaf[layer, idx]
        return g.reshape(g.shape[0], -1, *g.shape[3:])

    kg, vg = pages(cache["k_pages"]), pages(cache["v_pages"])
    if "k_scale" in cache:
        kg = _dequantize_kv(kg, pages(cache["k_scale"]), dtype)
        vg = _dequantize_kv(vg, pages(cache["v_scale"]), dtype)
    return kg, vg


def _layer_pool(cache, layer):
    """One layer's pool leaves (the Pallas kernels take a single layer)."""
    if layer is None:
        return cache
    return {name: leaf[layer] for name, leaf in cache.items()}


def paged_decode_attention(x, p, cfg, cache, pos, page_table, *,
                           window: Optional[int] = None, layer=None):
    """One-token decode over a paged KV pool.

    x: (B, 1, D); pos: scalar or (B,) per-sequence positions; page_table:
    (B, max_pages) int32 mapping logical page → physical page (−1 ⇒
    unallocated; inactive slots carry all-(−1) rows and scribble into the
    reserved null page 0).  The new token's K/V are quantized under
    ``cfg.kv_cache_format`` and scattered into (physical page, slot) =
    (table[pos // page], pos % page); attention then reads the
    table-selected pages — via the page-table-indexed flash-decode kernel
    on the pallas backend, or a gather + masked XLA attention otherwise.
    ``layer`` indexes a stacked pool (see :func:`_store_kv`).
    Returns (out, new_cache).
    """
    b = x.shape[0]
    hd = cfg.hd
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = _project_qkv_decode(x, p, cfg, pos_b[:, None])
    page = cache["k_pages"].shape[-3]
    maxp = page_table.shape[1]
    rows = jnp.arange(b)
    # Inactive slots (all-unmapped rows) clamp to the null page 0.
    phys = jnp.maximum(page_table[rows, pos_b // page], 0)
    new_cache = _store_kv(cache, layer, phys, pos_b % page, k[:, 0], v[:, 0],
                          _kv_storage_format(cfg))

    seq_lens = pos_b + 1
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if cfg.gemm_backend == "pallas":
        from repro.kernels import ops
        pool = _layer_pool(new_cache, layer)
        out = ops.flash_decode_paged(
            q[:, 0], pool["k_pages"], pool["v_pages"],
            page_table, seq_lens,
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            window=window, softcap=cfg.attn_softcap, scale=scale)
        out = out.reshape(b, 1, -1)
    else:
        # The gathered view is in logical order, so the masked XLA
        # attention below is bit-identical to the contiguous-cache path.
        kg, vg = _gather_kv(new_cache, layer, page_table,
                            jnp.dtype(cfg.compute_dtype))
        idx = jnp.arange(maxp * page)[None, :]
        mapped = jnp.repeat(page_table >= 0, page, axis=1)
        kv_positions = jnp.where((idx <= pos_b[:, None]) & mapped, idx, -1)
        out = _xla_attention(
            q, kg, vg, causal=True, window=window,
            softcap=cfg.attn_softcap, scale=scale,
            kv_positions=kv_positions, q_positions=pos_b[:, None],
            chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))
        out = out.reshape(b, 1, -1)
    return dense(out, p["o"], cfg), new_cache


def verify_paged_attention(x, p, cfg, cache, pos, page_table, *,
                           layer=None):
    """Score a K-token speculative window over the paged KV pool.

    x: (B, K, D) — per row, the last emitted token followed by K−1 draft
    proposals; pos: (B,) the window's first absolute positions (dynamic —
    slots sit at different depths, unlike ``paged_prefill_attention``'s
    static ``kv_len``); page_table: (B, max_pages).  This is the decode
    semantics of :func:`paged_decode_attention` run K times, expressed as
    ONE batched pass: the window's K/V are quantized under
    ``cfg.kv_cache_format`` and scattered into their (physical page, slot)
    targets FIRST, then each of the K queries attends over the gathered
    pages — scattered window tokens included, so a quantized cache
    round-trips the in-window tokens exactly as vanilla decode would, and
    the gathered KV axis has the *same* (max_pages·page) layout as the
    decode read (greedy acceptance therefore reproduces vanilla argmax
    bit-for-bit on the XLA path).  Within the window, causality between
    the K queries rides on ``q_positions``.

    A rejected suffix is never un-written: page slots past the accepted
    point hold garbage the next window simply overwrites — the engine
    rewinds only the host-side position (global-attention pages are
    position-addressed, so no old KV is ever overwritten by the window).
    ``layer`` indexes a stacked pool (see :func:`_store_kv`).
    Returns (out, new_cache).
    """
    b, klen, _ = x.shape
    hd = cfg.hd
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos_b[:, None] + jnp.arange(klen, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv_decode(x, p, cfg, positions)
    page = cache["k_pages"].shape[-3]
    maxp = page_table.shape[1]
    rows = jnp.arange(b)[:, None]
    # Inactive slots (all-unmapped rows) clamp to the null page 0.
    phys = jnp.maximum(page_table[rows, positions // page], 0)   # (B, K)
    new_cache = _store_kv(cache, layer, phys, positions % page, k, v,
                          _kv_storage_format(cfg))

    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if cfg.gemm_backend == "pallas":
        # The paged flash-decode kernel is one-query; run it per window
        # position (K is small and static) so every query goes through
        # the exact kernel vanilla decode uses — bit-identity by
        # construction.  seq_lens masks each query to its own prefix.
        from repro.kernels import ops
        pool = _layer_pool(new_cache, layer)
        outs = []
        for i in range(klen):
            o = ops.flash_decode_paged(
                q[:, i], pool["k_pages"], pool["v_pages"],
                page_table, pos_b + i + 1,
                k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
                window=None, softcap=cfg.attn_softcap, scale=scale)
            outs.append(o.reshape(b, 1, -1))
        out = jnp.concatenate(outs, axis=1)
    else:
        kg, vg = _gather_kv(new_cache, layer, page_table,
                            jnp.dtype(cfg.compute_dtype))
        idx = jnp.arange(maxp * page)[None, :]
        mapped = jnp.repeat(page_table >= 0, page, axis=1)
        kv_positions = jnp.where((idx <= positions[:, -1:]) & mapped,
                                 idx, -1)
        out = _xla_attention(
            q, kg, vg, causal=True, window=None,
            softcap=cfg.attn_softcap, scale=scale,
            kv_positions=kv_positions, q_positions=positions,
            chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))
        out = out.reshape(b, klen, -1)
    return dense(out, p["o"], cfg), new_cache


def paged_prefill_attention(x, p, cfg, cache, positions, page_table, *,
                            kv_len: int, layer=None):
    """One prefill *chunk* over the paged KV pool.

    x: (1, C, D) chunk activations; positions: (1, C) absolute positions
    ``[kv_len − C, kv_len)``; page_table: (1, max_pages).  The chunk's
    K/V are quantized under ``cfg.kv_cache_format`` and scattered into
    their (physical page, slot) targets *for storage*; the attention
    read uses the chunk's own K/V at full compute precision (prefill
    stays full-precision within a chunk — storage quantization only
    touches what later chunks/decodes re-read) concatenated with the
    pool pages holding the prior prefix — which includes pages this
    request only *aliased* from the prefix cache (the partial-prefix
    read the serving engine's prefix-cached admission relies on: the hit
    path re-reads cached KV, it never recomputes it).  ``kv_len`` is
    static, so every chunk index compiles once, the gather touches only
    the live prefix pages, and all chunk GEMMs share the single (C, D)
    plan-cache signature.  ``layer`` indexes a stacked pool (see
    :func:`_store_kv`).  Returns (out, new_cache).
    """
    b, c_len, _ = x.shape
    hd = cfg.hd
    q, k, v = _project_qkv(x, p, cfg, positions)
    page = cache["k_pages"].shape[-3]
    fmt = _kv_storage_format(cfg)
    pos0 = kv_len - c_len
    if pos0 % page == 0 and c_len % page == 0:
        # The chunk fills whole pages: one update per page, not per
        # (token, head).
        phys = jnp.maximum(page_table[0, pos0 // page:kv_len // page], 0)
        new_cache = _store_kv(cache, layer, phys, None,
                              k[0].reshape(-1, page, *k.shape[2:]),
                              v[0].reshape(-1, page, *v.shape[2:]), fmt)
    else:
        pos_v = positions[0]                   # (C,) absolute positions
        phys = jnp.maximum(page_table[0, pos_v // page], 0)
        new_cache = _store_kv(cache, layer, phys, pos_v % page, k[0], v[0],
                              fmt)

    # Gather only the pages holding the prior prefix [0, pos0) into
    # logical order (slot j of the view is absolute position j) and
    # append the chunk's full-precision K/V — pos0 = kv_len − C is
    # static, so the read is bounded by the live prefix, not max_pages.
    n_prefix = -(-pos0 // page)                    # pages covering [0, pos0)
    cdt = jnp.dtype(cfg.compute_dtype)

    if pos0:
        kg, vg = _gather_kv(new_cache, layer, page_table[:, :n_prefix], cdt)
        kg, vg = kg[:, :pos0], vg[:, :pos0]
        kg = jnp.concatenate([kg.astype(cdt), k.astype(cdt)], axis=1)
        vg = jnp.concatenate([vg.astype(cdt), v.astype(cdt)], axis=1)
    else:
        kg, vg = k.astype(cdt), v.astype(cdt)
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if cfg.gemm_backend == "pallas":
        from repro.kernels import ops
        out = ops.flash_attention(
            q.transpose(0, 2, 1, 3), kg.transpose(0, 2, 1, 3),
            vg.transpose(0, 2, 1, 3), causal=True, window=None,
            softcap=cfg.attn_softcap, scale=scale)
        out = out.transpose(0, 2, 1, 3)
    else:
        out = _xla_attention(
            q, kg, vg, causal=True, window=None,
            softcap=cfg.attn_softcap, scale=scale,
            q_positions=positions,
            chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))
    return dense(out.reshape(b, c_len, -1), p["o"], cfg), new_cache


def ring_chunk_attention(x, p, cfg, cache, positions, *, pos0: int,
                         window: int):
    """One prefill chunk of a sliding-window layer over its ring cache.

    x: (1, C, D); cache: the slot's (1, L, kv, hd) ring (L =
    min(window, cache_len)); ``pos0`` (static) is the chunk's first
    absolute position.  The chunk attends to the ring's pre-chunk
    contents plus itself under the window mask, then the chunk's last
    min(C, L) tokens overwrite their ring slots (slot = pos mod L) — the
    same layout decode and ``prefill_cache`` maintain, so decode resumes
    seamlessly after the last chunk.  Returns (out, new_cache).
    """
    b, c_len, _ = x.shape
    hd = cfg.hd
    q, k, v = _project_qkv(x, p, cfg, positions)
    ring_k, ring_v = cache["k"], cache["v"]
    length = ring_k.shape[1]
    idx = jnp.arange(length)
    # Ring slot i holds the most recent absolute position ≡ i (mod L)
    # strictly before the chunk; never-written slots and the chunk's own
    # positions are masked out (−1).
    rp = pos0 - ((pos0 - idx) % length)
    rp = jnp.where((rp >= pos0) | (rp < 0), -1, rp)
    kv_positions = jnp.concatenate(
        [jnp.broadcast_to(rp[None], (b, length)), positions], axis=1)
    kc = jnp.concatenate([ring_k, k.astype(ring_k.dtype)], axis=1)
    vc = jnp.concatenate([ring_v, v.astype(ring_v.dtype)], axis=1)
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    out = _xla_attention(
        q, kc, vc, causal=True, window=window,
        softcap=cfg.attn_softcap, scale=scale,
        kv_positions=kv_positions, q_positions=positions,
        chunk=getattr(cfg, "attn_chunk", _KV_CHUNK))

    keep = min(c_len, length)
    slots = (pos0 + c_len - keep + np.arange(keep)) % length
    new_cache = dict(cache)
    new_cache["k"] = ring_k.at[:, slots].set(
        k[:, c_len - keep:].astype(ring_k.dtype))
    new_cache["v"] = ring_v.at[:, slots].set(
        v[:, c_len - keep:].astype(ring_v.dtype))
    return dense(out.reshape(b, c_len, -1), p["o"], cfg), new_cache


def prefill_cache(k, v, cfg, seq_len: int, window: Optional[int], dtype
                  ) -> Tuple[dict, None]:
    """Build a decode cache from prefill K/V (B, S, kv, hd)."""
    b, s = k.shape[0], k.shape[1]
    length = min(window, seq_len) if window else seq_len
    if window and s >= length:
        # keep the last `length` positions at their ring slots
        start = s - length
        ksl, vsl = k[:, start:], v[:, start:]
        slots = (jnp.arange(length) + start) % length
        order = jnp.argsort(slots)
        kf, vf = ksl[:, order], vsl[:, order]
    else:
        pad = length - s
        kf = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if getattr(cfg, "cache_quant", False):
        kq, ks = _quantize_kv(kf)
        vq, vs = _quantize_kv(vf)
        return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    return {"k": kf.astype(dtype), "v": vf.astype(dtype)}

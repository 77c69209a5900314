"""Mamba2 SSD (state-space duality) mixer — arXiv:2405.21060.

Chunked SSD algorithm: the sequence is split into chunks of length Q; the
intra-chunk term is a masked, decay-weighted attention-like product
(quadratic only within the chunk) and the inter-chunk term is a scan over
per-chunk states — O(S·Q) compute, O(1) decode state.  The intra-chunk
block products are exactly the small/rectangular GEMMs the MTE geometry
solver targets.

Decode keeps (B, H, P, N) recurrent state plus a (B, W, conv_dim) causal
conv ring — O(1) in sequence length, which is what qualifies mamba2 for
the long_500k shape.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import init_dense

__all__ = ["init_ssd", "ssd_forward", "init_ssd_cache", "ssd_decode"]


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.d_state
    return d_inner, n_heads, conv_dim


def init_ssd(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads, conv_dim = _dims(cfg)
    d_in_proj = 2 * d_inner + 2 * s.d_state + n_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "in_proj": init_dense(ks[0], d, d_in_proj, dtype=dt),
        "conv_w": jax.random.normal(ks[1], (s.conv_width, conv_dim), dt) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads).astype(dt)),
        "D": jnp.ones((n_heads,), dt),
        "dt_bias": jnp.zeros((n_heads,), dt),
        "norm_scale": jnp.ones((d_inner,), dt),
        "out_proj": init_dense(ks[3], d_inner, d, dtype=dt,
                               scale=d_inner ** -0.5),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv via shifted adds.  x: (B, S, C); w: (W, C)."""
    width = w.shape[0]
    out = x * w[-1]
    for i in range(1, width):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[-1 - i]
    return out + b


def _gated_rmsnorm(y, z, scale, eps: float):
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(y.dtype)


def _split_proj(zxbcdt, cfg):
    s = cfg.ssm
    d_inner, n_heads, _ = _dims(cfg)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner: 2 * d_inner + 2 * s.d_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * s.d_state:]
    return z, xBC, dt


def _ssd_chunked(x, dt, a_log, bmat, cmat, chunk: int, h0=None):
    """Chunked state-space duality.

    x: (B, S, H, P); dt: (B, S, H); a_log: (H,) (A = -exp(a_log));
    bmat/cmat: (B, S, N); h0: optional (B, H, P, N) initial state (the
    serving engine's chunked prefill resumes mid-sequence).  Returns
    (B, S, H, P) f32.
    """
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))

    xf = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dtc = dt.astype(jnp.float32).reshape(b, nc, q, h)
    bc = bmat.astype(jnp.float32).reshape(b, nc, q, n)
    cc = cmat.astype(jnp.float32).reshape(b, nc, q, n)
    a = -jnp.exp(a_log.astype(jnp.float32))          # (H,)
    da = dtc * a                                      # (b, nc, q, h)
    da_cum = jnp.cumsum(da, axis=2)

    # -- intra-chunk (masked decay attention) ------------------------------
    # att[b,c,h,i,j] = (C_i·B_j) exp(cum_i - cum_j) dt_j  for i >= j
    cb = jnp.einsum("bcin,bcjn->bcij", cc, bc)
    diff = da_cum[:, :, :, None, :] - da_cum[:, :, None, :, :]   # (b,c,i,j,h)
    diff = jnp.transpose(diff, (0, 1, 4, 2, 3))                  # (b,c,h,i,j)
    mask = jnp.tril(jnp.ones((q, q), bool))
    # Mask the EXPONENT: on the upper triangle diff > 0 so exp would
    # overflow to inf, and where(mask, inf·x, 0) still back-propagates NaN.
    diff = jnp.where(mask[None, None, None], diff, -jnp.inf)
    decay = jnp.exp(diff)
    att = cb[:, :, None] * decay * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", att, xf)

    # -- per-chunk states ----------------------------------------------------
    decay_to_end = jnp.exp(da_cum[:, :, -1:, :] - da_cum)        # (b,c,q,h)
    weights = decay_to_end * dtc                                  # (b,c,q,h)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", weights, bc, xf)

    # -- inter-chunk recurrence ---------------------------------------------
    chunk_decay = jnp.exp(da_cum[:, :, -1, :])                    # (b,c,h)

    def step(carry, inp):
        dec, st = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry  # emit the state BEFORE this chunk

    init = (h0.astype(jnp.float32) if h0 is not None
            else jnp.zeros((b, h, p, n), jnp.float32))
    final_state, prev_states = jax.lax.scan(
        step, init, (chunk_decay.transpose(1, 0, 2),
                     states.transpose(1, 0, 2, 3, 4)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)            # (b,c,h,p,n)

    # -- off-diagonal contribution -------------------------------------------
    in_decay = jnp.exp(da_cum)                                    # (b,c,q,h)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", cc, prev_states, in_decay)

    y = (y_diag + y_off).reshape(b, nc * q, h, p)
    return y[:, :s], final_state


@jax.named_scope("ssd")
def ssd_forward(x, p, cfg, *, return_cache: bool = False, cache=None):
    """Full Mamba2 block forward.  x: (B, S, D) → (B, S, D).

    ``cache`` (optional ``{"state", "conv"}``) resumes the recurrence
    mid-sequence for the serving engine's chunked prefill: the conv ring
    replaces the zero padding and the inter-chunk scan starts from the
    carried state."""
    s = cfg.ssm
    d_inner, n_heads, _ = _dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    zxbcdt = jnp.einsum("bsd,df->bsf", x.astype(cdt),
                        p["in_proj"]["w"].astype(cdt),
                        preferred_element_type=jnp.float32)
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    hist = 0
    if cache is not None:
        hist = cache["conv"].shape[1]
        xbc = jnp.concatenate(
            [cache["conv"].astype(jnp.float32), xbc], axis=1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"].astype(jnp.float32),
                                   p["conv_b"].astype(jnp.float32)))[:, hist:]
    x_in = xbc[..., :d_inner]
    bmat = xbc[..., d_inner: d_inner + s.d_state]
    cmat = xbc[..., d_inner + s.d_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))

    xh = x_in.reshape(*x_in.shape[:2], n_heads, s.head_dim)
    y, final_state = _ssd_chunked(xh, dt, p["A_log"], bmat, cmat, s.chunk,
                                  h0=cache["state"] if cache else None)
    y = y + p["D"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(*x.shape[:2], d_inner)
    y = _gated_rmsnorm(y.astype(cdt), z.astype(cdt), p["norm_scale"],
                       s.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y.astype(cdt),
                     p["out_proj"]["w"].astype(cdt),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if return_cache:
        # conv ring holds the last conv_width *raw* xBC projections.
        raw = zxbcdt[..., d_inner: 2 * d_inner + 2 * s.d_state]
        if cache is not None:
            raw = jnp.concatenate(
                [cache["conv"].astype(jnp.float32), raw], axis=1)
        w = s.conv_width
        tail = raw[:, -w:]
        pad = w - tail.shape[1]
        if pad > 0:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        return out, {"state": final_state, "conv": tail.astype(cdt)}
    return out


# -- decode -------------------------------------------------------------------


def init_ssd_cache(cfg, batch: int, dtype):
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    return {
        "state": jnp.zeros((batch, n_heads, s.head_dim, s.d_state),
                           jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width, conv_dim), dtype),
    }


@jax.named_scope("ssd")
def ssd_decode(x, p, cfg, cache) -> Tuple[jax.Array, dict]:
    """One-token recurrent step.  x: (B, 1, D)."""
    s = cfg.ssm
    d_inner, n_heads, _ = _dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    zxbcdt = jnp.einsum("bsd,df->bsf", x.astype(cdt),
                        p["in_proj"]["w"].astype(cdt),
                        preferred_element_type=jnp.float32)
    z, xbc, dt = _split_proj(zxbcdt[:, 0], cfg)

    conv = jnp.concatenate(
        [cache["conv"][:, 1:], xbc[:, None].astype(cache["conv"].dtype)],
        axis=1)
    xbc = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", conv.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32))
        + p["conv_b"].astype(jnp.float32))
    x_in = xbc[:, :d_inner]
    bmat = xbc[:, d_inner: d_inner + s.d_state]
    cmat = xbc[:, d_inner + s.d_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # (B, H)

    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    da = jnp.exp(dt * a)                                          # (B, H)
    xh = x_in.reshape(-1, n_heads, s.head_dim)
    # Broadcast products, not an einsum: an outer-product dot_general keeps
    # the TPU compiler from updating a carried state in place.
    upd = (dt[..., None] * xh)[..., None] * bmat[:, None, None, :]
    state = cache["state"] * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, cmat)
    y = y + p["D"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(-1, 1, d_inner)
    y = _gated_rmsnorm(y.astype(cdt), z[:, None].astype(cdt), p["norm_scale"],
                       s.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y.astype(cdt),
                     p["out_proj"]["w"].astype(cdt),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, {"state": state, "conv": conv}

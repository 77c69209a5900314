"""Mixture-of-Experts block with capacity-based top-k routing.

MoE is the paper's sweet spot: every expert is a *small, skinny* GEMM
(qwen3: d_ff 1536; granite: d_ff 512 — Fig. 7 category I-III shapes), so
the per-expert compute runs through the MTE grouped-GEMM geometry.

Two execution paths:

- ``apply_moe`` (default, GSPMD): capacity-based dispatch expressed with a
  scatter into an (E, C, D) buffer + grouped einsums.  Under pjit the
  expert dim is sharded on the "model" mesh axis (EP) and GSPMD inserts
  the dispatch collectives.  This is the paper-faithful baseline the
  roofline analysis measures first.
- ``apply_moe_a2a`` (shard_map): explicit all-to-all expert parallelism —
  tokens are binned per expert-shard locally, exchanged with a single
  ``lax.all_to_all`` over the "model" axis, computed on the owning shard,
  and returned with a second all-to-all.  This is the beyond-paper
  optimization evaluated in EXPERIMENTS.md §Perf (collective-bound cell).

Both share the same router and per-expert FFN parameters and agree
numerically (up to capacity-drop differences at the margins; tests use
ample capacity so outputs match exactly).

Serving a device's share of the experts (``cfg.moe.held``) takes a third
path, ``apply_moe_held``: the router spans every expert, the layer
computes the part of the result that its held experts (the first ``held``
ids) give, and it drops no row.  ``shared_expert`` is the SwiGLU every
row goes through beside the routed ones (``cfg.moe.d_ff_shared``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import init_dense

__all__ = ["init_moe", "apply_moe", "apply_moe_a2a", "apply_moe_held",
           "shared_expert", "moe_capacity"]


def init_moe(key, cfg):
    d = cfg.d_model
    m = cfg.moe
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    e, f = m.held or m.n_experts, m.d_ff_expert
    p = {
        "router": init_dense(ks[0], d, m.n_experts, dtype=dt)["w"],
        "gate": jax.random.normal(ks[1], (e, d, f), dt) * d ** -0.5,
        "up": jax.random.normal(ks[2], (e, d, f), dt) * d ** -0.5,
        "down": jax.random.normal(ks[3], (e, f, d), dt) * f ** -0.5,
    }
    if m.d_ff_shared:
        sk = jax.random.split(jax.random.fold_in(key, 1), 3)
        fs = m.d_ff_shared
        p["shared_gate"] = init_dense(sk[0], d, fs, dtype=dt)
        p["shared_up"] = init_dense(sk[1], d, fs, dtype=dt)
        p["shared_down"] = init_dense(sk[2], fs, d, dtype=dt,
                                      scale=fs ** -0.5)
    return p


def moe_capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    cap = int(n_tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max(8, -(-cap // 8) * 8)


def _route(x2, router_w, cfg):
    """Top-k routing.  x2: (T, D) → weights (T, k), expert ids (T, k), aux.

    The router's operands take the model's operand width (float32 under a
    quantized policy, which keeps the router at full precision), its
    logits and probabilities float32."""
    from repro.models.layers import model_format
    m = cfg.moe
    fmt = model_format(cfg)
    odt = jnp.float32 if fmt.quantized else fmt.operand_jnp
    logits = jnp.einsum("td,de->te", x2.astype(odt), router_w.astype(odt),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    # Switch-style load-balance auxiliary loss.
    density = jnp.mean(
        jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(density * mean_prob) * m.router_aux_weight
    return vals, idx, aux


def _positions_in_expert(flat_e, n_experts):
    """Stable slot index of each assignment within its expert's queue."""
    oh = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    cum = jnp.cumsum(oh, axis=0)
    return jnp.sum(cum * oh, axis=-1) - 1


def _expert_ffn(buf, p, cfg):
    """Grouped per-expert SwiGLU over the (E, C, D) dispatch buffer.

    Expert GEMMs consume the model's format policy (per-expert
    per-channel scales on the int8 route) — precision is decided once in
    :func:`repro.models.layers.model_format`, not per call site.
    """
    from repro.models.layers import model_format
    cdt = jnp.dtype(cfg.compute_dtype)
    fmt = model_format(cfg)
    if cfg.gemm_backend == "pallas":
        from repro.core.epilogue import Epilogue
        from repro.kernels import ops
        g = ops.grouped_gemm(buf, p["gate"],
                             epilogue=Epilogue(activation="silu"),
                             out_dtype=cdt, format_policy=fmt)
        u = ops.grouped_gemm(buf, p["up"], out_dtype=cdt, format_policy=fmt)
        return ops.grouped_gemm(g * u, p["down"], out_dtype=cdt,
                                format_policy=fmt)
    from repro.core import formats as formats_lib
    g = jax.nn.silu(formats_lib.xla_grouped(buf, p["gate"], fmt
                                            ).astype(jnp.float32))
    u = formats_lib.xla_grouped(buf, p["up"], fmt).astype(jnp.float32)
    h = (g * u).astype(cdt)
    return formats_lib.xla_grouped(h, p["down"], fmt).astype(cdt)


@jax.named_scope("moe")
def apply_moe_held(x, p, cfg, rows=None):
    """Dropless expert layer over this device's share.  x: (B, S, D) →
    (the held experts' part of the layer (B, S, D), counts (2,) int32).

    Every row is routed over all ``n_experts`` (softmax, top-k,
    renormalised) and goes through each of the ``held`` experts it picked,
    weighted by its routing weight; picks of experts held elsewhere add
    nothing here.  The held experts run as one grouped product over all
    rows, each row weighted 0 where it did not pick the expert, so no row
    is sorted, padded or dropped: every route reads the held experts'
    weights whole (170 MB a layer at 9 experts of 768 and d 4096), and on
    a TPU v5e this took 0.76 / 0.99 ms a layer at 32 / 256 rows against
    0.98 / 1.77 ms for a sort into ``jax.lax.ragged_dot``.

    ``counts``: assignments of a counted row to a held expert, and held
    experts with at least one such assignment.  ``rows`` (B,) bool marks
    the counted batch rows (a decode's active slots); None counts all.
    """
    m = cfg.moe
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    vals, idx, _ = _route(x2, p["router"], cfg)
    pick = idx[None] == jnp.arange(m.held)[:, None, None]       # (E, T, k)
    weight = jnp.sum(jnp.where(pick, vals[None], 0.0), axis=-1)  # (E, T)
    buf = jnp.broadcast_to(x2[None], (m.held,) + x2.shape)
    out = _expert_ffn(buf, p, cfg).astype(jnp.float32)           # (E, T, D)
    y = jnp.sum(weight[..., None] * out, axis=0)
    counted = pick if rows is None else (
        pick & jnp.repeat(jnp.asarray(rows, bool), s)[None, :, None])
    counts = jnp.stack([jnp.sum(counted, dtype=jnp.int32),
                        jnp.sum(jnp.any(counted, axis=(1, 2)),
                                dtype=jnp.int32)])
    return y.reshape(b, s, d).astype(x.dtype), counts


@jax.named_scope("moe")
def shared_expert(x, p, cfg):
    """The shared SwiGLU expert that every row goes through."""
    from repro.models.layers import dense
    g = dense(x, p["shared_gate"], cfg, activation="silu")
    u = dense(x, p["shared_up"], cfg)
    return dense(g * u, p["shared_down"], cfg)


def apply_moe(x, p, cfg):
    """Capacity-dispatch MoE (GSPMD path).  x: (B, S, D) → (B, S, D), aux."""
    from repro.distributed.sharding import constrain
    batch_sh = ("pod", "data")
    b, s, d = x.shape
    m = cfg.moe
    x2 = constrain(x.reshape(-1, d), batch_sh, None)
    t = x2.shape[0]
    vals, idx, aux = _route(x2, p["router"], cfg)

    cap = moe_capacity(t, cfg)
    flat_e = constrain(idx.reshape(-1), batch_sh)  # (T·k,)
    pos = constrain(_positions_in_expert(flat_e, m.n_experts), batch_sh)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap)           # cap = OOB -> dropped

    x_rep = constrain(jnp.repeat(x2, m.top_k, axis=0), batch_sh, None)
    buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
    buf = buf.at[flat_e, safe_pos].set(x_rep, mode="drop")
    buf = constrain(buf, "model", None, None)      # EP: experts on "model"

    out_buf = _expert_ffn(buf, p, cfg)
    out_buf = constrain(out_buf, "model", None, None)

    gathered = out_buf.at[flat_e, safe_pos].get(mode="fill", fill_value=0.0)
    gathered = constrain(gathered, batch_sh, None)
    gathered = gathered * keep[:, None].astype(gathered.dtype)
    weighted = gathered.reshape(t, m.top_k, d) * vals[..., None].astype(gathered.dtype)
    return jnp.sum(weighted, axis=1).reshape(b, s, d).astype(x.dtype), aux


def apply_moe_a2a(x, p, cfg, *, mesh, ep_axis: str = "model",
                  token_axes=("pod", "data")):
    """Explicit expert-parallel MoE via shard_map all-to-all.

    Tokens are sharded over batch (``token_axes``) AND sequence
    (``ep_axis``) — every device routes only its own tokens; experts are
    sharded over ``ep_axis``.  Each device bins assignments by destination
    expert-shard into fixed-capacity send buffers, exchanges them with one
    ``all_to_all``, runs its local experts, and returns results with a
    second ``all_to_all``.  Collective volume per device per layer:
    ≈ 2 · T_dev·k·capacity_factor · D bytes — orders of magnitude below
    the GSPMD scatter path's cross-shard gathers (EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    ep = mesh.shape[ep_axis]
    token_axes = tuple(a for a in token_axes if a in mesh.shape)
    if m.n_experts % ep != 0:
        raise ValueError(f"{m.n_experts} experts not divisible by {ep} shards")
    e_local = m.n_experts // ep
    seq_sharded = x.shape[1] % ep == 0  # shard S over ep_axis when possible

    def local_fn(x_l, router_w, gate_l, up_l, down_l):
        b_l, s_l, d = x_l.shape
        x2 = x_l.reshape(-1, d)
        t_l = x2.shape[0]
        vals, idx, aux = _route(x2, router_w, cfg)
        mean_axes = token_axes + ((ep_axis,) if seq_sharded else ())
        if mean_axes:
            aux = jax.lax.pmean(aux, mean_axes)

        # --- bin assignments by destination shard -----------------------
        flat_e = idx.reshape(-1)
        dest = flat_e // e_local                        # (T_l·k,)
        send_cap = moe_capacity(t_l, cfg) * e_local     # per dest shard
        pos = _positions_in_expert(dest, ep)
        keep = pos < send_cap
        safe = jnp.where(keep, pos, send_cap)
        send_tok = jnp.zeros((ep, send_cap, d), x_l.dtype)
        send_tok = send_tok.at[dest, safe].set(
            jnp.repeat(x2, m.top_k, axis=0), mode="drop")
        send_eid = jnp.full((ep, send_cap), -1, jnp.int32)
        send_eid = send_eid.at[dest, safe].set(flat_e % e_local, mode="drop")

        # --- exchange: tokens travel to their expert's shard -------------
        recv_tok = jax.lax.all_to_all(send_tok, ep_axis, 0, 0, tiled=True)
        recv_eid = jax.lax.all_to_all(send_eid, ep_axis, 0, 0, tiled=True)
        recv2 = recv_tok.reshape(ep * send_cap, d)
        eid_flat = recv_eid.reshape(-1)

        # --- local grouped compute over e_local experts -------------------
        r = recv2.shape[0]
        cap2 = -(-r // e_local) * 2                     # generous local cap
        pos2 = _positions_in_expert(
            jnp.where(eid_flat < 0, e_local, eid_flat), e_local + 1)
        valid = eid_flat >= 0
        keep2 = valid & (pos2 < cap2)
        safe2 = jnp.where(keep2, pos2, cap2)
        eid2 = jnp.where(valid, eid_flat, 0)
        buf = jnp.zeros((e_local, cap2, d), x_l.dtype)
        buf = buf.at[jnp.where(keep2, eid2, e_local), safe2].set(
            recv2, mode="drop")
        out_buf = _expert_ffn(buf, {"gate": gate_l, "up": up_l,
                                    "down": down_l}, cfg)
        back = out_buf.at[eid2, safe2].get(mode="fill", fill_value=0.0)
        back = back * keep2[:, None].astype(back.dtype)

        # --- return trip ---------------------------------------------------
        back = back.reshape(ep, send_cap, d)
        ret = jax.lax.all_to_all(back, ep_axis, 0, 0, tiled=True)

        # --- combine -------------------------------------------------------
        got = ret.at[dest, safe].get(mode="fill", fill_value=0.0)
        got = got * keep[:, None].astype(got.dtype)
        weighted = got.reshape(t_l, m.top_k, d) * vals[..., None].astype(got.dtype)
        y = jnp.sum(weighted, axis=1).reshape(b_l, s_l, d).astype(x_l.dtype)
        return y, aux

    x_spec = P(token_axes if token_axes else None,
               ep_axis if seq_sharded else None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(x_spec, P()), check_vma=False)
    return fn(x, p["router"], p["gate"], p["up"], p["down"])

"""Decoder-stack assembly for all 10 assigned architectures.

The layer pattern of an ``ArchConfig`` is tiled into *groups* (one period
each); the group stack is executed with ``lax.scan`` over stacked group
params (small HLO, enables XLA's collective/compute overlap inside the
scanned body) plus an explicitly-unrolled tail for layer counts that do not
divide the period (e.g. recurrentgemma's 38 = 12·3 + 2).  Activation
rematerialization wraps the group body per ``cfg.remat``.

Four entry points:
- ``forward``  — training forward → logits (+ MoE aux loss)
- ``prefill``  — forward that also returns the decode cache
- ``prefill_chunk`` — one fixed-size prompt chunk straight into a *paged*
  decode cache (the serving engine's incremental prefill: attention
  layers write the chunk's KV into pool pages and attend over the pages
  already holding the prefix — including pages merely aliased from the
  prefix cache — while ring/recurrent layers advance their slot's state
  where it lies in the cache)
- ``decode``   — single-token cached step
- ``verify_chunk`` — speculative-decoding verification: K candidate
  tokens per slot scored in one batched pass whose GEMMs carry M = B·K
  rows (paged attention reads the window in one masked pass; ring and
  recurrent mixers replay their exact decode step per position so greedy
  acceptance is bit-identical to K vanilla decode steps)

Cache pytrees mirror the params pytree: ``{"groups": stacked, "tail": [..]}``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed, init_embedding, init_mlp, init_norm,
                                 mlp, norm, unembed)

__all__ = ["init_params", "forward", "prefill", "prefill_chunk", "decode",
           "decode_and_sample", "sample_token", "verify_chunk", "draft_from",
           "init_cache", "init_paged_cache", "loss_fn", "param_count"]


# -- init ---------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, kinds) -> Dict[str, Any]:
    mixer_kind, ffn_kind = kinds
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.d_model, cfg.norm_type, dt)}
    if mixer_kind in ("attn", "local"):
        p["mixer"] = attn_mod.init_attention(ks[0], cfg)
    elif mixer_kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg)
    elif mixer_kind == "ssd":
        p["mixer"] = ssm_mod.init_ssd(ks[0], cfg)
    if ffn_kind != "none":
        p["norm2"] = init_norm(cfg.d_model, cfg.norm_type, dt)
        p["ffn"] = (moe_mod.init_moe(ks[1], cfg) if ffn_kind == "moe"
                    else init_mlp(ks[1], cfg))
    if cfg.post_norms:
        p["post_norm1"] = init_norm(cfg.d_model, cfg.norm_type, dt)
        if ffn_kind != "none":
            p["post_norm2"] = init_norm(cfg.d_model, cfg.norm_type, dt)
    return p


def _group_layout(cfg: ArchConfig) -> Tuple[int, int]:
    """(number of scanned full groups, number of tail layers)."""
    if not cfg.scan_layers:
        return 0, cfg.n_layers
    return cfg.n_layers // cfg.period, cfg.n_layers % cfg.period


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    n_groups, n_tail = _group_layout(cfg)
    keys = jax.random.split(key, cfg.n_layers + 2)
    kinds = cfg.layer_kinds

    groups = None
    if n_groups:
        per_group = []
        for g in range(n_groups):
            layer_ps = [
                _init_layer(keys[g * cfg.period + j], cfg, kinds[g * cfg.period + j])
                for j in range(cfg.period)
            ]
            per_group.append(layer_ps)
        groups = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)

    tail = [_init_layer(keys[n_groups * cfg.period + j], cfg,
                        kinds[n_groups * cfg.period + j])
            for j in range(n_tail)]

    return {
        "embedding": init_embedding(keys[-2], cfg),
        "groups": groups,
        "tail": tail,
        "final_norm": init_norm(cfg.d_model, cfg.norm_type,
                                jnp.dtype(cfg.param_dtype)),
    }


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# -- one layer ----------------------------------------------------------------


def _layer_state(cache, layer, slot, fn, row_valid=None):
    """Run ``fn`` on per-slot state and write its new state back where
    the state lies: → (out, new_cache).

    ``cache`` is batch-axis-0 state (a ring, RG-LRU or SSD state, a
    contiguous KV cache): one layer's (B, ...) leaves, or with ``layer``
    the stacked (n_groups, B, ...) leaves the layer scan carries, of
    which this layer reads and writes only index ``layer``.  ``slot``
    narrows the read and the write to that batch row, so ``fn`` sees
    (1, ...) state.  ``fn(state)`` → (out, new_state).  ``row_valid``
    keeps the old state of invalid rows (:func:`_mask_rows`).
    """
    lead = (() if layer is None else (layer,)) + (() if slot is None
                                                  else (slot,))

    def at(a):
        return (lead + (0,) * (a.ndim - len(lead)),
                (1,) * len(lead) + a.shape[len(lead):])

    def read(a):
        start, size = at(a)
        one = jax.lax.dynamic_slice(a, start, size)
        return one if layer is None else one[0]

    def write(a, new):
        new = new.astype(a.dtype)
        return jax.lax.dynamic_update_slice(
            a, new if layer is None else new[None], at(a)[0])

    old = jax.tree.map(read, cache)
    out, new = fn(old)
    if row_valid is not None:
        new = _mask_rows(new, old, row_valid)
    return out, jax.tree.map(write, cache, new)


def _is_paged(cache) -> bool:
    """A paged KV pool layer cache (as opposed to per-slot state)."""
    return isinstance(cache, dict) and "k_pages" in cache


def _mask_rows(new, old, row_valid):
    """Keep only the valid batch rows of a batch-axis-0 cache update.

    ``row_valid`` is a (B,) bool vector; rows where it is False keep the
    old state, so a batched decode step over a partially-active batch
    cannot corrupt the ring/recurrent state of rows that are still
    prefilling (or quarantined) — the mask replaces the engine's former
    snapshot-and-undo of those rows.
    """
    def merge(n, o):
        m = row_valid.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o.astype(n.dtype))
    return jax.tree.map(merge, new, old)


def _apply_layer(x, lp, cfg: ArchConfig, kinds, positions, mode: str,
                 cache=None, pos=None, cache_len: Optional[int] = None,
                 page_table=None, slot=None, chunk_pos0: Optional[int] = None,
                 row_valid=None, layer=None):
    """Returns (x, new_cache, aux).

    ``aux`` float32: 0 for a layer without experts; for an expert layer a
    (3,) vector, its router load-balance loss, then the counts of a
    held-share expert layer (:func:`repro.models.moe.apply_moe_held`;
    exact in float32 below 2**24), which counts the ``row_valid`` rows
    (all rows without it).

    ``layer`` (inside the group scan of a mode that threads a cache):
    ``cache`` is the group's whole stacked leaf — paged pool or per-slot
    state — and ``layer`` this layer's index into it; the layer reads and
    writes only its own index (:func:`_layer_state`, ``_store_kv``).

    ``mode="prefill_chunk"`` runs one (1, C, D) prompt chunk against the
    serving cache: paged attention layers scatter the chunk's KV into
    pool pages and read the whole prefix back through ``page_table``
    (``chunk_pos0`` is the chunk's static first position); ring/recurrent
    layers carry the state of batch row ``slot``.

    ``row_valid`` (decode/verify): (B,) bool — batch rows whose cache
    update should be kept and whose expert work is counted.  Paged-attention layers ignore it (inactive
    rows already write into the reserved null page through the all-−1
    page-table row); batch-axis caches (ring/RG-LRU/SSD state) are
    where-merged so invalid rows keep their prior state.

    ``mode="verify"`` scores a (B, K, D) speculative window starting at
    per-row positions ``pos``.  Paged attention handles all K positions
    in one batched read (layout-identical to K decode reads — see
    ``verify_paged_attention``); every other mixer is a sequential
    recurrence whose batched formulation re-associates floating point, so
    those replay the *decode-step* kernel once per window position —
    keeping greedy verification bit-identical to vanilla decode while
    the dense FFN/projection GEMMs still run with M = B·K rows.
    """
    mixer_kind, ffn_kind = kinds
    window = cfg.window if mixer_kind == "local" else None
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    h = norm(x, lp["norm1"], cfg.norm_type, cfg.norm_eps)

    def on_state(c):
        """(out, new state) of a mixer that carries per-slot state ``c``."""
        if mode == "prefill_chunk":
            if mixer_kind in ("attn", "local"):  # sliding-window ring
                return attn_mod.ring_chunk_attention(
                    h, lp["mixer"], cfg, c, positions, pos0=chunk_pos0,
                    window=window)
            # Chunk 0 starts fresh (the slot row holds its previous
            # occupant's state); later chunks resume the carried state.
            forward = {"rglru": rglru_mod.rglru_forward,
                       "ssd": ssm_mod.ssd_forward}[mixer_kind]
            return forward(h, lp["mixer"], cfg, return_cache=True,
                           cache=c if chunk_pos0 else None)
        # decode, verify: one exact decode step per window position.
        step = {"rglru": lambda hi, c, i: rglru_mod.rglru_decode(
                    hi, lp["mixer"], cfg, c),
                "ssd": lambda hi, c, i: ssm_mod.ssd_decode(
                    hi, lp["mixer"], cfg, c),
                "attn": lambda hi, c, i: attn_mod.decode_attention(
                    hi, lp["mixer"], cfg, c, pos + i, window=window),
                "local": lambda hi, c, i: attn_mod.decode_attention(
                    hi, lp["mixer"], cfg, c, pos + i, window=window),
                }[mixer_kind]
        outs = []
        for i in range(h.shape[1]):
            o, c = step(h[:, i:i + 1], c, i)
            outs.append(o)
        return jnp.concatenate(outs, axis=1), c

    if mode in ("decode", "prefill_chunk", "verify") and not _is_paged(cache):
        out, new_cache = _layer_state(
            cache, layer, slot if mode == "prefill_chunk" else None,
            on_state, row_valid)
    elif mode == "verify":
        out, new_cache = attn_mod.verify_paged_attention(
            h, lp["mixer"], cfg, cache, pos, page_table, layer=layer)
    elif mode == "prefill_chunk":
        out, new_cache = attn_mod.paged_prefill_attention(
            h, lp["mixer"], cfg, cache, positions, page_table,
            kv_len=chunk_pos0 + h.shape[1], layer=layer)
    elif mode == "decode":
        # Paged KV pool (serving): the layer reads/writes through the
        # batch-wide page table instead of a per-slot cache stripe.
        out, new_cache = attn_mod.paged_decode_attention(
            h, lp["mixer"], cfg, cache, pos, page_table, window=window,
            layer=layer)
    elif mixer_kind in ("attn", "local"):
        if mode == "prefill":
            out, (k, v) = attn_mod.attention(
                h, lp["mixer"], cfg, positions, window=window, return_kv=True)
            new_cache = attn_mod.prefill_cache(
                k, v, cfg, cache_len or positions.shape[-1], window,
                jnp.dtype(cfg.compute_dtype))
        else:
            out = attn_mod.attention(h, lp["mixer"], cfg, positions,
                                     window=window)
    elif mixer_kind in ("rglru", "ssd"):
        forward = {"rglru": rglru_mod.rglru_forward,
                   "ssd": ssm_mod.ssd_forward}[mixer_kind]
        if mode == "prefill":
            out, new_cache = forward(h, lp["mixer"], cfg, return_cache=True)
        else:
            out = forward(h, lp["mixer"], cfg)
    else:
        raise ValueError(mixer_kind)

    if cfg.post_norms:
        out = norm(out, lp["post_norm1"], cfg.norm_type, cfg.norm_eps)
    x = _residual(x, out, cfg)

    if ffn_kind != "none":
        h = norm(x, lp["norm2"], cfg.norm_type, cfg.norm_eps)
        if ffn_kind == "moe":
            out, aux = _moe_dispatch(h, lp["ffn"], cfg, mode, row_valid)
        else:
            out = mlp(h, lp["ffn"], cfg)
        if cfg.post_norms:
            out = norm(out, lp["post_norm2"], cfg.norm_type, cfg.norm_eps)
        x = _residual(x, out, cfg)
    return x, new_cache, aux


def _residual(x, branch, cfg: ArchConfig):
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def _moe_dispatch(h, ffn_params, cfg: ArchConfig, mode: str, row_valid):
    """Route to the configured MoE implementation: → (out, aux), ``aux``
    as :func:`_apply_layer` returns it.

    Serving a held share (``cfg.moe.held``) takes the dropless
    ``apply_moe_held`` in every mode but training, whose capacity path
    needs every expert held.  Otherwise ``a2a`` (the beyond-paper §Perf
    optimization) needs an ambient mesh with a "model" axis and a sequence
    divisible by it, and else falls back to the GSPMD scatter path (also
    the single-device smoke-test path).  A shared expert adds its output
    to either.
    """
    m = cfg.moe
    loss = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    if m.held is not None and mode != "train":
        out, counts = moe_mod.apply_moe_held(h, ffn_params, cfg, row_valid)
    elif m.held is not None and m.held < m.n_experts:
        raise ValueError(f"training needs every expert held, not "
                         f"{m.held} of {m.n_experts}")
    else:
        out, loss = _moe_capacity(h, ffn_params, cfg)
    if m.d_ff_shared:
        out = out + moe_mod.shared_expert(h, ffn_params, cfg)
    return out, jnp.concatenate([loss[None], counts.astype(jnp.float32)])


def _moe_capacity(h, ffn_params, cfg: ArchConfig):
    if cfg.moe_impl == "a2a":
        am = jax.sharding.get_abstract_mesh()
        if (am is not None and not getattr(am, "empty", True)
                and "model" in am.axis_names
                and h.shape[1] % am.shape["model"] == 0):
            return moe_mod.apply_moe_a2a(h, ffn_params, cfg, mesh=am)
    return moe_mod.apply_moe(h, ffn_params, cfg)


# -- stack --------------------------------------------------------------------


def _remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _run_stack(x, params, cfg: ArchConfig, positions, mode: str,
               cache=None, pos=None, cache_len: Optional[int] = None,
               page_table=None, slot=None, chunk_pos0: Optional[int] = None,
               row_valid=None):
    """Scan the group stack + unrolled tail.  Returns (x, new_cache, aux):
    ``aux`` sums the layers' (:func:`_apply_layer`).

    In the modes that thread a cache (decode, prefill_chunk, verify), the
    cache is updated where it lies: every stacked (n_groups, ...) leaf of
    the group cache — paged pool, SSD state and conv history, RG-LRU
    state, sliding-window ring — rides in the scan carry, ``xs`` holds
    only the parameters and the layer index, and each layer reads and
    writes its own index of its leaves (one slot's of per-slot state in a
    chunk).  No layer slices out its whole slab to write it back as
    ``xs``/``ys`` would, and no program copies the cache at entry or exit.
    ``prefill`` returns the layers' caches as ``ys``; the unrolled tail
    takes and returns its layers' own caches.
    """
    n_groups, n_tail = _group_layout(cfg)
    kinds = cfg.layer_kinds
    aux_total = _zero_aux(cfg)
    new_cache = {"groups": None, "tail": []}
    cached_modes = ("prefill", "decode", "prefill_chunk", "verify")
    threads_cache = mode in ("decode", "prefill_chunk", "verify")

    if n_groups:
        def group_body(carry, xs):
            from repro.distributed.sharding import constrain
            xc, auxc, gc = carry
            # Pin the scan carry (and its saved-for-backward residuals) to
            # batch sharding — inference can drift to weight-style sharding.
            xc = constrain(xc, ("pod", "data"), None, None)
            gp, i = xs if threads_cache else (xs, None)
            caches_out = []
            for j in range(cfg.period):
                xc, c_new, aux = _apply_layer(
                    xc, _index_tree(gp, j), cfg, kinds[j], positions, mode,
                    cache=None if gc is None else gc[j], pos=pos,
                    cache_len=cache_len, page_table=page_table, slot=slot,
                    chunk_pos0=chunk_pos0, row_valid=row_valid, layer=i)
                caches_out.append(c_new)
                auxc = auxc + aux
            if threads_cache:
                return (xc, auxc, tuple(caches_out)), None
            ys = tuple(caches_out) if mode in cached_modes else None
            return (xc, auxc, None), ys

        body = _remat(group_body, cfg)
        if threads_cache:
            carry = (x, aux_total, cache["groups"])
            xs = (params["groups"], jnp.arange(n_groups, dtype=jnp.int32))
        else:
            carry, xs = (x, aux_total, None), params["groups"]
        (x, aux_total, carried), ys = jax.lax.scan(body, carry, xs)
        if mode in cached_modes:
            new_cache["groups"] = carried if threads_cache else ys

    for j in range(n_tail):
        idx = n_groups * cfg.period + j
        layer_cache = cache["tail"][j] if (cache and threads_cache) else None
        x, c_new, aux = _apply_layer(
            x, params["tail"][j], cfg, kinds[idx], positions, mode,
            cache=layer_cache, pos=pos, cache_len=cache_len,
            page_table=page_table, slot=slot, chunk_pos0=chunk_pos0,
            row_valid=row_valid)
        aux_total = aux_total + aux
        if mode in cached_modes:
            new_cache["tail"].append(c_new)

    return x, new_cache, aux_total


def _index_tree(tree, j: int):
    """Select position-j layer params out of a per-group params structure."""
    return tree[j] if isinstance(tree, (list, tuple)) else tree


# -- entry points --------------------------------------------------------------


def _inputs_to_x(batch, params, cfg: ArchConfig):
    from repro.distributed.sharding import constrain
    if cfg.frontend_stub:
        x = batch["embeddings"].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        x = embed(tokens, params["embedding"], cfg)
        b, s = tokens.shape
    return constrain(x, ("pod", "data"), None, None), b, s


def forward(params, batch, cfg: ArchConfig):
    """Training forward: → (logits f32 (B, S, V), aux loss)."""
    from repro.distributed.sharding import constrain
    x, b, s = _inputs_to_x(batch, params, cfg)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    x, _, aux = _run_stack(x, params, cfg, positions, "train")
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = unembed(x, params["embedding"], cfg)
    # Keep the (B, S, V) logits sharded batch×vocab — unconstrained they
    # replicate and 1M tokens × 256k vocab × f32 is petabytes.
    logits = constrain(logits, ("pod", "data"), None, "model")
    return logits, _aux_loss(aux)


def _zero_aux(cfg: ArchConfig):
    """A stack's zero ``aux`` (:func:`_apply_layer`): (3,) where a layer
    holds experts, else a scalar."""
    sparse = any(ffn == "moe" for _, ffn in cfg.layer_kinds)
    return jnp.zeros((3,) if sparse else (), jnp.float32)


def _aux_loss(aux):
    return aux if aux.ndim == 0 else aux[0]


def _counts(aux):
    """The expert counts of a stack's ``aux`` (:func:`_run_stack`)."""
    if aux.ndim == 0:
        return jnp.zeros((2,), jnp.int32)
    return aux[1:].astype(jnp.int32)


def prefill(params, batch, cfg: ArchConfig, cache_len: Optional[int] = None):
    """Prefill: → (last-position logits (B, V), cache).

    ``cache_len`` sets the decode capacity of the returned KV caches
    (defaults to the prefill length — pass the serving max_seq_len when
    decode steps will follow)."""
    x, b, s = _inputs_to_x(batch, params, cfg)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    x, cache, _ = _run_stack(x, params, cfg, positions, "prefill",
                             cache_len=cache_len)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = unembed(x[:, -1:], params["embedding"], cfg)
    return logits[:, 0], cache


def prefill_chunk(params, batch, cache, cfg: ArchConfig, *, pos0: int,
                  with_counts: bool = False):
    """One fixed-size prompt chunk against a *paged* decode cache.

    ``batch``: ``tokens`` (1, C) — the chunk, absolute positions
    ``[pos0, pos0+C)``; ``page_table`` (1, max_pages) — the sequence's
    logical→physical page map (every page covering ``[0, pos0+C)`` must
    be allocated, cached-prefix pages included); ``slot`` — scalar int32
    batch row whose ring/recurrent state this chunk advances.  ``pos0``
    is static: each chunk index compiles once, every chunk's GEMMs share
    the one (C, D) plan-cache signature, and the attention read covers
    exactly the live prefix.  Returns (last-position logits (1, V),
    new_cache) — the final chunk's logits seed sampling, mid-prompt
    chunks' logits are discarded.  ``with_counts`` appends the chunk's
    expert counts (:func:`_run_stack`; every row counts).
    """
    x, b, s = _inputs_to_x(batch, params, cfg)
    positions = pos0 + jnp.arange(s, dtype=jnp.int32)[None, :]
    x, new_cache, aux = _run_stack(
        x, params, cfg, positions, "prefill_chunk", cache=cache,
        slot=batch.get("slot", 0), page_table=batch["page_table"],
        chunk_pos0=pos0)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = unembed(x[:, -1:], params["embedding"], cfg)
    if with_counts:
        return logits[:, 0], new_cache, _counts(aux)
    return logits[:, 0], new_cache


def decode(params, batch, cache, cfg: ArchConfig):
    """One-token decode: → (logits (B, V), new_cache).

    ``batch["pos"]`` is a scalar or a (B,) vector of per-sequence positions
    (continuous batching: slots sit at different depths).  With a paged
    cache (``init_paged_cache``), ``batch["page_table"]`` carries the
    (B, max_pages) int32 logical→physical page map the attention layers
    read KV through.  ``batch["row_valid"]`` (optional, (B,) bool) marks
    the rows whose batch-axis cache updates should be kept — see
    :func:`_mask_rows`."""
    logits, new_cache, _ = _decode(params, batch, cache, cfg)
    return logits, new_cache


def _decode(params, batch, cache, cfg: ArchConfig):
    """:func:`decode` with the step's expert counts of the ``row_valid``
    rows: → (logits, new_cache, counts)."""
    pos = batch["pos"]
    x, b, s = _inputs_to_x(batch, params, cfg)
    positions = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))
    row_valid = batch.get("row_valid")
    if row_valid is not None:
        row_valid = jnp.asarray(row_valid, bool).reshape(-1)
    x, new_cache, aux = _run_stack(x, params, cfg, positions, "decode",
                                   cache=cache, pos=pos,
                                   page_table=batch.get("page_table"),
                                   row_valid=row_valid)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = unembed(x, params["embedding"], cfg)
    return logits[:, 0], new_cache, _counts(aux)


def sample_token(logits, key, temperature):
    """Sample one token per row from ``logits`` on-device.

    ``temperature`` is a scalar or (B,) vector; rows with temperature
    <= 0 take the fp32 argmax (bit-identical to host-side
    ``np.argmax`` of the same values — XLA and numpy both break ties on
    the lowest index), rows with temperature > 0 draw from
    ``jax.random.categorical`` under a per-row key
    (``fold_in(key, row)``), so the whole sampling step stays inside the
    async dispatch stream.  → (tokens (B,) int32, finite (B,) bool) —
    ``finite`` is the row-wise NaN/inf quarantine predicate, computed
    here so the host never needs the logits to check it."""
    lf = jnp.asarray(logits, jnp.float32)
    temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32).reshape(-1),
                             (lf.shape[0],))
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    row_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(lf.shape[0], dtype=jnp.uint32))
    safe = jnp.where(temps > 0, temps, 1.0)
    sampled = jax.vmap(jax.random.categorical)(
        row_keys, lf / safe[:, None]).astype(jnp.int32)
    tokens = jnp.where(temps > 0, sampled, greedy)
    finite = jnp.isfinite(lf).all(axis=-1)
    return tokens, finite


def decode_and_sample(params, batch, cache, cfg: ArchConfig, *,
                      key, temperatures, active_rows,
                      with_counts: bool = False):
    """One decode step with sampling fused into the same jitted program.

    This is the async-serving entry point: the host never has to fetch
    the (B, V) logits to pick a token, so a ``jax.jit`` of this function
    returns device futures the engine can chain into the *next* step's
    inputs before ever blocking.  ``batch["tokens"]`` doubles as the
    carried last-token state: rows in ``active_rows`` are updated with
    the freshly sampled token, inactive rows keep their previous value,
    and the returned ``next_tokens`` feeds straight back in as the next
    step's ``batch["tokens"]``.

    → (tokens (B,) int32, finite (B,) bool, logits_f32 (B, V),
    next_tokens (B, 1) int32, new_cache).  The fp32 logits remain an
    output so fault-injection runs can still fetch and poison them
    host-side; greedy rows are the argmax of exactly these values, so
    host-side ``np.argmax`` re-derivation matches bit-for-bit.
    ``with_counts`` appends the step's expert counts of the
    ``batch["row_valid"]`` rows."""
    if with_counts:
        logits, new_cache, counts = _decode(params, batch, cache, cfg)
    else:
        logits, new_cache = decode(params, batch, cache, cfg)
    lf = jnp.asarray(logits, jnp.float32)
    tokens, finite = sample_token(lf, key, temperatures)
    active = jnp.asarray(active_rows, bool).reshape(-1)
    next_tokens = jnp.where(active[:, None], tokens[:, None],
                            jnp.asarray(batch["tokens"], jnp.int32))
    out = (tokens, finite, lf, next_tokens, new_cache)
    return out + (counts,) if with_counts else out


def verify_chunk(params, batch, cache, cfg: ArchConfig):
    """Speculative-decoding verification: → (logits f32 (B, K, V), new_cache).

    ``batch["tokens"]`` is (B, K): per row, the last *emitted* token
    followed by K−1 draft proposals; ``batch["pos"]`` (scalar or (B,))
    gives each row's window start — the position of that last emitted
    token, i.e. the number of positions already holding KV.  Logits row
    ``i`` is the target distribution for position ``pos+i+1`` and judges
    draft ``i+1`` — the engine accepts the longest prefix of drafts the
    target agrees with and resamples at the first mismatch.

    Paged attention scores all K positions in one pass; ring/recurrent
    layers replay exact decode steps (see ``_apply_layer``); FFN and
    projection GEMMs run once with M = B·K rows — the tall/skinny M=1
    decode GEMV becomes a small GEMM on the same plan-cache signature
    family as a prefill chunk.  ``batch["row_valid"]`` masks batch-axis
    cache updates as in :func:`decode`; rows the engine later rejects are
    rolled back by restoring state and replaying accepted tokens (paged
    KV past the accepted point is garbage the next window overwrites).
    """
    pos = batch["pos"]
    x, b, s = _inputs_to_x(batch, params, cfg)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = pos_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    row_valid = batch.get("row_valid")
    if row_valid is not None:
        row_valid = jnp.asarray(row_valid, bool).reshape(-1)
    x, new_cache, _ = _run_stack(x, params, cfg, positions, "verify",
                                 cache=cache, pos=pos_b,
                                 page_table=batch.get("page_table"),
                                 row_valid=row_valid)
    x = norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    logits = unembed(x, params["embedding"], cfg)
    return logits.astype(jnp.float32), new_cache


def draft_from(params, cfg: ArchConfig, *, groups: int = 1):
    """Weight-shared draft params: the first ``groups`` layer groups of a
    scanned target stack, plus the target's embedding/unembedding and
    final norm.  Pairs with ``cfg.draft(groups)`` — a truncated-depth
    draft costs no extra memory (every leaf is a view/slice of the target
    params) and is the zero-setup baseline drafter; a distilled or
    separately-trained draft can be substituted by passing any params
    matching the draft config.
    """
    n_groups, _ = _group_layout(cfg)
    if not n_groups:
        raise ValueError("draft_from needs a scanned group stack "
                         "(cfg.scan_layers with n_layers >= period)")
    if not 0 < groups <= n_groups:
        raise ValueError(f"groups must be in [1, {n_groups}], got {groups}")
    return {
        "embedding": params["embedding"],
        "groups": jax.tree.map(lambda a: a[:groups], params["groups"]),
        "tail": [],
        "final_norm": params["final_norm"],
    }


def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    """Zero decode cache for all layers (fixed-capacity)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    n_groups, n_tail = _group_layout(cfg)
    kinds = cfg.layer_kinds

    def layer_cache(kind):
        mixer = kind[0]
        if mixer in ("attn", "local"):
            window = cfg.window if mixer == "local" else None
            return attn_mod.init_attn_cache(cfg, batch, seq_len, window, cdt)
        if mixer == "rglru":
            return rglru_mod.init_rglru_cache(cfg, batch, cdt)
        if mixer == "ssd":
            return ssm_mod.init_ssd_cache(cfg, batch, cdt)
        raise ValueError(mixer)

    groups = None
    if n_groups:
        one_group = tuple(layer_cache(kinds[j]) for j in range(cfg.period))
        groups = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), one_group)
    tail = [layer_cache(kinds[n_groups * cfg.period + j])
            for j in range(n_tail)]
    return {"groups": groups, "tail": tail}


def init_paged_cache(cfg: ArchConfig, batch: int, seq_len: int, *,
                     num_pages: int, page_size: int):
    """Decode cache whose global-attention layers store KV in fixed-size
    pages of a shared pool (physical page 0 reserved as the null page).

    Sliding-window (ring), RG-LRU and SSD layers keep their fixed
    per-slot state — their decode memory is already O(window)/O(1), so
    paging them buys nothing.  ``cfg.kv_cache_format`` selects the paged
    storage format (int8pt/int8 add scale pages).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    n_groups, n_tail = _group_layout(cfg)
    kinds = cfg.layer_kinds

    def layer_cache(kind):
        mixer = kind[0]
        if mixer == "attn":
            return attn_mod.init_paged_attn_cache(cfg, num_pages, page_size,
                                                  cdt)
        if mixer == "local":
            return attn_mod.init_attn_cache(cfg, batch, seq_len, cfg.window,
                                            cdt)
        if mixer == "rglru":
            return rglru_mod.init_rglru_cache(cfg, batch, cdt)
        if mixer == "ssd":
            return ssm_mod.init_ssd_cache(cfg, batch, cdt)
        raise ValueError(mixer)

    groups = None
    if n_groups:
        one_group = tuple(layer_cache(kinds[j]) for j in range(cfg.period))
        groups = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), one_group)
    tail = [layer_cache(kinds[n_groups * cfg.period + j])
            for j in range(n_tail)]
    return {"groups": groups, "tail": tail}


# -- loss ----------------------------------------------------------------------


def loss_fn(params, batch, cfg: ArchConfig):
    """Next-token cross entropy (+ MoE aux).  Returns (loss, metrics)."""
    logits, aux = forward(params, batch, cfg)
    if cfg.frontend_stub:
        targets = batch["targets"]
        valid = jnp.ones_like(targets, jnp.float32)
    else:
        tokens = batch["tokens"]
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        valid = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:], jnp.float32),
             jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
    # nll = logsumexp(logits) − logits[target], with the target picked via a
    # mask-and-sum instead of take_along_axis: a gather along the
    # model-sharded vocab dim would force GSPMD to replicate the (B, S, V)
    # logits, and the logsumexp form never materializes full log-probs.
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    onehot = (vocab_iota == targets[..., None]).astype(logits.dtype)
    target_logit = jnp.sum(logits * onehot, axis=-1)
    nll = lse - target_logit
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    ce = jnp.sum(nll * valid) / denom
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux,
                  "tokens": denom}

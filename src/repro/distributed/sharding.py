"""Sharding policy: parameter / activation PartitionSpecs per architecture.

Strategy (1000+ node design, see DESIGN.md §5):

- **TP on "model"**: attention heads, MLP hidden, MoE experts, vocab.
- **FSDP on "data"**: the other matrix dim of every large weight is sharded
  over the data axis.  GSPMD all-gathers weights per layer on use and
  reduce-scatters gradients in the transpose — ZeRO-3 with zero manual
  collectives.  Optimizer state mirrors params ⇒ fully sharded too.
- **"pod"**: hierarchical data parallelism.  Params are *replicated* across
  pods (gradient all-reduce crosses the pod axis once per step); the batch
  is sharded over (pod, data).
- Activations: the batch dim is sharded over (pod, data); everything else
  propagates.  Decode shards the KV cache batch over (pod, data) and KV
  heads over "model" where head counts allow.

``param_specs(cfg, params)`` walks the params pytree by path and assigns a
spec from name rules; leading stacked-group dims get a None prepended
automatically (specs are rank-aware).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["batch_axes", "param_specs", "batch_specs", "cache_specs",
           "named_shardings", "logical_to_sharding", "constrain",
           "fit_spec"]


def constrain(x, *dims):
    """with_sharding_constraint against the ambient mesh, dropping axis
    names the mesh does not define; no-op outside any mesh context.

    ``dims`` entries: None, an axis name, or a tuple of axis names.
    """
    am = jax.sharding.get_abstract_mesh()
    if am is None or getattr(am, "empty", True):
        return x
    names = set(am.axis_names)

    def keep(d):
        if d is None:
            return None
        if isinstance(d, tuple):
            kept = tuple(a for a in d if a in names)
            return kept if kept else None
        return d if d in names else None

    spec = P(*[keep(d) for d in dims])
    return jax.lax.with_sharding_constraint(x, spec)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _rule(path: Tuple[str, ...], leaf_ndim: int, cfg) -> P:
    """Name-rule table → PartitionSpec for the *trailing* named dims."""
    name = "/".join(path)
    last = path[-1]
    parent = path[-2] if len(path) >= 2 else ""

    # ---- embeddings -----------------------------------------------------
    if last == "table":
        return P("model", "data")           # (vocab, d_model)
    if last == "head":
        return P("data", "model")           # (d_model, vocab)

    # ---- MoE ------------------------------------------------------------
    if last == "router":
        return P(None, None)
    if parent == "ffn" and last in ("gate", "up") and leaf_ndim == 3:
        return P("model", "data", None)     # (E, D, Fe): EP + FSDP
    if parent == "ffn" and last == "down" and leaf_ndim == 3:
        return P("model", None, "data")     # (E, Fe, D)

    # ---- attention -------------------------------------------------------
    if parent in ("q", "k", "v") and last == "w":
        return P("data", "model")           # (D, H·hd)
    if parent in ("q", "k", "v") and last == "b":
        return P("model")
    if parent == "o" and last == "w":
        return P("model", "data")           # (H·hd, D)
    if parent == "o" and last == "b":
        return P(None)

    # ---- dense MLP --------------------------------------------------------
    if parent in ("gate", "up", "gate_proj", "rec_proj", "wa", "wx",
                  "in_proj") and last == "w":
        return P("data", "model")
    if parent in ("gate", "up", "gate_proj", "rec_proj", "wa", "wx",
                  "in_proj") and last == "b":
        return P("model")
    if parent in ("down", "out_proj") and last == "w":
        return P("model", "data")
    if parent in ("down", "out_proj") and last == "b":
        return P(None)

    # ---- convs / vectors / norms -------------------------------------------
    if last in ("conv_w", "conv_b"):
        return P(None) if leaf_ndim == 1 else P(None, "model")
    if last in ("scale", "bias", "lam", "A_log", "D", "dt_bias",
                "norm_scale"):
        return P(None)
    return P(*([None] * leaf_ndim))


def _axis_size(mesh: Mesh, d) -> int:
    if d is None:
        return 1
    if isinstance(d, tuple):
        out = 1
        for a in d:
            out *= mesh.shape[a]
        return out
    return mesh.shape[d]


def fit_spec(mesh: Mesh, dims, shape) -> P:
    """Drop axis names whose size does not divide the dimension — explicit
    jit in_shardings require exact divisibility (uneven dims fall back to
    replication on that dim)."""
    out = []
    for d, n in zip(dims, shape):
        if d is not None and n % _axis_size(mesh, d) != 0:
            if isinstance(d, tuple):
                kept = []
                size = 1
                for a in d:
                    if n % (size * mesh.shape[a]) == 0:
                        kept.append(a)
                        size *= mesh.shape[a]
                d = tuple(kept) if kept else None
            else:
                d = None
        out.append(d)
    return P(*out)


def param_specs(cfg, params, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching ``params`` (handles stacked groups)."""
    def visit(path, leaf):
        names = tuple(_key_name(k) for k in path)
        stacked = "groups" in names  # leading (n_groups,) dim
        spec = _rule(tuple(n for n in names if not n.isdigit() and
                           n not in ("groups", "tail")) or names,
                     leaf.ndim - (1 if stacked else 0), cfg)
        dims = list(spec)
        # pad/trim to leaf rank
        base = leaf.ndim - (1 if stacked else 0)
        dims = (dims + [None] * base)[:base]
        if stacked:
            dims = [None] + dims
        return fit_spec(mesh, dims, leaf.shape)

    return jax.tree_util.tree_map_with_path(visit, params)


def _key_name(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def batch_specs(mesh: Mesh, batch_tree) -> Any:
    axes = batch_axes(mesh)
    spec_b = axes if axes else None

    def visit(leaf):
        if leaf.ndim == 0:
            return P()
        return fit_spec(mesh, [spec_b] + [None] * (leaf.ndim - 1),
                        leaf.shape)

    return jax.tree.map(visit, batch_tree)


def cache_specs(cfg, mesh: Mesh, cache_tree) -> Any:
    """KV/recurrent-state cache: batch over (pod, data); model axis on the
    KV-head dim when divisible, else replicated on that dim."""
    axes = batch_axes(mesh)
    model = mesh.shape.get("model", 1)

    def visit(path, leaf):
        names = tuple(_key_name(k) for k in path)
        stacked = "groups" in names
        base_ndim = leaf.ndim - (1 if stacked else 0)
        last = names[-1]
        dims: list = [axes if axes else None] + [None] * (base_ndim - 1)
        if last in ("k", "v", "k_scale", "v_scale") and base_ndim == 4:
            # (B, S, kv_heads, hd): shard kv heads when they divide the
            # axis; MHA/MQA head counts that don't divide fall back to
            # head_dim sharding when enabled (§Perf iteration: qwen1.5's
            # kv=20 cache otherwise replicates 16× across model ranks).
            if getattr(cfg, "cache_shard_seq", False):
                dims[1] = "model"           # flash-decode: shard KV sequence
            elif cfg.n_kv_heads % model == 0:
                dims[2] = "model"
            elif getattr(cfg, "cache_shard_hd", False) and cfg.hd % model == 0:
                dims[3] = "model"
        if stacked:
            dims = [None] + dims
        shape = leaf.shape
        return fit_spec(mesh, dims, shape)

    return jax.tree_util.tree_map_with_path(visit, cache_tree)


def named_shardings(mesh: Mesh, spec_tree) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def logical_to_sharding(mesh: Mesh, cfg, params_shape) -> Any:
    return named_shardings(mesh, param_specs(cfg, params_shape, mesh))

"""Architecture configuration system.

One ``ArchConfig`` instance fully describes a model: the decoder layer
pattern (attention / local attention / RG-LRU / Mamba2-SSD mixers, MLP or
MoE feed-forward), all dimension and feature switches the 10 assigned
architectures need, and the execution knobs (GEMM policy/backend, remat,
compute dtype).  ``reduced()`` derives the CPU smoke-test configuration of
the same family.  ``input_specs()`` produces ShapeDtypeStruct stand-ins for
the dry-run (no allocation).

Registry: ``get_config(name)`` — one module per assigned architecture under
``repro/configs/`` registers itself.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ArchConfig", "MoEConfig", "SSMConfig", "RGLRUConfig",
           "ShapeSpec", "SHAPES", "ARCH_NAMES", "get_config", "input_specs"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # Experts held on this device: the first ``held`` ids.  Set, the layer
    # serves dropless over its share (``moe.apply_moe_held``); None, every
    # expert is held and serving dispatches by capacity as training does.
    held: Optional[int] = None
    d_ff_shared: int = 0          # a shared SwiGLU expert beside the routed


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    norm_eps: float = 1e-6        # the gated RMSNorm before out_proj


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    width: Optional[int] = None   # defaults to d_model
    conv_width: int = 4
    c: float = 8.0                # the a_t = a^(c·r_t) exponent constant


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # defaults to d_model // n_heads
    # Layer pattern: one period of (mixer, ffn) kinds, tiled over n_layers.
    # mixer: "attn" | "local" | "rglru" | "ssd"; ffn: "mlp" | "moe" | "none".
    pattern: Tuple[Tuple[str, str], ...] = (("attn", "mlp"),)
    window: Optional[int] = None            # sliding window for "local"
    mlp_type: str = "swiglu"                # swiglu | geglu | gelu
    norm_type: str = "rmsnorm"              # rmsnorm | layernorm
    qkv_bias: bool = False
    mlp_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope: bool = True                       # False: no position embedding
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    attn_scale: Optional[float] = None      # defaults to head_dim ** -0.5
    post_norms: bool = False                # gemma2 post-attn/ffn norms
    tied_embeddings: bool = False
    embed_scale: bool = False               # gemma: x *= sqrt(d_model)
    norm_eps: Optional[float] = None        # None: each norm's own default
    # Granite's multipliers: the embedding's output, each residual branch
    # and (dividing) the logits.
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    frontend_stub: bool = False             # audio/vlm: inputs are embeddings
    # execution knobs
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    format_policy: Optional[str] = None     # repro.core.formats policy name
    #                                         (fp32|bf16|bf16acc|int8); None
    #                                         infers from compute_dtype.  The
    #                                         SEW contract: every projection /
    #                                         expert GEMM runs under this
    #                                         format and gets per-format
    #                                         cached plans.
    gemm_policy: str = "mte"                # mte | amx | xla (dispatch policy)
    gemm_backend: str = "xla"               # xla | pallas
    remat: str = "full"                     # none | full | dots
    scan_layers: bool = True
    moe_impl: str = "scatter"               # scatter (GSPMD) | a2a (shard_map)
    attn_chunk: int = 1024                  # KV-chunk for the XLA flash scan
    cache_shard_hd: bool = True             # decode KV: shard head_dim on
    #                                         "model" when kv_heads don't divide
    #                                         (§Perf pair 2: 11x; inert otherwise)
    cache_shard_seq: bool = False           # decode KV: shard cache seq dim
    #                                         on "model" (flash-decode style)
    cache_quant: bool = False               # int8 KV cache (per-token-head
    #                                         symmetric scales) — serving
    kv_cache_format: Optional[str] = None   # FormatPolicy for *paged* KV
    #                                         storage (serving engine): None
    #                                         keeps compute_dtype pages;
    #                                         int8pt (per-tensor scales, the
    #                                         quantized default) / int8 /
    #                                         bf16 / fp32 select the stored
    #                                         element width.
    decode_qkv_grouped: bool = False        # batch the decode-step q/k/v
    #                                         GEMVs as ONE grouped GEMM so
    #                                         the plan cache sees a single
    #                                         grouped signature per step
    #                                         instead of 3 GEMV launches
    use_graph: bool = True                  # execute the MLP block and the
    #                                         attention projections as
    #                                         compiled repro.graph programs
    #                                         (kernel backend): traced →
    #                                         fused → program-scheduled
    #                                         against the plan cache.
    #                                         False = eager per-GEMM
    #                                         dispatch (launchers expose
    #                                         --no-graph for debugging).

    def __post_init__(self):
        assert self.n_heads % self.n_kv_heads == 0
        if self.format_policy is not None:
            from repro.core.formats import FORMATS
            assert self.format_policy in FORMATS, (
                f"unknown format_policy {self.format_policy!r}; "
                f"known: {sorted(FORMATS)}")
        if self.kv_cache_format is not None:
            from repro.core.formats import FORMATS
            assert self.kv_cache_format in FORMATS, (
                f"unknown kv_cache_format {self.kv_cache_format!r}; "
                f"known: {sorted(FORMATS)}")
        for mixer, ffn in self.pattern:
            assert mixer in ("attn", "local", "rglru", "ssd"), mixer
            assert ffn in ("mlp", "moe", "none"), ffn
            if mixer == "local":
                assert self.window is not None, "local attention needs window"
            if ffn == "moe":
                assert self.moe is not None
        if self.moe is not None and self.moe.held is not None:
            assert 0 < self.moe.held <= self.moe.n_experts, self.moe

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        reps = -(-self.n_layers // self.period)
        return (self.pattern * reps)[: self.n_layers]

    @property
    def is_subquadratic(self) -> bool:
        """True when no layer needs O(S²) state/compute at decode."""
        return all(m != "attn" for m, _ in self.pattern)

    def cache_len(self, mixer: str, seq_len: int) -> int:
        if mixer == "local":
            return min(self.window, seq_len)
        return seq_len

    def n_params(self) -> int:
        """Approximate parameter count (for 6·N·D roofline bookkeeping)."""
        d, hd = self.d_model, self.hd
        total = self.vocab * d * (1 if self.tied_embeddings else 2)
        for mixer, ffn in self.layer_kinds:
            if mixer in ("attn", "local"):
                total += d * hd * (self.n_heads + 2 * self.n_kv_heads)
                total += self.n_heads * hd * d
            elif mixer == "rglru":
                w = (self.rglru.width or d)
                total += 2 * d * w + w * d           # gate/rec/out projections
                total += 2 * (w * w + w)             # wa, wx (+biases)
                total += self.rglru.conv_width * w + w + w  # conv + lam
            elif mixer == "ssd":
                di = self.ssm.expand * d
                nh = di // self.ssm.head_dim
                proj = 2 * di + 2 * self.ssm.d_state + nh
                total += d * proj + di * d
            if ffn == "mlp":
                k = 3 if self.mlp_type in ("swiglu", "geglu") else 2
                total += k * d * self.d_ff
            elif ffn == "moe":
                m = self.moe
                total += d * m.n_experts  # router
                total += (m.held or m.n_experts) * 3 * d * m.d_ff_expert
                total += 3 * d * m.d_ff_shared
        total += d  # final norm
        return total

    def n_active_params(self) -> int:
        """Active-per-token parameters (MoE: top-k experts only)."""
        if self.moe is None:
            return self.n_params()
        full = self.n_params()
        moe_layers = sum(1 for _, f in self.layer_kinds if f == "moe")
        held = self.moe.held or self.moe.n_experts
        all_e = moe_layers * held * 3 * self.d_model * self.moe.d_ff_expert
        act_e = moe_layers * self.moe.top_k * 3 * self.d_model * self.moe.d_ff_expert
        return full - all_e + act_e

    def reduced(self) -> "ArchConfig":
        """CPU smoke-test configuration of the same family."""
        kw = dict(
            n_layers=2 * self.period,
            d_model=128,
            n_heads=4,
            n_kv_heads=max(1, 4 * self.n_kv_heads // self.n_heads),
            head_dim=32,
            d_ff=256,
            vocab=512,
            window=16 if self.window else None,
            compute_dtype="float32",
            # Smoke tests validate numerics against f32 oracles, so the
            # production format policy is dropped with the bf16 compute
            # dtype; tests opt back in explicitly per case.
            format_policy=None,
            remat="none",
        )
        if self.moe:
            # capacity_factor = n_experts ⇒ capacity = T·k: zero drops even
            # under fully-unbalanced routing, so smoke tests are exact.
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_ff_expert=64,
                capacity_factor=4.0,
                held=None if self.moe.held is None else 4,
                d_ff_shared=64 if self.moe.d_ff_shared else 0)
        if self.ssm:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=16, chunk=8)
        if self.rglru:
            kw["rglru"] = dataclasses.replace(self.rglru, width=128)
        return dataclasses.replace(self, **kw)

    def draft(self, groups: int = 1, *,
              format_policy: Optional[str] = None) -> "ArchConfig":
        """Config for a truncated-depth speculative-decoding draft.

        Same widths and layer pattern, ``groups`` periods deep — pairs
        with ``models.model.draft_from`` which slices the target's
        scanned group params (zero extra memory).  ``format_policy``
        optionally runs the draft under a cheaper GEMM format than the
        target (e.g. an int8 draft under a bf16 target); the draft keeps
        its own plan-cache signatures either way since its layer count
        differs.
        """
        n_groups = self.n_layers // self.period if self.scan_layers else 0
        if not 0 < groups <= n_groups:
            raise ValueError(
                f"draft needs 1..{n_groups} scanned groups, got {groups}")
        return dataclasses.replace(
            self, name=f"{self.name}_draft{groups}",
            n_layers=groups * self.period, format_policy=format_policy)


# ---------------------------------------------------------------------------
# Assigned input shapes (LM family: seq_len × global_batch)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

ARCH_NAMES = [
    "recurrentgemma_9b", "qwen3_moe_235b", "granite_moe_1b",
    "musicgen_medium", "chameleon_34b", "gemma2_27b", "starcoder2_7b",
    "gemma_2b", "qwen15_4b", "mamba2_130m",
]

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    name = name.replace("-", "_")
    if name not in _REGISTRY:
        importlib.import_module(f"repro.configs.{name}")
    return _REGISTRY[name]


def input_specs(cfg: ArchConfig, shape: ShapeSpec):
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    train/prefill: token ids (B, S) — labels are shifted tokens, derived
    in-step.  With ``frontend_stub`` (musicgen/chameleon assignments say the
    modality frontend is a stub), the inputs are precomputed frame/patch
    embeddings (B, S, D) instead of ids.
    decode: one new token per sequence plus the position scalar; the KV /
    recurrent cache is a separate argument built by ``init_cache_specs``.
    """
    b, s = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        if cfg.frontend_stub:
            return {"embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                       jnp.bfloat16),
                    "targets": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    # decode: one token per sequence with a fixed-capacity cache
    if cfg.frontend_stub:
        return {"embeddings": jax.ShapeDtypeStruct((b, 1, cfg.d_model),
                                                   jnp.bfloat16),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}

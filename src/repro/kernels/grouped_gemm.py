"""Grouped (per-expert) GEMM Pallas kernel — MTE applied to MoE.

MoE expert GEMMs are the archetype of the paper's target workloads: many
*small, skinny* matrix products (e.g. qwen3-moe's 128 experts at
d_ff=1536, granite-moe's 32 experts at d_ff=512 — Fig. 7 categories I-III
shapes).  A rigid 128×128×128 schedule pads each expert's token slice up to
the MXU tile; the MTE geometry solver instead picks the block shape from
the *per-expert* capacity and hidden dims.

x: (G, C, K) — C tokens routed to each of G experts (capacity-based
routing); w: (G, K, N).  Grid (G, gm, gn, gk); the accumulator tile stays
in VMEM across the K loop, epilogue fused on the last step (activation for
the up-projection, none for the down-projection).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.core.geometry import BlockGeometry, cdiv

__all__ = ["grouped_gemm_pallas"]


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, k: int, bk: int,
            epilogue: Epilogue):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = x_ref[0]
    w = w_ref[0]
    if k % bk != 0:
        # Mask the K tail of BOTH operands (OOB padding may be NaN).
        rem = k - (nk - 1) * bk
        limit = jnp.where(ki == nk - 1, rem, bk)
        ka = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) < limit
        a = jnp.where(ka, a, jnp.zeros_like(a))
        kw = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) < limit
        w = jnp.where(kw, w, jnp.zeros_like(w))
    acc_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(ki == nk - 1)
    def _epi():
        o_ref[0] = epilogue.apply(acc_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("geom", "epilogue", "out_dtype", "acc_dtype",
                              "interpret"))
def grouped_gemm_pallas(x, w, *, geom: BlockGeometry,
                        epilogue: Epilogue = Epilogue(),
                        out_dtype=jnp.float32, acc_dtype=None,
                        interpret: bool):
    """Per-expert GEMM with the accumulator at the format policy's
    ``SEW_o`` (f32 by default, int32 for int8 operands, bf16 for the
    narrow-accumulator fast path)."""
    acc_dtype = (jnp.dtype(acc_dtype) if acc_dtype is not None
                 else (jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer)
                       else jnp.float32))
    g, cap, k = x.shape
    gw, kw, n = w.shape
    if gw != g or kw != k:
        raise ValueError(f"group shapes mismatch: {x.shape} x {w.shape}")

    bm = min(geom.bm, max(8, cdiv(cap, 8) * 8))
    bn = min(geom.bn, max(128, cdiv(n, 128) * 128))
    bk = min(geom.bk, max(8, cdiv(k, 8) * 8))
    gm, gn, gk = cdiv(cap, bm), cdiv(n, bn), cdiv(k, bk)

    kernel = functools.partial(_kernel, nk=gk, k=k, bk=bk, epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        grid=(g, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gi, i, j, ki: (gi, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, ki: (gi, ki, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, ki: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, cap, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(x, w)

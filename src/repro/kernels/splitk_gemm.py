"""Split-K GEMM — MTE's "vectorize all three loops" at the grid level.

The paper's point (ii): MTE vectorizes M, N **and K**, which is what keeps
small/skinny GEMMs efficient.  On TPU the analogue is split-K: when the
(M, N) grid cannot fill the machine (decode GEMVs, small-OC convolutions,
per-expert slices), the K loop is split across ``n_split`` grid slices,
each accumulating an f32 partial; a cheap reduction (+ the fused epilogue)
combines them.  The geometry solver (`solve_block_geometry`) decides when
``split_k > 1`` pays from the same capacity arithmetic as Formula 2/3.

Cost model (napkin): split-K adds ``n_split·M·N·4`` bytes of partial
round-trip but multiplies usable parallelism by ``n_split`` — profitable
whenever ``grid_mn < cores`` and ``K ≫ bk``, exactly the solver's rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.core.geometry import BlockGeometry, cdiv

__all__ = ["mte_gemm_splitk_pallas"]


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int, k: int, bk: int,
            k_per_split: int, masked: bool):
    si = pl.program_id(0)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if masked:
        # global K offset of this block; mask anything past the true K
        k_start = si * k_per_split + ki * bk
        limit = jnp.clip(k - k_start, 0, bk)
        ka = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) < limit
        # Mosaic cannot select on a packed (bf16/int8) block with fewer
        # rows than its sublane tile (decode M=4), so mask at 32 bits.
        wide = (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                else jnp.float32)
        a = jnp.where(ka, a.astype(wide), 0).astype(a.dtype)
        kb = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0) < limit
        b = jnp.where(kb, b, jnp.zeros_like(b))
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("geom", "n_split", "epilogue", "out_dtype",
                              "acc_dtype", "interpret"))
def mte_gemm_splitk_pallas(a, b, c=None, bias=None, *, geom: BlockGeometry,
                           n_split: int = 4,
                           epilogue: Epilogue = Epilogue(),
                           out_dtype=jnp.float32, acc_dtype=None,
                           interpret: bool):
    """``epilogue(a @ b [, c, bias])`` with the K loop split over
    ``n_split`` grid slices (partials in the format's accumulator dtype —
    f32 by default, int32 for quantized int8 operands — + final fused
    reduction; the β·C / bias terms join at the reduction, once, not per
    partial)."""
    acc_dtype = (jnp.dtype(acc_dtype) if acc_dtype is not None
                 else (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                       else jnp.float32))
    m, k = a.shape
    k2, n = b.shape
    if k2 != k:
        raise ValueError(f"contraction mismatch: {a.shape} x {b.shape}")
    if epilogue.needs_c_input and c is None:
        raise ValueError("epilogue.beta != 0 requires c operand")
    if epilogue.has_bias and bias is None:
        raise ValueError("epilogue.has_bias requires bias operand")

    bm = m if m <= geom.bm else geom.bm
    bn = min(geom.bn, max(128, cdiv(n, 128) * 128))
    bk = min(geom.bk, max(8, cdiv(k, 8) * 8))
    k_per_split = cdiv(cdiv(k, n_split), bk) * bk
    gk = cdiv(k_per_split, bk)
    gm, gn = cdiv(m, bm), cdiv(n, bn)

    kernel = functools.partial(_kernel, nk=gk, k=k, bk=bk,
                               k_per_split=k_per_split,
                               masked=n_split * k_per_split != k)
    partials = pl.pallas_call(
        kernel,
        grid=(n_split, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda s, i, j, ki, gk=gk: (i, s * gk + ki)),
            pl.BlockSpec((bk, bn),
                         lambda s, i, j, ki, gk=gk: (s * gk + ki, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, ki: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_split, m, n), acc_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(a, b)
    out = epilogue.apply(jnp.sum(partials, axis=0), c_in=c, bias=bias)
    return out.astype(out_dtype)

"""Rigid-ISA baseline GEMM — models AMX semantics on TPU (paper §II-D).

Two deliberate handicaps reproduce the two AMX defects the paper
identifies:

1. **Fixed geometry**: the block schedule is always 128×128×128 (the MXU
   analogue of AMX's immutable 16×16×SEW tile), so small / tall / skinny
   GEMMs pay full padding waste instead of adapting like the MTE solver.
2. **No matrix↔vector interplay**: the epilogue is *not* fused — the raw
   accumulator is written to HBM and a second element-wise kernel reads it
   back to apply α/β/bias/activation, reproducing AMX's round trip through
   memory to reach the AVX-512 registers (§II-C1).

Used by the efficiency benchmarks as the AMX stand-in and available as
``policy="amx"`` throughout the framework.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.core.geometry import BlockGeometry, cdiv
from repro.core.tile_state import SEW
from repro.kernels.mte_gemm import mte_gemm_pallas

__all__ = ["rigid_gemm_pallas", "epilogue_pass_pallas"]


def _epilogue_kernel(acc_ref, c_ref, bias_ref, o_ref, *, epilogue: Epilogue):
    acc = acc_ref[...]
    c_in = c_ref[...] if c_ref is not None else None
    bias = bias_ref[0] if bias_ref is not None else None
    o_ref[...] = epilogue.apply(acc, c_in=c_in, bias=bias).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("epilogue", "out_dtype", "interpret"))
def epilogue_pass_pallas(acc, c=None, bias=None, *,
                         epilogue: Epilogue = Epilogue(),
                         out_dtype=jnp.float32, interpret: bool):
    """Standalone element-wise epilogue pass (the AVX-512-through-memory leg)."""
    m, n = acc.shape
    bm = min(256, max(8, cdiv(m, 8) * 8))
    bn = min(512, max(128, cdiv(n, 128) * 128))

    in_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j))]
    operands = [acc]
    has_c, has_bias = c is not None, bias is not None
    if has_c:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
        operands.append(c)
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        operands.append(bias.reshape(1, -1))

    def kernel(*refs):
        a_ref = refs[0]
        idx = 1
        c_ref = refs[idx] if has_c else None
        idx += int(has_c)
        b_ref = refs[idx] if has_bias else None
        o_ref = refs[-1]
        _epilogue_kernel(a_ref, c_ref, b_ref, o_ref, epilogue=epilogue)

    return pl.pallas_call(
        kernel,
        grid=(cdiv(m, bm), cdiv(n, bn)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(*operands)


def rigid_gemm_pallas(a, b, c=None, bias=None, *,
                      epilogue: Epilogue = Epilogue(),
                      out_dtype=jnp.float32, interpret: bool):
    """AMX-semantics GEMM: fixed 128³ blocks + epilogue via HBM round trip."""
    sew_i = SEW.from_dtype(a.dtype)
    sew_o = SEW.from_dtype(out_dtype)
    geom = BlockGeometry(bm=128, bn=128, bk=128, split_k=1, n_acc=8,
                         transposed_b=False, sew_i=sew_i, sew_o=sew_o,
                         policy="amx")
    # Stage 1: bare MMA, raw f32 accumulator spilled to HBM.
    acc = mte_gemm_pallas(a, b, geom=geom, epilogue=Epilogue(),
                          out_dtype=jnp.float32, interpret=interpret)
    if epilogue.is_identity:
        return acc.astype(out_dtype)
    # Stage 2: reload and post-process (the memory round trip).
    return epilogue_pass_pallas(acc, c=c, bias=bias, epilogue=epilogue,
                                out_dtype=out_dtype, interpret=interpret)

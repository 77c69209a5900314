"""Pallas TPU kernel for the MTE geometry-agnostic GEMM (paper §III).

This is the TPU-native realization of the paper's `tfmul`/`tfwmul`
instructions plus the fused vector-mode epilogue:

- The block schedule (bm, bn, bk) comes from the MTE geometry solver
  (:func:`repro.core.geometry.solve_block_geometry`) — never hard-coded,
  exactly as MTE derives tile shapes from VLEN/RLEN/SEW instead of baking
  them into the ISA.
- The accumulator tile lives in VMEM scratch for the whole K loop (the
  vector-register-resident C tile of Algorithm 1) and the epilogue
  (α/β, bias broadcast, softcap, activation) is applied to it *in place*
  on the final K step — the paper's seamless matrix→vector transition with
  no memory round-trip.
- Mixed precision (`tfwmul`): SEW_i < SEW_o inputs accumulate into an f32
  (or int32) tile; the optional transposed-B layout of Formula 3 is a
  BlockSpec index-map change, not a data copy.
- Ragged edges: M/N raggedness is handled by Pallas' clipped block writes;
  K raggedness is masked in-kernel (the `tvmask` analogue) so padded
  garbage never contaminates real accumulator columns.

Grid: (gm, gn, gk) with K innermost (sequential accumulation); M/N dims
are parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.core.geometry import BlockGeometry, cdiv

__all__ = ["mte_gemm_pallas"]


def _acc_dtype(in_dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(in_dtype, jnp.integer) else jnp.float32


def _gemm_kernel(a_ref, b_ref, c_ref, bias_ref, o_ref, acc_ref, *,
                 nk: int, k: int, bk: int, epilogue: Epilogue,
                 b_transposed: bool):
    """One (m, n, k) grid step.  c_ref/bias_ref are None when unused."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if k % bk != 0:
        # K-tail masking (the tvmask analogue): zero out-of-range K slices
        # of BOTH operands on the last step — OOB-padded values (NaN under
        # interpret mode) must never reach the accumulator, and 0·NaN = NaN
        # so masking one side is not enough.
        rem = k - (nk - 1) * bk
        limit = jnp.where(ki == nk - 1, rem, bk)
        ka = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) < limit
        a = jnp.where(ka, a, jnp.zeros_like(a))
        k_dim_b = 1 if b_transposed else 0
        kb = jax.lax.broadcasted_iota(jnp.int32, b.shape, k_dim_b) < limit
        b = jnp.where(kb, b, jnp.zeros_like(b))
    if b_transposed:
        # Formula 3 layout: the b block is (bn, bk), contract on dim 1 both.
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_ref.dtype)
    else:
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_ref.dtype)

    @pl.when(ki == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        c_in = c_ref[...] if c_ref is not None else None
        bias = bias_ref[0] if bias_ref is not None else None
        out = epilogue.apply(acc, c_in=c_in, bias=bias)
        o_ref[...] = out.astype(o_ref.dtype)


def _bind_kernel(has_c: bool, has_bias: bool):
    """Adapt the kernel signature to the optional-operand combination."""
    if has_c and has_bias:
        return _gemm_kernel
    if has_c:
        def k_c(a_ref, b_ref, c_ref, o_ref, acc_ref, **kw):
            return _gemm_kernel(a_ref, b_ref, c_ref, None, o_ref, acc_ref, **kw)
        return k_c
    if has_bias:
        def k_b(a_ref, b_ref, bias_ref, o_ref, acc_ref, **kw):
            return _gemm_kernel(a_ref, b_ref, None, bias_ref, o_ref, acc_ref, **kw)
        return k_b

    def k_n(a_ref, b_ref, o_ref, acc_ref, **kw):
        return _gemm_kernel(a_ref, b_ref, None, None, o_ref, acc_ref, **kw)
    return k_n


def _clip_block(block: int, dim: int) -> int:
    """Clamp a solved block dim to the (8-aligned) problem dim."""
    return min(block, max(8, cdiv(dim, 8) * 8))


@functools.partial(
    jax.jit,
    static_argnames=("geom", "epilogue", "out_dtype", "acc_dtype",
                     "interpret"))
def mte_gemm_pallas(a, b, c=None, bias=None, *, geom: BlockGeometry,
                    epilogue: Epilogue = Epilogue(),
                    out_dtype=jnp.float32, acc_dtype=None,
                    interpret: bool):
    """``epilogue(a @ b [, c, bias])`` with an MTE-solved block schedule.

    a: (M, K); b: (K, N), or (N, K) when ``geom.transposed_b`` (Formula 3
    col-major B).  bias: (N,) row bias.  Output: (M, N) in ``out_dtype``;
    accumulation runs at ``acc_dtype`` — the format policy's ``SEW_o``
    (f32/int32 by default, bf16 for the narrow-accumulator fast path).
    """
    acc_dtype = jnp.dtype(acc_dtype) if acc_dtype is not None \
        else _acc_dtype(a.dtype)
    m, k = a.shape
    n, kb = (b.shape if geom.transposed_b else b.shape[::-1])
    if kb != k:
        raise ValueError(f"contraction mismatch: {a.shape} x {b.shape}")
    if epilogue.needs_c_input and c is None:
        raise ValueError("epilogue.beta != 0 requires c operand")
    if epilogue.has_bias and bias is None:
        raise ValueError("epilogue.has_bias requires bias operand")
    if epilogue.has_bias and epilogue.bias_axis != "row":
        raise NotImplementedError("kernel bias fusion supports row bias only")

    bm, bn, bk = (_clip_block(geom.bm, m), _clip_block(geom.bn, n),
                  _clip_block(geom.bk, k))
    gm, gn, gk = cdiv(m, bm), cdiv(n, bn), cdiv(k, bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
        (pl.BlockSpec((bn, bk), lambda i, j, ki: (j, ki))
         if geom.transposed_b else
         pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j))),
    ]
    operands = [a, b]
    if c is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)))
        operands.append(c)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)))
        operands.append(bias.reshape(1, -1))

    kernel = functools.partial(
        _bind_kernel(c is not None, bias is not None),
        nk=gk, k=k, bk=bk, epilogue=epilogue,
        b_transposed=geom.transposed_b)

    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(*operands)

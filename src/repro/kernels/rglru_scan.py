"""RG-LRU linear-recurrence Pallas kernel (Griffin/recurrentgemma).

The gated linear recurrence ``h_t = a_t · h_{t-1} + b_t`` is the paper's
"vector processing mode" workload — pure element-wise math on
register-resident data.  This kernel streams (a, b) through VMEM in
sequence chunks with the hidden state as a grid-carried scratch
accumulator: grid (B, S/bt) with the sequence axis sequential, the chunk
recurrence unrolled inside the kernel (bt element-wise FMAs on VREGs —
long-vector execution exactly as §IV-A2 describes for non-GEMM work).

Used on the serving path (prefill); training keeps the associative-scan
formulation (log-depth, autodiff-native).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import cdiv

__all__ = ["rglru_scan_pallas"]


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bt: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[0]
    a = a_ref[0]
    b = b_ref[0]
    for t in range(bt):  # unrolled chunk recurrence (element-wise FMAs)
        h = a[t] * h + b[t]
        o_ref[0, t] = h
    h_ref[...] = jnp.broadcast_to(h, h_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rglru_scan_pallas(a, b, *, block_t: int = 64, interpret: bool):
    """h_t = a_t·h_{t-1} + b_t along axis 1.  a, b: (B, S, W) f32."""
    bsz, s, w = a.shape
    bt = min(block_t, s)
    gs = cdiv(s, bt)
    pad = gs * bt - s
    if pad:
        # identity steps: a=1, b=0 leave the carry untouched
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(bsz, gs),
        in_specs=[
            pl.BlockSpec((1, bt, w), lambda i, si: (i, si, 0)),
            pl.BlockSpec((1, bt, w), lambda i, si: (i, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, w), lambda i, si: (i, si, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, gs * bt, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((8, w), a.dtype)],
        interpret=interpret,
    )(a, b)
    return out[:, :s]

"""Pallas TPU kernel for the RG-LRU linear recurrence h_t = a_t h_{t-1} + b_t.

Grid: (batch, d/blk_d, S/blk_s) with the sequence axis "arbitrary"
(sequential): the carry h lives in VMEM scratch across sequence blocks, and
within a block the recurrence unrolls with a fori_loop over VREG rows. The
channel axis is the lane dimension (blk_d a multiple of 128), so each step is
a pure VPU axpy — this is the TPU-native shape of the GPU "linear scan"
kernels used by Griffin-style models (HBM traffic = one read of a,b + one
write of h; arithmetic intensity ~1 FLOP/byte, i.e. purely memory-bound,
which the roofline table confirms).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["rglru_scan"]


def _kernel(a_ref, b_ref, h_ref, carry, *, blk_s: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    a = a_ref[0].astype(jnp.float32)  # (blk_s, blk_d)
    b = b_ref[0].astype(jnp.float32)

    def step(i, h):
        h = a[i] * h + b[i]
        h_ref[0, i, :] = h.astype(h_ref.dtype)
        return h

    carry[...] = jax.lax.fori_loop(0, blk_s, step, carry[...])


def rglru_scan(a: jax.Array, b: jax.Array, *, blk_s: int = 256, blk_d: int = 256,
               interpret: bool = False) -> jax.Array:
    """a, b: (B, S, D) -> h: (B, S, D) with h_t = a_t h_{t-1} + b_t, h_0 = b_0."""
    B, S, D = a.shape
    blk_s, blk_d = min(blk_s, S), min(blk_d, D)
    assert S % blk_s == 0 and D % blk_d == 0, "wrapper must pad"
    kern = functools.partial(_kernel, blk_s=blk_s)
    return pl.pallas_call(
        kern,
        grid=(B, D // blk_d, S // blk_s),
        in_specs=[
            pl.BlockSpec((1, blk_s, blk_d), lambda bb, dd, ss: (bb, ss, dd)),
            pl.BlockSpec((1, blk_s, blk_d), lambda bb, dd, ss: (bb, ss, dd)),
        ],
        out_specs=pl.BlockSpec((1, blk_s, blk_d), lambda bb, dd, ss: (bb, ss, dd)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((blk_d,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)

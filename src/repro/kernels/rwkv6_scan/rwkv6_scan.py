"""Pallas TPU kernel for the RWKV-6 WKV recurrence.

Per (batch, head): state S in R^{n x n} (n = head_dim = 64 for the assigned
config; 16 KiB fp32 — comfortably VMEM-resident). Grid:
(batch, heads, S/blk_s) with the time axis "arbitrary"; the state carries in
VMEM scratch across time blocks, and a fori_loop walks the steps inside a
block:

    out_t = r_t (S + diag(u) k_t^T v_t)
    S     = diag(w_t) S + k_t^T v_t

Each step is two rank-1 outer products + one (1 x n) @ (n x n) matvec — VPU
work with the n x n state held in registers/VMEM, never touching HBM. HBM
traffic is one read of r/k/v/w and one write of out: like the RG-LRU scan
this is purely memory-bound, the structural reason RWKV decode beats
attention at long context.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["wkv_scan"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, S_scr, *, blk_s: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _():
        S_scr[...] = jnp.zeros_like(S_scr)

    r = r_ref[0, :, 0].astype(jnp.float32)  # (blk_s, n)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    w = w_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)        # (n,)

    def step(i, S):
        kv = jnp.outer(k[i], v[i])                    # (n, n)
        out = r[i] @ (S + u[:, None] * kv)            # (n,)
        o_ref[0, i, 0, :] = out.astype(o_ref.dtype)
        return w[i][:, None] * S + kv

    S_scr[...] = jax.lax.fori_loop(0, blk_s, step, S_scr[...])


def wkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, *, blk_s: int = 128, interpret: bool = False) -> jax.Array:
    """r,k,v,w: (B, S, H, n); u: (H, n). Returns out (B, S, H, n)."""
    B, S, H, n = r.shape
    blk_s = min(blk_s, S)
    assert S % blk_s == 0, "wrapper must pad"
    kern = functools.partial(_kernel, blk_s=blk_s)
    spec = pl.BlockSpec((1, blk_s, 1, n), lambda bb, hh, ss: (bb, ss, hh, 0))
    return pl.pallas_call(
        kern,
        grid=(B, H, S // blk_s),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, n), lambda bb, hh, ss: (hh, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, n), r.dtype),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)

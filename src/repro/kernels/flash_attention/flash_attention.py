"""Pallas TPU flash attention (causal / sliding-window), online-softmax form.

Grid: (batch*heads, n_q_blocks, n_k_blocks) with the k axis "arbitrary"
(sequential) so the running max / denominator / accumulator live in VMEM
scratch across k blocks. Block shapes (blk_q, head_dim) / (blk_k, head_dim)
— head_dim is kept whole (<=256 for the assigned archs) so each MXU matmul
is (blk_q x head_dim) @ (head_dim x blk_k), lane-dim 128-aligned.

Causality/window are enforced two ways:
  * block-level: fully-masked k blocks are skipped (no compute, no loads of
    the probs path) via pl.when on the block indices;
  * element-level: an iota-based mask inside partially-masked blocks.

GQA is handled by the ops.py wrapper (kv heads are expanded logically via an
index map — no materialized repeat_kv copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            blk_q: int, blk_k: int, sm_scale: float, causal: bool, window: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = kj * blk_k

    # block-level skip: in causal mode k block strictly after q block's end;
    # in window mode k block strictly before the band.
    live = True
    if causal:
        live = k_start <= q_start + blk_q - 1
    if window:
        live = live & (k_start + blk_k - 1 >= q_start - window + 1)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32)          # (blk_q, dh)
        k = k_ref[0].astype(jnp.float32)          # (blk_k, dh)
        s = (q @ k.T) * sm_scale                   # (blk_q, blk_k)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v_ref[0].astype(jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, dh); k, v: (BH, Sk, dh). Returns (BH, Sq, dh).

    BH is the flattened batch*query-heads axis; the wrapper maps GQA kv heads
    into the same BH indexing via its own reshape/index plan.
    """
    bh, sq, dh = q.shape
    _, sk, _ = k.shape
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    assert sq % blk_q == 0 and sk % blk_k == 0, "wrapper must pad seq lens"
    sm_scale = 1.0 / (dh ** 0.5)

    kern = functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k,
                             sm_scale=sm_scale, causal=causal, window=window)
    return pl.pallas_call(
        kern,
        grid=(bh, sq // blk_q, sk // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)

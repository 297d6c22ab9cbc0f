"""Pallas TPU flash attention (causal / sliding-window / GQA).

Grid: (batch, q_heads, q_blocks, kv_blocks) — the kv axis is minor-most, so
the f32 accumulator/max/denominator scratch persists across kv iterations of
one q block (the classic TPU flash pattern).  BlockSpecs stage one
(q_block, head_dim) query tile and one (kv_block, head_dim) key/value tile
into VMEM per step; GQA maps q-head h to kv-head h // group in the index map
so repeated K/V are never materialised.

Masked-out kv blocks (beyond the causal frontier or outside the sliding
window) skip their compute via ``pl.when`` — on hardware those grid steps
cost only the (prefetch-overlapped) DMA, giving the ~2x causal saving.

Validated in interpret mode against ``ref.attention_ref`` (CPU has no
Mosaic backend; see tests/test_kernels_pallas.py) and compiled for a
described v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int], offset: int,
            q_blk: int, kv_blk: int, n_kv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = offset + i * q_blk                 # absolute position of q row 0
    kv_lo = j * kv_blk
    relevant = True
    if causal:
        relevant = jnp.asarray(kv_lo <= q_lo + q_blk - 1)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, kv_lo + kv_blk - 1 > q_lo - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (q_blk, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (kv_blk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale       # (q_blk, kv_blk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        kpos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mask = jnp.ones_like(logits, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           window: Optional[int] = None, offset: int = 0,
                           scale: Optional[float] = None,
                           q_blk: int = 256, kv_blk: int = 256,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    ``interpret`` defaults to True exactly when the default backend is not
    a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    q_blk = min(q_blk, sq)
    kv_blk = min(kv_blk, skv)
    assert sq % q_blk == 0 and skv % kv_blk == 0
    n_q, n_kv = sq // q_blk, skv // kv_blk
    grid = (b, h, n_q, n_kv)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, offset=offset,
        q_blk=q_blk, kv_blk=kv_blk, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, kv_blk, d),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, kv_blk, d),
                         lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, d), jnp.float32),   # acc
            pltpu.VMEM((q_blk,), jnp.float32),     # running max
            pltpu.VMEM((q_blk,), jnp.float32),     # running denom
        ],
        interpret=interpret,
    )(q, k, v)

"""Pallas TPU decode attention (flash-decoding style).

One new query token per sequence attends to a long KV cache.  Grid:
(batch, kv_heads, kv_blocks) with the per-head query *group* (GQA) kept
resident in VMEM scratch; kv blocks stream through VMEM with an online
softmax.  ``length`` masks the valid cache prefix; ``window`` implements the
ring-buffer sliding-window case (every slot < length valid — see
layers.attn_decode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, kv_blk: int, n_kv: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    kv_lo = j * kv_blk

    @pl.when(kv_lo < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (kv_blk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale       # (G, kv_blk)
        kpos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(kpos < length, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            length: Optional[jax.Array] = None,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            kv_blk: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, Hkv, S, D); length: (B,) int32 -> (B, Hq, D).

    With a ring-buffer window cache (S == window), all slots < length are
    valid, so the same masking applies.  ``length`` is a scalar-prefetch
    operand (SMEM, whole array): Mosaic refuses a rank-1 (1,) block of it.
    ``interpret`` defaults to True exactly when the default backend is not
    a TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    _, hkv, s, _ = k.shape
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    kv_blk = min(kv_blk, s)
    assert s % kv_blk == 0
    n_kv = s // kv_blk
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    qg = q.reshape(b, hkv, g, d)
    grid = (b, hkv, n_kv)
    kernel = functools.partial(_kernel, scale=scale, kv_blk=kv_blk, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda b_, h_, j, ln: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, kv_blk, d),
                             lambda b_, h_, j, ln: (b_, h_, j, 0)),
                pl.BlockSpec((1, 1, kv_blk, d),
                             lambda b_, h_, j, ln: (b_, h_, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, h_, j, ln: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), qg, k, v)
    return out.reshape(b, h, d)

"""Pallas TPU fused RMSNorm.

Row-tiled: each grid step normalises a (rows_blk, d) tile in VMEM — one HBM
read and one write per element (the unfused jnp path reads x twice: once for
the variance, once for the scale-multiply).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
                   rows_blk: int = 256,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Row-tiled RMSNorm over the last axis.  ``interpret`` defaults to True
    exactly when the default backend is not a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    rows_blk = min(rows_blk, rows)
    # pad rows to a multiple of the block
    pad = (-rows) % rows_blk
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = ((rows + pad) // rows_blk,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows_blk, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rows_blk, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)

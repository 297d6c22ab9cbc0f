"""Pallas TPU selective-scan (Mamba) kernel.

Grid: (batch, d_inner blocks).  Each grid step keeps its (d_blk, N) state
resident in VMEM and walks the time axis with ``fori_loop``, fusing the
discretisation (exp(dt*A)), state update and C-projection — the HBM traffic
is exactly one read of u/dt/B/C and one write of y (the jnp fallback
materialises (B, T, d, N) discretised terms or re-reads per chunk).

TPU adaptation note: the CUDA kernel in the Mamba paper tiles over
threadblocks with warp shuffles for the chunk-carry; on TPU the carry lives
in VMEM scratch across sequential time steps of one grid cell instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref,
            y_ref, hT_ref, h_scr, *, t_len: int):
    h_scr[...] = h0_ref[0].astype(jnp.float32)          # (d_blk, N)
    A = A_ref[...].astype(jnp.float32)                  # (d_blk, N)
    D = D_ref[0].astype(jnp.float32)                    # (d_blk,)

    def step(t, _):
        u_t = u_ref[0, t].astype(jnp.float32)           # (d_blk,)
        dt_t = dt_ref[0, t].astype(jnp.float32)         # (d_blk,)
        b_t = B_ref[0, t].astype(jnp.float32)           # (N,)
        c_t = C_ref[0, t].astype(jnp.float32)           # (N,)
        da = jnp.exp(dt_t[:, None] * A)                 # (d_blk, N)
        db = dt_t[:, None] * b_t[None, :]
        h = da * h_scr[...] + db * u_t[:, None]
        h_scr[...] = h
        y_ref[0, t, :] = (h @ c_t + D * u_t).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, t_len, step, ())
    hT_ref[0, :, :] = h_scr[...].astype(hT_ref.dtype)


def mamba_scan_pallas(u: jax.Array, dt: jax.Array, A: jax.Array,
                      B: jax.Array, C: jax.Array, D: jax.Array,
                      h0: Optional[jax.Array] = None,
                      d_blk: int = 256, interpret: Optional[bool] = None):
    """Shapes as ref.mamba_scan_ref. Returns (y, h_T).

    ``D`` travels as a (1, d_in) row tiled in (1, d_blk) blocks: a rank-1
    block gives Mosaic a layout it refuses.  The kernel reads and writes
    float32 (it computes in float32 anyway): the time loop touches one row
    at a dynamic offset, which Mosaic allows for 32-bit rows but not for
    packed bfloat16 ones; ``y`` is cast back to ``u.dtype`` after.
    ``interpret`` defaults to True exactly when the default backend is not
    a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bt, t, d_in = u.shape
    n = A.shape[1]
    d_blk = min(d_blk, d_in)
    assert d_in % d_blk == 0
    n_d = d_in // d_blk
    if h0 is None:
        h0 = jnp.zeros((bt, d_in, n), jnp.float32)
    out_dtype = u.dtype
    u, dt, B, C = (a.astype(jnp.float32) for a in (u, dt, B, C))
    grid = (bt, n_d)
    kernel = functools.partial(_kernel, t_len=t)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, d_blk), lambda b_, i: (b_, 0, i)),   # u
            pl.BlockSpec((1, t, d_blk), lambda b_, i: (b_, 0, i)),   # dt
            pl.BlockSpec((d_blk, n), lambda b_, i: (i, 0)),          # A
            pl.BlockSpec((1, t, n), lambda b_, i: (b_, 0, 0)),       # B
            pl.BlockSpec((1, t, n), lambda b_, i: (b_, 0, 0)),       # C
            pl.BlockSpec((1, d_blk), lambda b_, i: (0, i)),          # D
            pl.BlockSpec((1, d_blk, n), lambda b_, i: (b_, i, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((1, t, d_blk), lambda b_, i: (b_, 0, i)),   # y
            pl.BlockSpec((1, d_blk, n), lambda b_, i: (b_, i, 0)),   # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, t, d_in), jnp.float32),
            jax.ShapeDtypeStruct((bt, d_in, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_blk, n), jnp.float32)],
        interpret=interpret,
    )(u, dt, A, B, C, D.reshape(1, d_in), h0)
    return y.astype(out_dtype), hT

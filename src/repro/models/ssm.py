"""State-space and recurrent mixers: Mamba (Jamba) and xLSTM (sLSTM+mLSTM).

Training paths are *cost-transparent*: chunked python loops + associative
scans rather than long `lax.scan`s, so `cost_analysis` on the compiled step
counts the real work (see kernels/ops.py docstring).  The one exception is
sLSTM, whose stabilised recurrence is not associative — it uses `lax.scan`
over time and the roofline pipeline adds an analytic correction
(benchmarks/roofline.py).

Decode paths are single-step state updates (O(1) per token — these mixers are
the reason the `long_500k` cell is runnable for xLSTM/Jamba).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ModelConfig
from .module import dense_init
from .layers import rmsnorm, rmsnorm_init


# --------------------------------------------------------------------------
# Causal depthwise conv (shared by mamba / mLSTM)
# --------------------------------------------------------------------------

def _causal_conv(x, w, state=None):
    """x: (B, S, C); w: (C, K) depthwise. state: (B, K-1, C) history or None.
    Returns (y (B,S,C), new_state)."""
    b, s, c = x.shape
    k = w.shape[1]
    hist = jnp.zeros((b, k - 1, c), x.dtype) if state is None else state
    xp = jnp.concatenate([hist.astype(x.dtype), x], axis=1)
    cols = [xp[:, i:i + s] for i in range(k)]                  # K shifted views
    y = sum(cols[i] * w[:, i] for i in range(k))
    return y, xp[:, -(k - 1):] if k > 1 else jnp.zeros((b, 0, c), x.dtype)


def _conv_step(x, w, state):
    """x: (B, C); state: (B, K-1, C). Returns (y (B,C), new_state)."""
    k = w.shape[1]
    xp = jnp.concatenate([state.astype(x.dtype), x[:, None]], axis=1)  # (B,K,C)
    y = jnp.einsum("bkc,ck->bc", xp, w)
    return y, xp[:, 1:]


# --------------------------------------------------------------------------
# Mamba
# --------------------------------------------------------------------------

def mamba_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype=dtype),
        "conv": (jax.random.normal(ks[1], (di, cfg.ssm_conv), jnp.float32)
                 * (cfg.ssm_conv ** -0.5)).astype(dtype),
        "x_proj": dense_init(ks[2], di, r + 2 * n, dtype=dtype),
        "dt_proj": dense_init(ks[3], r, di, scale=r ** -0.5, dtype=dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                  (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[4], di, d,
                               scale=di ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                               dtype=dtype),
    }


def _mamba_core(p, xc, z, cfg, h0=None):
    """xc: (B,S,di) post-conv activations; z: gate. Returns (y, h_last)."""
    r, n = cfg.dt_rank, cfg.ssm_state
    proj = xc @ p["x_proj"]                                     # (B,S,r+2n)
    dt_r, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_last = ops.mamba_scan(xc, dt, A, Bm, Cm, p["D"], h0=h0)
    return y * jax.nn.silu(z), h_last


def mamba_apply(p, x, cfg: ModelConfig) -> jax.Array:
    xz = x @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, _ = _causal_conv(xin, p["conv"])
    xc = jax.nn.silu(xc)
    y, _ = _mamba_core(p, xc, z, cfg)
    return y @ p["out_proj"]


def mamba_make_cache(cfg: ModelConfig, batch: int, dtype):
    di, n, k = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {"conv": jnp.zeros((batch, k - 1, di), dtype),
            "h": jnp.zeros((batch, di, n), jnp.float32)}


def mamba_decode(p, x, cache, cfg: ModelConfig):
    """x: (B, D). Returns (out (B, D), new_cache)."""
    xz = x @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, conv_state = _conv_step(xin, p["conv"], cache["conv"])
    xc = jax.nn.silu(xc)
    r, n = cfg.dt_rank, cfg.ssm_state
    proj = xc @ p["x_proj"]
    dt_r, Bm, Cm = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h = ops.mamba_step(xc, dt, A, Bm, Cm, p["D"], cache["h"])
    out = (y * jax.nn.silu(z)) @ p["out_proj"]
    return out, {"conv": conv_state, "h": h}


# --------------------------------------------------------------------------
# mLSTM (matrix-memory LSTM with exponential gating), chunkwise-parallel
# --------------------------------------------------------------------------

def mlstm_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.n_heads
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype=dtype),
        "conv": (jax.random.normal(ks[1], (di, cfg.ssm_conv), jnp.float32)
                 * (cfg.ssm_conv ** -0.5)).astype(dtype),
        "wq": dense_init(ks[2], di, di, dtype=dtype),
        "wk": dense_init(ks[3], di, di, dtype=dtype),
        "wv": dense_init(ks[4], di, di, dtype=dtype),
        "w_gates": dense_init(ks[5], d, 2 * h, scale=0.02, dtype=jnp.float32),
        "gate_bias": jnp.concatenate(
            [jnp.linspace(3.0, 6.0, h), jnp.zeros(h)]),  # forget bias high
        "norm": rmsnorm_init(cfg.d_inner),
        "out_proj": dense_init(ks[6], di, d,
                               scale=di ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                               dtype=dtype),
    }


def _mlstm_chunk(q, k, v, logf, logi, state):
    """One chunk of the stabilised mLSTM recurrence.

    q/k/v: (B, H, W, dh); logf/logi: (B, H, W); state = (C (B,H,dh,dh),
    n (B,H,dh), m (B,H)).  Returns (h (B,H,W,dh), new_state).
    """
    b, hh, w, dh = q.shape
    C0, n0, m0 = state
    F = jnp.cumsum(logf, axis=-1)                              # (B,H,W)
    # log-weights of key j for query i (j <= i):  F_i - F_j + logi_j
    lw = F[..., :, None] - F[..., None, :] + logi[..., None, :]
    mask = jnp.tril(jnp.ones((w, w), bool))
    lw = jnp.where(mask, lw, -jnp.inf)
    inter_lw = m0[..., None] + F                               # (B,H,W)
    m = jnp.maximum(jnp.max(lw, axis=-1), inter_lw)            # (B,H,W)
    m = jnp.maximum(m, -1e30)
    dec = jnp.exp(lw - m[..., None])                           # (B,H,W,W)
    inter = jnp.exp(inter_lw - m)                              # (B,H,W)
    scale = dh ** -0.5
    scores = jnp.einsum("bhwd,bhud->bhwu", q, k) * scale * dec
    h_intra = jnp.einsum("bhwu,bhud->bhwd", scores, v)
    h_inter = inter[..., None] * jnp.einsum("bhij,bhwj->bhwi", C0, q) * scale
    n_i = jnp.einsum("bhwu,bhud->bhwd", dec, k) \
        + inter[..., None] * n0[..., None, :].repeat(w, axis=-2)
    denom = jnp.maximum(
        jnp.abs(jnp.einsum("bhwd,bhwd->bhw", n_i, q) * scale),
        jnp.exp(-m))
    h = (h_intra + h_inter) / denom[..., None]
    # chunk-end state
    Fw = F[..., -1]                                            # (B,H)
    lw_end = Fw[..., None] - F + logi                          # (B,H,W)
    m_end = jnp.maximum(m0 + Fw, jnp.max(lw_end, axis=-1))
    wgt = jnp.exp(lw_end - m_end[..., None])
    carry = jnp.exp(m0 + Fw - m_end)
    C1 = carry[..., None, None] * C0 + jnp.einsum(
        "bhw,bhwd,bhwe->bhde", wgt, v, k)
    n1 = carry[..., None] * n0 + jnp.einsum("bhw,bhwd->bhd", wgt, k)
    return h, (C1, n1, m_end)


def mlstm_apply(p, x, cfg: ModelConfig) -> jax.Array:
    b, s, d = x.shape
    hh = cfg.n_heads
    di = cfg.d_inner
    dh = di // hh
    xz = x @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, _ = _causal_conv(xin, p["conv"])
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"]).reshape(b, s, hh, dh).transpose(0, 2, 1, 3)
    k = (xc @ p["wk"]).reshape(b, s, hh, dh).transpose(0, 2, 1, 3)
    v = (xin @ p["wv"]).reshape(b, s, hh, dh).transpose(0, 2, 1, 3)
    gates = x.astype(jnp.float32) @ p["w_gates"] + p["gate_bias"]
    logf = jax.nn.log_sigmoid(gates[..., :hh]).transpose(0, 2, 1)
    logi = gates[..., hh:].transpose(0, 2, 1)                  # (B,H,S)
    # adaptive chunk: cap the unrolled python loop at 32 chunks so 32k+
    # sequences stay compile-tractable (intra-chunk work is quadratic in w,
    # still tiny vs the projections at these widths)
    w = min(max(cfg.lstm_chunk, s // 32), s)
    assert s % w == 0
    state = (jnp.zeros((b, hh, dh, dh), jnp.float32),
             jnp.zeros((b, hh, dh), jnp.float32),
             jnp.full((b, hh), -1e30, jnp.float32))
    hs = []
    for c0 in range(0, s, w):                  # static chunk loop
        hc, state = _mlstm_chunk(
            q[:, :, c0:c0 + w].astype(jnp.float32),
            k[:, :, c0:c0 + w].astype(jnp.float32),
            v[:, :, c0:c0 + w].astype(jnp.float32),
            logf[:, :, c0:c0 + w], logi[:, :, c0:c0 + w], state)
        hs.append(hc)
    h = jnp.concatenate(hs, axis=2).transpose(0, 2, 1, 3).reshape(b, s, di)
    h = rmsnorm(h.astype(x.dtype), p["norm"], cfg.norm_eps)
    return (h * jax.nn.silu(z)) @ p["out_proj"]


def mlstm_make_cache(cfg: ModelConfig, batch: int, dtype):
    hh = cfg.n_heads
    dh = cfg.d_inner // hh
    return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
            "C": jnp.zeros((batch, hh, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, hh, dh), jnp.float32),
            "m": jnp.full((batch, hh), -1e30, jnp.float32)}


def mlstm_decode(p, x, cache, cfg: ModelConfig):
    b, d = x.shape
    hh = cfg.n_heads
    di = cfg.d_inner
    dh = di // hh
    xz = x @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, conv_state = _conv_step(xin, p["conv"], cache["conv"])
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"]).reshape(b, hh, dh).astype(jnp.float32)
    k = (xc @ p["wk"]).reshape(b, hh, dh).astype(jnp.float32)
    v = (xin @ p["wv"]).reshape(b, hh, dh).astype(jnp.float32)
    gates = x.astype(jnp.float32) @ p["w_gates"] + p["gate_bias"]
    logf = jax.nn.log_sigmoid(gates[..., :hh])
    logi = gates[..., hh:]
    m = jnp.maximum(logf + cache["m"], logi)
    fc = jnp.exp(logf + cache["m"] - m)
    ic = jnp.exp(logi - m)
    scale = dh ** -0.5
    C = fc[..., None, None] * cache["C"] + ic[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", v, k)
    n = fc[..., None] * cache["n"] + ic[..., None] * k
    num = jnp.einsum("bhde,bhe->bhd", C, q) * scale
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q) * scale),
                      jnp.exp(-m))
    h = (num / den[..., None]).reshape(b, di)
    h = rmsnorm(h.astype(x.dtype), p["norm"], cfg.norm_eps)
    out = (h * jax.nn.silu(z)) @ p["out_proj"]
    return out, {"conv": conv_state, "C": C, "n": n, "m": m}


# --------------------------------------------------------------------------
# sLSTM (scalar-memory LSTM with exponential gating + recurrent weights)
# --------------------------------------------------------------------------

def slstm_init(key, cfg: ModelConfig, dtype) -> Dict:
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 3)
    return {
        "w": dense_init(ks[0], d, 4 * d, dtype=dtype),         # i,f,z,o
        "r": (jax.random.normal(ks[1], (4, h, dh, dh), jnp.float32)
              * dh ** -0.5).astype(dtype),
        "b": jnp.concatenate([jnp.zeros(d), jnp.full(d, 3.0),
                              jnp.zeros(2 * d)]),
        "out_proj": dense_init(ks[2], d, d,
                               scale=d ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                               dtype=dtype),
    }


def _slstm_cell(p, wx_t, state, cfg: ModelConfig):
    """wx_t: (B, 4D) precomputed input contribution; state=(h,c,n,m)."""
    h_prev, c_prev, n_prev, m_prev = state
    b, d = h_prev.shape
    hh = cfg.n_heads
    dh = d // hh
    hp = h_prev.reshape(b, hh, dh)
    rec = jnp.einsum("bhd,ghde->bghe", hp.astype(jnp.float32),
                     p["r"].astype(jnp.float32)).reshape(b, 4 * d)
    g = wx_t.astype(jnp.float32) + rec + p["b"]
    gi, gf, gz, go = jnp.split(g, 4, axis=-1)
    m = jnp.maximum(jax.nn.log_sigmoid(gf) + m_prev, gi)
    i = jnp.exp(gi - m)
    f = jnp.exp(jax.nn.log_sigmoid(gf) + m_prev - m)
    c = f * c_prev + i * jnp.tanh(gz)
    n = f * n_prev + i
    h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1e-6)
    return h, (h, c, n, m)


def slstm_apply(p, x, cfg: ModelConfig) -> jax.Array:
    """Sequential scan over time (non-associative recurrence)."""
    b, s, d = x.shape
    wx = x @ p["w"]                                            # (B,S,4D)
    state = tuple(jnp.zeros((b, d), jnp.float32) for _ in range(3)) + \
        (jnp.full((b, d), 0.0, jnp.float32),)

    def step(st, wx_t):
        h, st2 = _slstm_cell(p, wx_t, st, cfg)
        return st2, h

    _, hs = jax.lax.scan(step, state, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    return h @ p["out_proj"]


def slstm_make_cache(cfg: ModelConfig, batch: int, dtype):
    d = cfg.d_model
    return {"h": jnp.zeros((batch, d), jnp.float32),
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.zeros((batch, d), jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32)}


def slstm_decode(p, x, cache, cfg: ModelConfig):
    wx = x @ p["w"]
    h, (h2, c, n, m) = _slstm_cell(
        p, wx, (cache["h"], cache["c"], cache["n"], cache["m"]), cfg)
    out = h.astype(x.dtype) @ p["out_proj"]
    return out, {"h": h2, "c": c, "n": n, "m": m}

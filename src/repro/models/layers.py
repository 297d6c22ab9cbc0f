"""Attention (GQA / SWA / MLA), RoPE, RMSNorm and MLP layers.

All functions are pure: ``*_init(key, cfg) -> params`` and
``*_apply(params, x, ...) -> y``.  Decode variants consume/return explicit
caches (KV tensors + a scalar position) so the serving loop and the dry-run
can shard them as first-class inputs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from .config import ModelConfig
from .module import dense_init


def rmsnorm_init(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x, scale, eps):
    with obs.scope("norm"):
        return ops.rmsnorm(x, scale, eps)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) with even D; positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions.ndim == 1:
        positions = positions[None]
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention (with optional sliding window), train + decode
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False) -> Dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, dtype=dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype=dtype),
        "wo": dense_init(ks[3], h * hd, d,
                         scale=(h * hd) ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                         dtype=dtype),
    }


def _attention_shards(cfg: ModelConfig) -> Dict[str, Any]:
    """The registered mesh and the axes the batch splits over, for
    :func:`ops.flash_attention`, whose kernel runs once per shard."""
    from . import partitioning as part
    mesh = part.mesh()
    if mesh is None:
        return {}
    return {"mesh": mesh, "batch_axes": (tuple(mesh.axis_names) if cfg.pure_dp
                                         else part.batch_axes())}


def attn_apply(p, x, cfg: ModelConfig, positions, causal=True,
               use_rope=True) -> jax.Array:
    """Full-sequence attention. x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with obs.scope("attn_proj"):
        q = (x @ p["wq"]).reshape(b, s, h, hd)
        k = (x @ p["wk"]).reshape(b, s, hkv, hd)
        v = (x @ p["wv"]).reshape(b, s, hkv, hd)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    from . import partitioning as part
    mesh = part.mesh()
    if cfg.seq_shard and causal and mesh is not None and \
            s % mesh.shape["model"] == 0:
        # context parallelism: S sharded over 'model'; ring-gather K/V
        out = ops.cp_flash_attention(
            qt, kt, vt, mesh, axis="model", causal=True, window=cfg.window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    else:
        out = ops.flash_attention(
            qt, kt, vt, causal=causal, window=cfg.window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            **_attention_shards(cfg))
    with obs.scope("attn_proj"):
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        return out @ p["wo"]


def cross_attn_apply(p, x, kv_cache, cfg: ModelConfig) -> jax.Array:
    """Cross attention vs precomputed encoder K/V: kv_cache = (k, v) with
    shape (B, Henc_kv, S_enc, hd)."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q = (x @ p["wq"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k, v = kv_cache
    out = ops.flash_attention(q, k, v, causal=False, window=None,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              **_attention_shards(cfg))
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ p["wo"]


def attn_make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hkv, hd = cfg.n_kv_heads, cfg.hd
    cache_len = min(max_len, cfg.window) if cfg.window else max_len
    return {"k": jnp.zeros((batch, hkv, cache_len, hd), dtype),
            "v": jnp.zeros((batch, hkv, cache_len, hd), dtype)}


def attn_decode(p, x, cache, pos, cfg: ModelConfig, use_rope=True):
    """One-token decode. x: (B, D); cache k/v: (B, Hkv, C, hd); ``pos``:
    scalar absolute position.  Sliding windows use a ring buffer of width
    ``cfg.window``.  Returns (out (B, D), new_cache)."""
    b, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(b, 1, h, hd)
    k = (x @ p["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ p["wv"]).reshape(b, 1, hkv, hd)
    if use_rope:
        pq = jnp.full((1,), pos)
        q = apply_rope(q, pq, cfg.rope_theta)
        k = apply_rope(k, pq, cfg.rope_theta)
    c = cache["k"].shape[2]
    slot = pos % c if cfg.window else pos
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
        (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
        (0, 0, slot, 0))
    length = jnp.minimum(pos + 1, c)
    out = ops.decode_attention(
        q.reshape(b, h, hd), ck, cv,
        length=jnp.broadcast_to(length, (b,)).astype(jnp.int32))
    # NOTE on ring buffers: with a window ring buffer every slot < length is
    # valid (all within the last `window` positions), so no extra masking is
    # needed beyond `length`.
    return out.reshape(b, h * hd) @ p["wo"], {"k": ck, "v": cv}


# --------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention
# --------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    h = cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    if cfg.q_lora_rank:                 # low-rank query with its own norm
        q = {"wdq": dense_init(ks[0], d, cfg.q_lora_rank, dtype=dtype),
             "q_norm": rmsnorm_init(cfg.q_lora_rank),
             "wuq": dense_init(ks[1], cfg.q_lora_rank, h * qk, dtype=dtype)}
    else:                               # a direct query projection
        q = {"wq": dense_init(ks[0], d, h * qk, dtype=dtype)}
    return {
        **q,
        "wdkv": dense_init(ks[2], d, cfg.kv_lora_rank, dtype=dtype),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank),
        "wkr": dense_init(ks[3], d, cfg.qk_rope_head_dim, dtype=dtype),
        "wukv": dense_init(
            ks[4], cfg.kv_lora_rank,
            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype=dtype),
        "wo": dense_init(ks[5], h * cfg.v_head_dim, d,
                         scale=(h * cfg.v_head_dim) ** -0.5
                         / (2 * cfg.n_layers) ** 0.5, dtype=dtype),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions):
    """Shared q / (compressed kv) computation. Returns q, c_kv, k_rope."""
    b, s, _ = x.shape
    h = cfg.n_heads
    with obs.scope("attn_proj"):
        if cfg.q_lora_rank:
            q = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps) @ p["wuq"]
        else:
            q = x @ p["wq"]
        q = q.reshape(b, s, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        c_kv = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)  # (B,S,r)
        k_rope = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                            cfg.rope_theta)                      # (B,S,1,dr)
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(p, x, cfg: ModelConfig, positions) -> jax.Array:
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions)
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    with obs.scope("attn_proj"):
        kv = (c_kv @ p["wukv"]).reshape(
            b, s, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
        k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
        k_rope_h = jnp.broadcast_to(
            k_rope, (b, s, h, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, k_rope_h], -1)
        # v head dim != qk head dim -> pad v to qk width for the shared
        # kernel
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - cfg.v_head_dim)))
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, vp))
    out = _causal_by_query_chunk(qt, kt, vt, cfg, scale=qk ** -0.5)
    with obs.scope("attn_proj"):
        out = out.transpose(0, 2, 1, 3)[..., :cfg.v_head_dim]
        return out.reshape(b, s, h * cfg.v_head_dim) @ p["wo"]


def _causal_by_query_chunk(q, k, v, cfg: ModelConfig, scale: float):
    """Causal attention, one call per chunk of ``cfg.q_chunk`` queries over
    the keys up to its end, each recomputed in the backward pass.  Without
    that the backward of a remat layer holds every score block of the
    sequence at once: 4.6 GB in float32 for 16 heads of two sequences of
    8192 (AOT compile for a v5e).  This way one chunk's blocks are live at a
    time, for one more forward pass of the attention."""
    s = q.shape[2]
    c = min(cfg.q_chunk, s)
    outs = []
    for lo in range(0, s, c):
        attend = functools.partial(
            ops.flash_attention, causal=True, window=None, offset=lo,
            scale=scale, q_chunk=c, kv_chunk=cfg.kv_chunk,
            **_attention_shards(cfg))
        if s > c:
            attend = jax.checkpoint(attend)
        outs.append(attend(q[:, :, lo:lo + c], k[:, :, :lo + c],
                           v[:, :, :lo + c]))
    return jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]


def mla_make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Compressed cache: c_kv (B, S, r_kv) + k_rope (B, S, dr)."""
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim),
                                dtype)}


def mla_decode(p, x, cache, pos, cfg: ModelConfig, absorbed: bool = True):
    """One-token MLA decode against the *compressed* cache.

    ``absorbed=True`` uses the weight-absorption trick: queries are mapped
    into the latent space (q' = q_nope @ W_ukv^k) and attention runs directly
    over c_kv — no per-step decompression of the whole cache.  With
    ``absorbed=False`` the cache is decompressed each step (the naive form
    the absorbed one is tested against).
    """
    b, d = x.shape
    h = cfg.n_heads
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(
        p, x[:, None, :], cfg, jnp.full((1,), pos))
    cache = {
        "c_kv": jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), (0, pos, 0)),
        "k_rope": jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope_new[:, :, 0].astype(
                cache["k_rope"].dtype), (0, pos, 0)),
    }
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s_max = cache["c_kv"].shape[1]
    kpos = jnp.arange(s_max)
    valid = kpos <= pos
    wukv = p["wukv"].reshape(
        cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
    wk = wukv[:, :, :cfg.qk_nope_head_dim]            # (r, h, dqk)
    wv = wukv[:, :, cfg.qk_nope_head_dim:]            # (r, h, dv)
    if absorbed:
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk)
        logits = jnp.einsum("bhr,bsr->bhs", q_lat,
                            cache["c_kv"].astype(jnp.float32))
        logits += jnp.einsum("bhd,bsd->bhs", q_rope[:, 0],
                             cache["k_rope"].astype(jnp.float32))
        logits = jnp.where(valid[None, None], logits * scale, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", w,
                           cache["c_kv"].astype(jnp.float32))
        out = jnp.einsum("bhr,rhd->bhd", o_lat, wv)
    else:
        kv = jnp.einsum("bsr,rhd->bshd", cache["c_kv"].astype(jnp.float32),
                        wukv)
        k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
        logits = jnp.einsum("bhd,bshd->bhs", q_nope[:, 0], k_nope)
        logits += jnp.einsum("bhd,bsd->bhs", q_rope[:, 0],
                             cache["k_rope"].astype(jnp.float32))
        logits = jnp.where(valid[None, None], logits * scale, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhs,bshd->bhd", w, v)
    out = out.astype(x.dtype).reshape(b, h * cfg.v_head_dim)
    return out @ p["wo"], cache


# --------------------------------------------------------------------------
# Dense MLP (SwiGLU)
# --------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None) -> Dict:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    return {"gate": dense_init(ks[0], d, d_ff, dtype=dtype),
            "up": dense_init(ks[1], d, d_ff, dtype=dtype),
            "down": dense_init(ks[2], d_ff, d,
                               scale=d_ff ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                               dtype=dtype)}


def mlp_apply(p, x) -> jax.Array:
    with obs.scope("mlp"):
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]

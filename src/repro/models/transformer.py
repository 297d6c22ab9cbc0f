"""Decoder-only LM assembled from periodic blocks.

Layer stacks run as ``lax.scan`` over a *period super-block* (1 layer for
dense archs, 8 for Jamba's [attn + 7 mamba], 2 for xLSTM's alternation) with
stacked parameters, keeping the compiled HLO size independent of depth.
``first_k_dense`` (DeepSeek) layers run unscanned before the stack.

Entry points:
  init(key, cfg)                      -> params
  forward(params, x, cfg, positions)  -> (hidden, aux)
  lm_loss(params, batch, cfg)         -> (loss, metrics)
  init_cache(cfg, batch, max_len)     -> decode cache
  decode_step(params, cache, tok, pos, cfg) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from . import layers as L
from . import moe as M
from . import ssm as S
from .config import ModelConfig
from .module import dense_init, embed_init, stack_init

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------
# Block init / apply / decode
# --------------------------------------------------------------------------

def block_init(key, spec, cfg: ModelConfig, dtype) -> Params:
    mixer, ffn = spec
    k1, k2 = jax.random.split(key)
    bp: Params = {"ln1": L.rmsnorm_init(cfg.d_model)}
    if mixer == "attn":
        bp["mixer"] = L.attn_init(k1, cfg, dtype)
    elif mixer == "mla":
        bp["mixer"] = L.mla_init(k1, cfg, dtype)
    elif mixer == "mamba":
        bp["mixer"] = S.mamba_init(k1, cfg, dtype)
    elif mixer == "mlstm":
        bp["mixer"] = S.mlstm_init(k1, cfg, dtype)
    elif mixer == "slstm":
        bp["mixer"] = S.slstm_init(k1, cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn is not None:
        bp["ln2"] = L.rmsnorm_init(cfg.d_model)
        bp["ffn"] = M.moe_init(k2, cfg, dtype) if ffn == "moe" \
            else L.mlp_init(k2, cfg, dtype)
    return bp


def block_apply(bp, x, spec, cfg: ModelConfig, positions):
    """-> (x, aux): ``aux["loss"]`` the block's balance loss, and for a MoE
    block ``aux["moe"]`` its counters (``moe.moe_apply``)."""
    mixer, ffn = spec
    aux = {"loss": jnp.zeros((), jnp.float32)}
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    if mixer == "attn":
        mx = L.attn_apply(bp["mixer"], h, cfg, positions)
    elif mixer == "mla":
        mx = L.mla_apply(bp["mixer"], h, cfg, positions)
    elif mixer == "mamba":
        mx = S.mamba_apply(bp["mixer"], h, cfg)
    elif mixer == "mlstm":
        mx = S.mlstm_apply(bp["mixer"], h, cfg)
    elif mixer == "slstm":
        mx = S.slstm_apply(bp["mixer"], h, cfg)
    x = x + mx
    if ffn is not None:
        h2 = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if ffn == "moe":
            y, aux["loss"], aux["moe"] = M.moe_apply(bp["ffn"], h2, cfg)
        else:
            y = L.mlp_apply(bp["ffn"], h2)
        x = x + y
    return x, aux


def block_make_cache(spec, cfg: ModelConfig, batch: int, max_len: int, dtype):
    mixer, _ = spec
    if mixer == "attn":
        return L.attn_make_cache(cfg, batch, max_len, dtype)
    if mixer == "mla":
        return L.mla_make_cache(cfg, batch, max_len, dtype)
    if mixer == "mamba":
        return S.mamba_make_cache(cfg, batch, dtype)
    if mixer == "mlstm":
        return S.mlstm_make_cache(cfg, batch, dtype)
    if mixer == "slstm":
        return S.slstm_make_cache(cfg, batch, dtype)
    raise ValueError(mixer)


def block_decode(bp, x, cache, spec, cfg: ModelConfig, pos):
    mixer, ffn = spec
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    if mixer == "attn":
        mx, cache = L.attn_decode(bp["mixer"], h, cache, pos, cfg)
    elif mixer == "mla":
        mx, cache = L.mla_decode(bp["mixer"], h, cache, pos, cfg)
    elif mixer == "mamba":
        mx, cache = S.mamba_decode(bp["mixer"], h, cache, cfg)
    elif mixer == "mlstm":
        mx, cache = S.mlstm_decode(bp["mixer"], h, cache, cfg)
    elif mixer == "slstm":
        mx, cache = S.slstm_decode(bp["mixer"], h, cache, cfg)
    x = x + mx
    if ffn is not None:
        h2 = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if ffn == "moe":
            y, _, _ = M.moe_apply(bp["ffn"], h2[:, None, :], cfg)
            y = y[:, 0]
        else:
            y = L.mlp_apply(bp["ffn"], h2)
        x = x + y
    return x, cache


# --------------------------------------------------------------------------
# Full model
# --------------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> Params:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab,
                                    dtype=dtype)
    if cfg.first_k_dense:
        spec = (cfg.period[0][0], "mlp")
        params["prefix"] = [
            block_init(jax.random.fold_in(keys[2], i), spec, cfg, dtype)
            for i in range(cfg.first_k_dense)]
    stack = {}
    for i, spec in enumerate(cfg.period):
        stack[f"pos{i}"] = stack_init(
            lambda k, spec=spec: block_init(k, spec, cfg, dtype),
            jax.random.fold_in(keys[3], i), cfg.n_periods)
    params["stack"] = stack
    if cfg.mtp:
        params["mtp"] = {
            "proj": dense_init(keys[4], 2 * cfg.d_model, cfg.d_model,
                               dtype=dtype),
            "norm_h": L.rmsnorm_init(cfg.d_model),
            "norm_e": L.rmsnorm_init(cfg.d_model),
            "block": block_init(keys[5], cfg.period[0], cfg, dtype),
            "final_norm": L.rmsnorm_init(cfg.d_model),
        }
    return params


def forward(params, x, cfg: ModelConfig, positions) -> Tuple[jax.Array, Dict]:
    """x: (B, S, D) embedded inputs -> (hidden (B,S,D), aux): ``aux["loss"]``
    the balance losses summed, ``aux["moe"]`` the counters of each MoE
    position of the stack (``stack/pos<i>``), stacked over its layers."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.first_k_dense:
        spec = (cfg.period[0][0], "mlp")
        for bp in params["prefix"]:
            x, a = block_apply(bp, x, spec, cfg, positions)
            aux += a["loss"]

    def period_body(carry, xs):
        x, aux = carry
        moe = {}
        for i, spec in enumerate(cfg.period):
            x, a = block_apply(xs[f"pos{i}"], x, spec, cfg, positions)
            aux += a["loss"]
            if "moe" in a:
                moe[f"stack/pos{i}"] = a["moe"]
        return (x, aux), moe

    if cfg.remat:
        period_body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers:
        (x, aux), moe = jax.lax.scan(period_body, (x, aux), params["stack"])
    else:
        per = []
        for j in range(cfg.n_periods):
            sl = jax.tree.map(lambda a: a[j], params["stack"])
            (x, aux), m = period_body((x, aux), sl)
            per.append(m)
        moe = jax.tree.map(lambda *a: jnp.stack(a), *per)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, {"loss": aux, "moe": moe}


def logits_fn(params, h, cfg: ModelConfig) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (h @ w).astype(jnp.float32)


def _chunked_ce(params, h, labels, mask, cfg: ModelConfig,
                chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without materialising (B, S, V) logits at once."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    tot, cnt = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    with obs.scope("head_loss"):
        for i in range(0, s, chunk):
            # final chunk may be ragged (e.g. the MTP branch's shifted
            # sequence)
            lg = logits_fn(params, h[:, i:i + chunk], cfg)   # (B, c, V) f32
            lab = labels[:, i:i + chunk]
            msk = mask[:, i:i + chunk]
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
            tot += jnp.sum((lse - gold) * msk)
            cnt += jnp.sum(msk)
    return tot, cnt


def embed_tokens(params, tokens, cfg: ModelConfig) -> jax.Array:
    with obs.scope("embed"):
        return params["embed"][tokens]


def lm_loss(params, batch, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """batch: {'inputs': (B,S) int32 | 'embeds': (B,S,D), 'labels': (B,S),
    optional 'mask': (B,S)}."""
    if "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        x = embed_tokens(params, batch["inputs"], cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    h, aux = forward(params, x, cfg, positions)
    moe = dict(aux["moe"])
    aux = aux["loss"]
    tot, cnt = _chunked_ce(params, h, labels, mask, cfg)
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = {"ce": loss, "aux": aux, "tokens": cnt}
    if cfg.mtp and "inputs" in batch:
        mp = params["mtp"]
        # predict token t+2: combine h_t with embedding of t+1 (= labels_t)
        h_in = L.rmsnorm(h[:, :-1], mp["norm_h"], cfg.norm_eps)
        e_in = L.rmsnorm(embed_tokens(params, labels[:, :-1], cfg),
                         mp["norm_e"], cfg.norm_eps)
        x2 = jnp.concatenate([h_in, e_in], axis=-1) @ mp["proj"]
        x2, a2 = block_apply(mp["block"], x2, cfg.period[0], cfg,
                             positions[:-1])
        if "moe" in a2:
            moe["mtp/block"] = a2["moe"]
        x2 = L.rmsnorm(x2, mp["final_norm"], cfg.norm_eps)
        tot2, cnt2 = _chunked_ce(params, x2, labels[:, 1:], mask[:, 1:], cfg)
        mtp_loss = tot2 / jnp.maximum(cnt2, 1.0)
        loss = loss + cfg.mtp_weight * mtp_loss
        metrics["mtp"] = mtp_loss
    loss = loss + aux
    if moe:
        metrics.update(M.counters(moe, cfg))
    return loss, metrics


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dtype = _dtype(cfg)
    cache: Params = {}
    if cfg.first_k_dense:
        spec = (cfg.period[0][0], "mlp")
        cache["prefix"] = [block_make_cache(spec, cfg, batch, max_len, dtype)
                           for _ in range(cfg.first_k_dense)]
    stack = {}
    for i, spec in enumerate(cfg.period):
        one = block_make_cache(spec, cfg, batch, max_len, dtype)
        stack[f"pos{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape),
            one)
    cache["stack"] = stack
    return cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """tokens: (B,) int32; pos: scalar int32 absolute position.
    Returns (logits (B, V) f32, new_cache)."""
    x = params["embed"][tokens]

    def period_body(x, xs):
        bp, bc = xs
        new_bc = {}
        for i, spec in enumerate(cfg.period):
            x, new_bc[f"pos{i}"] = block_decode(
                bp[f"pos{i}"], x, bc[f"pos{i}"], spec, cfg, pos)
        return x, new_bc

    new_cache: Params = {}
    if cfg.first_k_dense:
        spec = (cfg.period[0][0], "mlp")
        new_cache["prefix"] = []
        for bp, bc in zip(params["prefix"], cache["prefix"]):
            x, nc = block_decode(bp, x, bc, spec, cfg, pos)
            new_cache["prefix"].append(nc)
    x, new_stack = jax.lax.scan(period_body, x,
                                (params["stack"], cache["stack"]))
    new_cache["stack"] = new_stack
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, x, cfg)
    return logits, new_cache

"""Jitted step functions shared by the trainer, server and dry-run."""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import ModelAPI, model_api
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer, clip_by_global_norm


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    clip_norm: float = 1.0) -> Callable:
    api = model_api(cfg)
    obs.log_compiles()

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = api.loss(p, batch, cfg)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        with obs.scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            params, opt_state = optimizer.update(grads, opt_state, params)
        out = {"loss": loss, "grad_norm": gnorm}
        out.update({k: v for k, v in metrics.items()})
        return params, opt_state, out

    return train_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """Inference prefill: full no-grad forward, last-token logits.

    (Cache extraction happens in the step-wise serving path; prefill compute
    and memory are dominated by the forward pass lowered here.)"""
    api = model_api(cfg)

    def prefill_step(params, batch):
        if cfg.is_encdec:
            from repro.models import encdec
            enc = encdec.encode(params, batch["frames"], cfg)
            h = encdec.decode_train(params, enc, batch["inputs"], cfg)
            w = params["embed"].T
            return (h[:, -1] @ w).astype(jnp.float32)
        from repro.models import transformer
        if "embeds" in batch:
            x = batch["embeds"].astype(
                jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
        else:
            x = transformer.embed_tokens(params, batch["inputs"], cfg)
        positions = jnp.arange(x.shape[1])
        h, _ = transformer.forward(params, x, cfg, positions)
        return transformer.logits_fn(params, h[:, -1:], cfg)[:, 0]

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    api = model_api(cfg)

    def serve_step(params, cache, tokens, pos):
        logits, cache = api.decode_step(params, cache, tokens, pos, cfg)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    return serve_step

"""Optimizers (no optax dependency): AdamW and Adafactor, with global-norm
clipping and warmup+cosine schedule.

AdamW keeps f32 moments (2 x 4 bytes/param); Adafactor keeps factored second
moments (~4 bytes/row+col) — the memory-feasible choice for the 235B/398B/
671B cells (see EXPERIMENTS.md §Dry-run memory table).  Both update params
in their storage dtype; moments/statistics are always f32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[..., Tuple[Any, Any]]   # (grads, state, params) -> (p, s)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale
                                   ).astype(x.dtype), tree), norm


def warmup_cosine(base_lr: float, warmup: int, total: int,
                  floor: float = 0.1) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        step = step.astype(jnp.float32)
        warm = base_lr * jnp.minimum(1.0, (step + 1) / max(warmup, 1))
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, base_lr * cos)
    return lr


def adamw(lr: Callable | float, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)   # keeps sharding
        return {"mu": jax.tree.map(zeros, params),
                "nu": jax.tree.map(zeros, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if p.ndim >= 2:                       # no decay on norms/bias
                upd = upd + weight_decay * p.astype(jnp.float32)
            return m, v, (p.astype(jnp.float32) - lr_t * upd).astype(p.dtype)

        flat = jax.tree.map(upd, grads, state["mu"], state["nu"], params)
        mu = jax.tree.map(lambda t: t[0], flat,
                          is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree.map(lambda t: t[1], flat,
                          is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.map(lambda t: t[2], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"mu": mu, "nu": nu, "step": step}

    return Optimizer(init, update)


def adafactor(lr: Callable | float, decay: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0,
              weight_decay: float = 0.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        def stats(p):
            if p.ndim >= 2:
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"stats": jax.tree.map(stats, params,
                                      is_leaf=lambda x: hasattr(x, "ndim")),
                "step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        beta = 1.0 - (step.astype(jnp.float32) + 1.0) ** -decay

        def upd(g, st, p):
            g = g.astype(jnp.float32)
            g2 = g * g + eps
            if p.ndim >= 2:
                vr = beta * st["vr"] + (1 - beta) * g2.mean(axis=-1)
                vc = beta * st["vc"] + (1 - beta) * g2.mean(axis=-2)
                # Shazeer-Stern factored estimate: V ~= vr vc^T / mean(vr)
                mean_vr = jnp.maximum(vr.mean(axis=-1)[..., None, None], eps)
                vhat = vr[..., :, None] * vc[..., None, :] / mean_vr
                u = g / jnp.sqrt(jnp.maximum(vhat, eps))
                new_st = {"vr": vr, "vc": vc}
            else:
                v = beta * st["v"] + (1 - beta) * g2
                u = g / jnp.sqrt(jnp.maximum(v, eps))
                new_st = {"v": v}
            rms = jnp.sqrt(jnp.mean(u * u) + 1e-12)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            p32 = p.astype(jnp.float32)
            if weight_decay and p.ndim >= 2:
                u = u + weight_decay * p32
            return new_st, (p32 - lr_t * u).astype(p.dtype)

        is_stats = lambda x: isinstance(x, dict) and ("vr" in x or "v" in x)
        flat = jax.tree.map(upd, grads, state["stats"], params,
                            is_leaf=lambda x: hasattr(x, "ndim"))
        stats = jax.tree.map(lambda t: t[0], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.map(lambda t: t[1], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"stats": stats, "step": step}

    return Optimizer(init, update)


def pick_optimizer(n_params: int, lr) -> Tuple[str, Optimizer]:
    """Memory policy: Adafactor above 20B params (moments would not fit),
    AdamW otherwise."""
    if n_params > 20e9:
        return "adafactor", adafactor(lr)
    return "adamw", adamw(lr)

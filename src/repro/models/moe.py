"""Top-k mixture-of-experts: a router over every expert of the layer, and
the experts this layer holds.

``cfg.n_experts`` counts the experts held here and ``cfg.n_router`` the
router's published width.  The layer holds experts ``[0, n_experts)``,
routes each token over all ``n_router``, and computes its own experts' part
of the result; what the absent experts would add belongs to the chips that
hold them (expert parallelism, whose exchange one chip does not have).
Shared experts are computed once, for every token.

Router (DeepSeek-V3, arXiv:2412.19437 §2.1.2, where the config says so):
softmax or sigmoid scores; ``router_bias`` adds a correction bias used only
to choose the top k; ``n_group`` > 1 first keeps, per token, the
``topk_group`` groups whose two best scores sum highest.  Gates are the
chosen scores without the bias, normalised over the k, times
``routed_scale``.  The balance loss is switch-style over the batch,
or with ``seq_aux`` DeepSeek-V3's sequence-wise ``alpha * sum_i f_i P_i``.

Two dispatches, chosen by what the layer observes:

* dropless, where the experts are not sharded over a mesh axis (one chip):
  the token copies routed to held experts are sorted by expert, each held
  expert runs a grouped matmul (:func:`grouped_matmul`) over exactly its
  rows, and the results are scatter-added, weighted, to their tokens.  No
  copy is dropped.
* capacity, where the experts are sharded over ``model`` (the multi-device
  dry-run): each expert takes a window of ``capacity_factor`` times its
  share of the copies, gathered so that GSPMD lowers the dispatch to
  all-to-all traffic; copies past a window are dropped, and counted.

``moe_apply`` returns the output, the balance loss and the layer's
counters: the load of every router expert (the copies it was chosen for,
which :func:`update_router_bias` turns into the bias's next value), the
copies computed here, the largest held expert's copies and the copies
dropped.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro import obs
from . import partitioning as part
from .config import ModelConfig
from .module import dense_init
from .layers import mlp_apply, mlp_init


def moe_init(key, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    d, e, h = cfg.d_model, cfg.n_experts, cfg.d_expert
    params = {
        "router": dense_init(ks[0], d, cfg.n_router, scale=0.02,
                             dtype=jnp.float32),
        "experts": {
            "gate": dense_init(ks[1], d, e * h, dtype=dtype).reshape(d, e, h)
                    .transpose(1, 0, 2),                        # (E, D, H)
            "up": dense_init(ks[2], d, e * h, dtype=dtype).reshape(d, e, h)
                  .transpose(1, 0, 2),
            "down": dense_init(ks[3], e * h, d,
                               scale=h ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                               dtype=dtype).reshape(e, h, d),
        },
    }
    if cfg.router_bias:
        # state, not a gradient leaf: see update_router_bias
        params["router_bias"] = jnp.zeros((cfg.n_router,), jnp.float32)
    if cfg.n_shared_experts:
        params["shared"] = mlp_init(
            jax.random.fold_in(key, 7), cfg, dtype,
            d_ff=cfg.n_shared_experts * cfg.d_expert)
    return params


# --------------------------------------------------------------------------
# Router
# --------------------------------------------------------------------------

def select(choice, cfg: ModelConfig) -> jax.Array:
    """The ``top_k`` experts each token takes by ``choice`` (T, E): with
    ``n_group`` > 1 only from the ``topk_group`` groups of consecutive
    experts whose two best choices sum highest."""
    if cfg.n_group > 1:
        t, e = choice.shape
        g = choice.reshape(t, cfg.n_group, e // cfg.n_group)
        best = jax.lax.top_k(g, 2)[0].sum(-1)                   # (T, G)
        _, keep = jax.lax.top_k(best, cfg.topk_group)
        kept = jnp.zeros(best.shape, bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(kept[..., None], g, -jnp.inf).reshape(t, e)
    return jax.lax.top_k(choice, cfg.top_k)[1]


def route(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (idx (T, k) over the router's width, gates (T, k)
    float32, load (E,) int32, balance loss)."""
    b, s, d = x.shape
    e, k = cfg.n_router, cfg.top_k
    with obs.scope("router"):
        logits = x.reshape(b * s, d).astype(jnp.float32) @ p["router"]
        if cfg.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
        if cfg.router_bias:
            choice = scores + jax.lax.stop_gradient(p["router_bias"])
        idx = select(choice, cfg)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9) \
            * cfg.routed_scale
        chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)  # (T, E)
        load = chosen.sum(0).astype(jnp.int32)
        if cfg.seq_aux:
            # per sequence: f_i = E/(k S) * copies to i, P_i = mean over
            # the sequence of the scores normalised over all E
            f = chosen.reshape(b, s, e).mean(1) * (e / k)
            norm = scores / scores.sum(-1, keepdims=True)
            pi = norm.reshape(b, s, e).mean(1)
            aux = jnp.mean(jnp.sum(f * pi, -1)) * cfg.router_aux_weight
        else:
            # switch-style: top-1 share against mean score, over the batch
            top1 = jnp.argmax(scores, axis=-1)
            frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32),
                                   axis=0)
            aux = e * jnp.sum(frac_tokens * scores.mean(axis=0)) \
                * cfg.router_aux_weight
    return idx, gates, load, aux


def update_router_bias(new_params, params, loads, cfg: ModelConfig):
    """DeepSeek-V3's load rule after a step: ``b_i += gamma * sign(mean
    load - load_i)`` over every router expert, with the loads counted in
    this step on this chip's tokens.  ``loads`` maps the path of each MoE
    placement (``stack/pos1``, ``mtp/block``) to its loads, (E,) or stacked
    (layers, E).  The bias comes from ``params`` as the step found it:
    whatever the optimizer did to it (its gradient is zero) is replaced,
    so it is neither decayed, clipped nor given moments."""
    def put(tree, keys, value):
        if not keys:
            return value
        return dict(tree, **{keys[0]: put(tree[keys[0]], keys[1:], value)})

    for path, load in loads.items():
        keys = path.split("/") + ["ffn", "router_bias"]
        bias = params
        for key in keys:
            bias = bias[key]
        load = load.astype(jnp.float32)
        step = jnp.sign(load.mean(-1, keepdims=True) - load)
        new_params = put(new_params, keys,
                         bias + cfg.router_bias_speed * step)
    return new_params


def counters(stats: Dict[str, Dict], cfg: ModelConfig) -> Dict:
    """A step's MoE counters from each placement's layer counters: copies
    computed here and copies dropped, summed over layers; the largest held
    expert's copies in any layer; with a correction bias, the loads that
    update it (``moe_load``)."""
    out = {
        "moe_rows_here": sum(s["rows_here"].sum() for s in stats.values()),
        "moe_max_rows": jnp.max(jnp.stack(
            [s["max_rows"].max() for s in stats.values()])),
        "moe_dropped": sum(s["dropped"].sum() for s in stats.values()),
    }
    if cfg.router_bias:
        out["moe_load"] = {k: s["load"] for k, s in stats.items()}
    return out


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------

def _gmm_tiling(m, k, n):
    """Megablox tiles: 512 along a dimension it divides, else the whole
    dimension."""
    return tuple(512 if x % 512 == 0 else x for x in (m, k, n))


def _megablox(a, w, sizes, interpret=False):
    return gmm(a, w, sizes, preferred_element_type=a.dtype,
               tiling=_gmm_tiling, interpret=interpret)


def grouped_matmul(a, w, sizes):
    """``a[rows of group g] @ w[g]`` for consecutive groups of ``sizes``
    rows: (M, K) x (G, K, N) -> (M, N); rows past the last group are left
    undefined.  Chosen by the platform it is lowered for: the Pallas
    megablox kernel on a TPU, whose time per row on a v5e is 2.35x less
    than ``ragged_dot``'s at the moonlight cell's shapes (and which makes a
    step's time swing less with its held experts' load), and XLA's
    ``jax.lax.ragged_dot`` elsewhere."""
    return jax.lax.platform_dependent(a, w, sizes, tpu=_megablox,
                                      default=jax.lax.ragged_dot)


def _dropless(p, xf, idx, gates, cfg: ModelConfig, acc_dt):
    """Every copy routed to a held expert, computed by a grouped matmul over
    the true group sizes."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    with obs.scope("dispatch"):
        flat = idx.reshape(-1)                                  # (T*k,)
        expert = jnp.where(flat < e, flat, e)       # absent experts: last
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.bincount(expert, length=e + 1)[:e].astype(jnp.int32)
        tok = order // k
        here = (expert < e)[order][:, None]   # the rows of the held groups
        wgt = jnp.where(here[:, 0], gates.reshape(-1)[order], 0.0)
        xs = jnp.where(here, xf[tok], 0)                        # (T*k, D)

    # The TPU's grouped matmuls leave the rows past the last group
    # unwritten, in their result and in their input's gradient alike: every
    # result is masked before it is used, and so is the input, whose
    # gradient would otherwise carry those rows to the tokens.
    def grouped(a, w):
        return jnp.where(here, grouped_matmul(a, w, sizes), 0)

    with obs.scope("experts"):
        ex = p["experts"]
        h = jax.nn.silu(grouped(xs, ex["gate"])) * grouped(xs, ex["up"])
        ys = grouped(h, ex["down"])
    with obs.scope("dispatch"):
        ys = ys.astype(jnp.float32) * wgt[:, None]
        y = jnp.zeros((t, d), acc_dt).at[tok].add(ys.astype(acc_dt))
    stats = {"rows_here": sizes.sum(), "max_rows": sizes.max(),
             "dropped": jnp.zeros((), jnp.int32)}
    return y, stats


def _capacity_group(xf, idx, w, e, cap):
    """Sorted capacity dispatch for one token group.

    xf: (Tg, D); idx, w: (Tg, k).  Returns (xg (E,cap,D), tok (E,cap),
    wgt (E,cap), sizes (E,)) with ``tok`` local to the group."""
    t, k = idx.shape
    flat_e = idx.reshape(-1)                                    # (Tg*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st_, sw = flat_e[order], flat_t[order], flat_w[order]
    sizes = jnp.bincount(se, length=e)                          # (E,)
    starts = jnp.cumsum(sizes) - sizes
    win = starts[:, None] + jnp.arange(cap)[None]               # (E, cap)
    valid = (jnp.arange(cap)[None] < jnp.minimum(sizes, cap)[:, None])
    win = jnp.clip(win, 0, t * k - 1)
    tok = st_[win]                                              # (E, cap)
    wgt = jnp.where(valid, sw[win], 0.0)
    xg = xf[tok] * valid[..., None].astype(xf.dtype)            # (E, cap, D)
    return xg, tok, wgt, sizes


def _capacity(p, xf, idx, gates, cfg: ModelConfig, acc_dt):
    """Capacity dispatch for experts sharded over ``model``.

    ``cfg.moe_dispatch_groups`` > 1 enables *grouped local dispatch*: tokens
    are routed within data-shard-aligned groups, so the dispatch gather moves
    each group's tokens only across the expert (model) axis — all-to-all
    shaped traffic — instead of all-gathering every token to every shard
    (EXPERIMENTS.md §Perf H3).  Capacity is per (expert, group), preserving
    total expert FLOPs."""
    if cfg.n_experts != cfg.n_router:
        raise ValueError(
            f"{cfg.name}: the capacity dispatch shards every router expert "
            f"over 'model'; this layer holds {cfg.n_experts} of "
            f"{cfg.n_router}")
    t, d = xf.shape
    k, e = cfg.top_k, cfg.n_experts
    g = max(1, cfg.moe_dispatch_groups)
    assert t % g == 0, (t, g)
    cap = int(max(1, -(-t * k * cfg.capacity_factor // (e * g))))
    with obs.scope("dispatch"):
        xg, tok, wgt, sizes = jax.vmap(
            lambda xfg, ig, wg: _capacity_group(xfg, ig, wg, e, cap)
        )(xf.reshape(g, t // g, d), idx.reshape(g, t // g, k),
          gates.reshape(g, t // g, k))
    # xg: (G, E, cap, D) — groups over the batch axes, experts over 'model':
    # hierarchical EP (without the batch-axes sharding the expert FLOPs
    # inflate by the DP degree — observed 16x on qwen3).
    with obs.scope("experts"):
        if g > 1:
            xg = part.constrain(xg, "BATCH", "model", None, None)
            h = jax.nn.silu(jnp.einsum("gecd,edh->gech", xg,
                                       p["experts"]["gate"])) \
                * jnp.einsum("gecd,edh->gech", xg, p["experts"]["up"])
            h = part.constrain(h, "BATCH", "model", None, None)
            out = jnp.einsum("gech,ehd->gecd", h, p["experts"]["down"])
            out = part.constrain(out, "BATCH", "model", None, None)
        else:
            xg1 = part.constrain(xg[0], "model", "BATCH", None)
            h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xg1,
                                       p["experts"]["gate"])) \
                * jnp.einsum("ecd,edh->ech", xg1, p["experts"]["up"])
            h = part.constrain(h, "model", "BATCH", None)
            out = jnp.einsum("ech,ehd->ecd", h, p["experts"]["down"])
            out = part.constrain(out, "model", "BATCH", None)[None]

    # combine: per-group scatter-add back to the group's tokens (token-
    # sharded — unconstrained GSPMD tends to replicate this over the model
    # axis, costing TP-degree x activation memory)
    def combine(out_g, tok_g, wgt_g):
        yg = jnp.zeros((t // g, d), acc_dt)
        return yg.at[tok_g.reshape(-1)].add(
            (out_g * wgt_g[..., None]).reshape(-1, d).astype(acc_dt))

    with obs.scope("dispatch"):
        y = jax.vmap(combine)(out, tok, wgt)                    # (G, T/G, D)
        y = part.constrain(y.reshape(t, d), "BATCH", None)
    kept = jnp.minimum(sizes, cap)
    stats = {"rows_here": kept.sum(), "max_rows": kept.max(),
             "dropped": (sizes - kept).sum()}
    return y, stats


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array, Dict]:
    """x: (B, S, D) -> (y, balance loss, counters)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    idx, gates, load, aux = route(p, x, cfg)
    acc_dt = jnp.bfloat16 if cfg.moe_combine_dtype == "bfloat16" \
        else jnp.float32
    dispatch = _capacity if part.axis_size("model") > 1 else _dropless
    y, stats = dispatch(p, xf, idx, gates, cfg, acc_dt)
    y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], xf)
    stats["load"] = load
    return y.reshape(b, s, d), aux, stats

"""smollm-360m (HuggingFaceTB/SmolLM-360M): its plain reference, and the
cell driver that runs the system under test against it.

The reference is a straightforward float32 Llama in ``jax.numpy`` written
from the published architecture, importing nothing of the system under
test: token embedding, per layer RMSNorm -> grouped-query causal attention
with rotary positions (rotate-half) -> residual -> RMSNorm -> SwiGLU MLP ->
residual, a final RMSNorm and the tied embedding as the LM head; mean
cross-entropy over every token; gradients clipped to a global norm; AdamW
with decoupled weight decay on every leaf of rank two or more as the
traffic file states (``weight_decay_on``): the weight matrices, the
embedding and the per-layer norm scales, stacked over the layers, but not
the final norm scale.  It
makes its own weights and batches from the seed by the recipe the system
uses (normal draws of the same keys and scales; Zipf tokens from the same
NumPy streams), so both start from the same numbers without sharing any.

Weights are stored in the configuration's dtype (bfloat16: each update is
rounded to it, as the configuration states); everything else is float32,
and every matmul runs at ``precision="highest"``.  ``matmul="int8"`` gives
the control: the same computation with every matmul operand, forward and
backward, rounded to int8 under one symmetric scale per tensor, the step
below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import numpy as np

#: leaves are named by their path in the system's parameter tree; the
#: norm scales are kept in float32
NORM_LEAVES = ("final_norm", "stack/pos0/ln1", "stack/pos0/ln2")


def run(ctx):
    import sys
    from yardstick import train_lm
    return train_lm.run_cell(ctx, sys.modules[__name__])


# ---------------------------------------------------------------------------
# Inputs from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> np.ndarray:
    """All 64 bits of the seed as a raw threefry key."""
    s = seed % 2 ** 64
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def batches(seed: int, batch: int, seq: int, vocab: int, alpha: float,
            steps: int) -> List[Dict[str, np.ndarray]]:
    """Zipf(alpha) tokens: step ``i`` draws batch x (seq + 1) uniforms from
    ``default_rng(SeedSequence([seed, 0, i]))`` and maps them through the
    Zipf CDF; inputs and labels are the sequence shifted by one."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -alpha
    cdf = np.cumsum(probs / probs.sum())
    out = []
    for i in range(steps):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0, i]))
        toks = np.searchsorted(cdf, rng.random((batch, seq + 1)))
        toks = np.clip(toks, 0, vocab - 1).astype(np.int32)
        out.append({"inputs": toks[:, :-1], "labels": toks[:, 1:]})
    return out


def stored(m: dict, x):
    """``x`` as the configuration stores weight matrices: rounded to its
    dtype, computed on in float32."""
    import jax.numpy as jnp
    return x.astype(m["dtype"]).astype(jnp.float32)


def init(m: dict, key):
    """float32 copies of the initial weights as stored."""
    import jax
    import jax.numpy as jnp
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    f, n_layers, v = m["d_ff"], m["n_layers"], m["vocab"]

    def dense(k, d_in, d_out, scale=None):
        scale = scale if scale is not None else d_in ** -0.5
        return stored(m, jax.random.normal(k, (d_in, d_out), jnp.float32)
                      * scale)

    def layer(k):
        k1, k2 = jax.random.split(k)
        ka = jax.random.split(k1, 4)
        kf = jax.random.split(k2, 3)
        out_scale = 1 / (2 * n_layers) ** 0.5
        return {
            "ln1": jnp.ones((d,), jnp.float32),
            "wq": dense(ka[0], d, h * hd),
            "wk": dense(ka[1], d, kv * hd),
            "wv": dense(ka[2], d, kv * hd),
            "wo": dense(ka[3], h * hd, d, (h * hd) ** -0.5 * out_scale),
            "ln2": jnp.ones((d,), jnp.float32),
            "gate": dense(kf[0], d, f),
            "up": dense(kf[1], d, f),
            "down": dense(kf[2], f, d, f ** -0.5 * out_scale),
        }

    keys = jax.random.split(key, 8)
    embed = (jax.random.normal(keys[0], (v, d), jnp.float32) * 0.02)
    layers = jax.vmap(layer)(jax.random.split(
        jax.random.fold_in(keys[3], 0), n_layers))
    p = {"embed": stored(m, embed),
         "final_norm": jnp.ones((d,), jnp.float32)}
    for name, arr in layers.items():
        group = "ffn" if name in ("gate", "up", "down") else \
            "mixer" if name.startswith("w") else None
        p["stack/pos0/" + (f"{group}/" if group else "") + name] = arr
    return p


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _int8(x):
    """Round to int8 under a symmetric per-tensor scale."""
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _einsum_highest(spec, a, b):
    import jax
    import jax.numpy as jnp
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _int8_einsum(spec):
    import jax

    @jax.custom_vjp
    def f(a, b):
        return _einsum_highest(spec, _int8(a), _int8(b))

    def fwd(a, b):
        qa, qb = _int8(a), _int8(b)
        return _einsum_highest(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: _einsum_highest(spec, x, y), qa, qb)
        return vjp(_int8(g))

    f.defvjp(fwd, bwd)
    return f


def einsum_for(matmul: str):
    if matmul == "highest":
        return _einsum_highest
    if matmul == "int8":
        return lambda spec, a, b: _int8_einsum(spec)(a, b)
    raise ValueError(matmul)


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (rows, S, heads, hd); rotate-half rotary positions."""
    import jax.numpy as jnp
    s, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss_sum(p, inputs, labels, m: dict, ein) -> "jax.Array":
    """Summed cross-entropy of a block of sequences."""
    import jax
    import jax.numpy as jnp
    h, kv, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], \
        m["norm_eps"]
    rows, s = inputs.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        y = _rmsnorm(x, w["ln1"], eps)
        q = ein("rsd,de->rse", y, w["wq"]).reshape(rows, s, h, hd)
        k = ein("rsd,de->rse", y, w["wk"]).reshape(rows, s, kv, hd)
        v = ein("rsd,de->rse", y, w["wv"]).reshape(rows, s, kv, hd)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        k = jnp.repeat(k, h // kv, axis=2)          # query head j reads
        v = jnp.repeat(v, h // kv, axis=2)          # kv head j // (h/kv)
        logits = ein("rqhd,rkhd->rhqk", q, k) * hd ** -0.5
        logits = jnp.where(causal, logits, -jnp.inf)
        a = ein("rhqk,rkhd->rqhd", jax.nn.softmax(logits, axis=-1), v)
        x = x + ein("rse,ed->rsd", a.reshape(rows, s, h * hd), w["wo"])
        y = _rmsnorm(x, w["ln2"], eps)
        g = ein("rsd,df->rsf", y, w["gate"])
        u = ein("rsd,df->rsf", y, w["up"])
        return x + ein("rsf,fd->rsd", jax.nn.silu(g) * u, w["down"]), None

    stack = {k.rsplit("/", 1)[1]: v for k, v in p.items()
             if k.startswith("stack/")}
    x = p["embed"][inputs]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stack)
    x = _rmsnorm(x, p["final_norm"], eps)
    logits = ein("rsd,vd->rsv", x, p["embed"])
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


def lr_at(step: int, o: dict) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to
    a tenth of it at ``total``; ``step`` counts from 1."""
    lr, warm, total = o["lr"], o["warmup"], o["total"]
    if step < warm:
        return lr * min(1.0, (step + 1) / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


def reference(m: dict, traffic: dict, seed: int, steps: int,
              matmul: str = "highest", keep_rows: int = 0) -> dict:
    """``steps`` training steps from the seed's weights on the seed's
    batches.  Returns the loss of each step, the norm of each leaf's first
    (clipped) gradient, and the norm of each leaf's change after the last
    step.  Sequences run one at a time; ``keep_rows`` > 0 keeps only
    that many sequences of each batch (a fault reading: half the batch
    left out)."""
    import jax
    import jax.numpy as jnp
    o = traffic["optimizer"]
    data = batches(seed, traffic["batch"], traffic["seq"], m["vocab"],
                   traffic["zipf_alpha"], steps)
    ein = einsum_for(matmul)

    @jax.jit
    def grads(p, inputs, labels):
        n = inputs.size

        def body(acc, xs):
            loss, g = jax.value_and_grad(loss_sum)(p, xs[0], xs[1], m, ein)
            return jax.tree.map(jnp.add, acc, (loss, g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (tot, g), _ = jax.lax.scan(body, zero,
                                   (inputs[:, None], labels[:, None]))
        g = jax.tree.map(lambda x: x / n, g)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(norm, 1e-9))
        return tot / n, jax.tree.map(lambda x: x * scale, g)

    @jax.jit
    def adamw(p, g, mu, nu, t, lr):
        c1, c2 = 1 - o["b1"] ** t, 1 - o["b2"] ** t
        mu = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, mu, g)
        nu = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b,
                          nu, g)
        new = {}
        for k in p:
            upd = (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + o["eps"])
            if p[k].ndim >= 2:
                upd = upd + o["weight_decay"] * p[k]
            new[k] = p[k] - lr * upd
            if k not in NORM_LEAVES:
                new[k] = stored(m, new[k])
        return new, mu, nu

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in t.items()})
    with jax.default_matmul_precision("highest"):
        p = init(m, seed_key(seed))
        p0 = p
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        losses, first = [], None
        for t, b in enumerate(data, start=1):
            inputs, labels = b["inputs"], b["labels"]
            if keep_rows:
                inputs, labels = inputs[:keep_rows], labels[:keep_rows]
            loss, g = grads(p, jnp.asarray(inputs), jnp.asarray(labels))
            if first is None:
                first = {k: float(v) for k, v in norms(g).items()}
            p, mu, nu = adamw(p, g, mu, nu, float(t), lr_at(t, o))
            losses.append(float(loss))
        change = norms(jax.tree.map(jnp.subtract, p, p0))
        return {"losses": losses, "grad_norms": first,
                "change_norms": {k: float(v) for k, v in change.items()}}

"""moonlight-16b-a3b (moonshotai/Moonlight-16B-A3B, the DeepSeek-V3 block):
its plain reference, and the cell driver that runs the system under test
against it.

The reference is a float32 model in ``jax.numpy`` written from the
published equations (DeepSeek-V3, arXiv:2412.19437, §2.1.1-2.1.2),
importing nothing of the system under test:

* token embedding; per layer RMSNorm -> multi-head latent attention ->
  residual -> RMSNorm -> feed-forward -> residual; a final RMSNorm and an
  untied head; mean cross-entropy over every token of the vocabulary slice;
* attention: ``q = x W_q`` (no q LoRA), split into 128 dims without and 64
  with rotary positions; ``c = RMSNorm(x W_dkv)`` (rank 512), ``[k_nope,
  v] = c W_ukv``; ``k_rope = RoPE(x W_kr)`` shared by the 16 heads; causal
  softmax of ``(q_nope k_nope + q_rope k_rope) / sqrt(192)``, computed in
  blocks of at most 1024 query rows with an exact softmax per row;
* the first layer's feed-forward is a SwiGLU of width 11264; the others
  are a mixture of experts: ``s = sigmoid(x W_r)`` over the router's 64
  experts; the top 6 of ``s + b`` (the correction bias, used only to
  choose); gates the chosen ``s`` normalised to sum 1, times 2.446; the
  held experts (the first ``n_experts``) each a SwiGLU of width 1408
  computed densely over every token and weighted by its gate (zero where
  not chosen); the 2 shared experts as one SwiGLU of width 2816; what the
  absent experts would add is left out, as the program leaves it to the
  chips that hold them;
* the sequence-wise balance loss ``alpha * sum_i f_i P_i`` per sequence,
  averaged over the batch and summed over the expert layers, with
  ``f_i = E / (k S) * (tokens choosing i)`` and ``P_i`` the mean of the
  scores normalised over all E;
* gradients clipped to a global norm, AdamW as the traffic file states
  (``weight_decay_on``); after each step every router expert's bias moves
  by ``gamma * sign(mean load - load_i)`` over this batch's loads, with no
  gradient, moments, decay or clipping.

Departures, each because the program makes the same one: rotary positions
rotate halves, not HF's interleaved pairs (equal under random weights up
to a permutation of the rope dims); the vocabulary is a slice; the loads
that move the bias are this chip's, not the global batch's.

It makes its own weights and batches from the seed by the program's recipe
(normal draws of the same keys and scales; Zipf tokens from the same NumPy
streams, ``smollm-360m.batches``).  Weights are stored in the
configuration's dtype, the router, its bias and the norm scales in float32;
everything is computed in float32 at ``precision="highest"``.
``matmul="int8"`` is the control: every matmul operand, forward and
backward, rounded to int8 under one scale per tensor.
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path
from typing import Dict

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
from yardstick import spec  # noqa: E402

#: the seed recipe, the int8 control and the learning-rate schedule are
#: smollm-360m's
_base = spec.load_module(HERE / "smollm-360m.py")
seed_key, batches, stored = _base.seed_key, _base.batches, _base.stored
einsum_for, lr_at = _base.einsum_for, _base.lr_at

#: leaves kept in float32 (not rounded to the weights' dtype)
FLOAT32_LEAVES = ("ln1", "ln2", "kv_norm", "final_norm", "router")
#: the correction bias: state, not a gradient leaf
BIAS = "stack/pos0/ffn/router_bias"


def run(ctx):
    from yardstick import train_moe
    return train_moe.run_cell(ctx, sys.modules[__name__])


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def init(m: dict, key) -> Dict:
    """float32 copies of the initial weights as stored, keyed by their path
    in the program's parameter tree; the expert layers stacked."""
    import jax
    import jax.numpy as jnp
    d, h, n_layers = m["d_model"], m["n_heads"], m["n_layers"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r, dr, dv = m["kv_lora_rank"], m["qk_rope_head_dim"], m["v_head_dim"]
    out_scale = 1 / (2 * n_layers) ** 0.5

    def dense(k, d_in, d_out, scale=None):
        scale = scale if scale is not None else d_in ** -0.5
        return stored(m, jax.random.normal(k, (d_in, d_out), jnp.float32)
                      * scale)

    def swiglu(k, f):
        kf = jax.random.split(k, 3)
        return {"gate": dense(kf[0], d, f), "up": dense(kf[1], d, f),
                "down": dense(kf[2], f, d, f ** -0.5 * out_scale)}

    def mla(k):
        ks = jax.random.split(k, 7)
        return {"wq": dense(ks[0], d, h * qk),
                "wdkv": dense(ks[2], d, r),
                "kv_norm": jnp.ones((r,), jnp.float32),
                "wkr": dense(ks[3], d, dr),
                "wukv": dense(ks[4], r, h * (m["qk_nope_head_dim"] + dv)),
                "wo": dense(ks[5], h * dv, d, (h * dv) ** -0.5 * out_scale)}

    def moe(k):
        ks = jax.random.split(k, 4)
        e, f = m["n_experts"], m["d_expert"]
        return {
            "router": jax.random.normal(ks[0], (d, m["router_experts"]),
                                        jnp.float32) * 0.02,
            "router_bias": jnp.zeros((m["router_experts"],), jnp.float32),
            "experts": {
                "gate": dense(ks[1], d, e * f).reshape(d, e, f)
                .transpose(1, 0, 2),
                "up": dense(ks[2], d, e * f).reshape(d, e, f)
                .transpose(1, 0, 2),
                "down": dense(ks[3], e * f, d, f ** -0.5 * out_scale)
                .reshape(e, f, d)},
            "shared": swiglu(jax.random.fold_in(k, 7),
                             m["n_shared_experts"] * f)}

    def block(k, ffn):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((d,), jnp.float32), "mixer": mla(k1),
                "ln2": jnp.ones((d,), jnp.float32), "ffn": ffn(k2)}

    keys = jax.random.split(key, 8)
    n_moe = n_layers - m["first_k_dense"]
    tree = {
        "embed": stored(m, jax.random.normal(
            keys[0], (m["vocab"], d), jnp.float32) * 0.02),
        "head": dense(keys[1], d, m["vocab"]),
        "final_norm": jnp.ones((d,), jnp.float32),
        "prefix": [block(jax.random.fold_in(keys[2], i),
                         lambda k: swiglu(k, m["d_ff"]))
                   for i in range(m["first_k_dense"])],
        "stack": {"pos0": jax.vmap(lambda k: block(k, moe))(
            jax.random.split(jax.random.fold_in(keys[3], 0), n_moe))},
    }
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)] = leaf
    return flat


def _leaves(p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, d); rotate-half rotary positions."""
    import jax.numpy as jnp
    s, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, w, ein):
    import jax
    g = ein("sd,df->sf", x, w["gate"])
    u = ein("sd,df->sf", x, w["up"])
    return ein("sf,fd->sd", jax.nn.silu(g) * u, w["down"])


def _mla(x, w, m, ein):
    """x: (S, D) -> (S, D), causal, query blocks of at most 1024 rows."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    h, dn, dr, dv = (m["n_heads"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    q = ein("sd,de->se", x, w["wq"]).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])],
                        -1)
    c = _rmsnorm(ein("sd,dr->sr", x, w["wdkv"]), w["kv_norm"],
                 m["norm_eps"])
    kv = ein("sr,re->se", c, w["wukv"]).reshape(s, h, dn + dv)
    k_rope = _rope(ein("sd,de->se", x, w["wkr"])[:, None], m["rope_theta"])
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (s, h, dr))],
                        -1)
    v = kv[..., dn:]
    blk = min(s, 1024)
    assert s % blk == 0, (s, blk)

    @jax.checkpoint
    def rows(args):
        qb, pos = args                                       # (blk, h, dqk)
        logits = ein("qhd,khd->hqk", qb, k) * (dn + dr) ** -0.5
        logits = jnp.where(jnp.arange(s)[None, None] <= pos[None, :, None],
                           logits, -jnp.inf)
        return ein("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), v)

    out = jax.lax.map(rows, (q.reshape(s // blk, blk, h, dn + dr),
                             jnp.arange(s).reshape(s // blk, blk)))
    return ein("se,ed->sd", out.reshape(s, h * dv), w["wo"])


def _moe(x, w, bias, m, ein):
    """x: (S, D) -> (routed + shared (S, D), load (E,), balance loss)."""
    import jax
    import jax.numpy as jnp
    e, k, held = m["router_experts"], m["top_k"], m["n_experts"]
    scores = jax.nn.sigmoid(ein("sd,de->se", x, w["router"]))
    _, idx = jax.lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32)          # (S, k, E)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * m["routed_scale"]
    dense_gates = jnp.einsum("ske,sk->se", chosen, gates)[:, :held]
    ex = w["experts"]
    g = ein("sd,edf->esf", x, ex["gate"])
    u = ein("sd,edf->esf", x, ex["up"])
    routed = ein("esf,efd->esd", jax.nn.silu(g) * u, ex["down"])
    y = ein("esd,se->sd", routed, dense_gates) \
        + _swiglu(x, w["shared"], ein)
    load = chosen.sum((0, 1))
    f = load * e / (k * x.shape[0])
    pi = jnp.mean(scores / scores.sum(-1, keepdims=True), axis=0)
    return y, load, jnp.sum(f * pi)


def loss_seq(p, bias, inputs, labels, m: dict, alpha: float, ein):
    """One sequence: (summed cross-entropy, (loads (layers, E), balance
    loss summed over the expert layers))."""
    import jax
    import jax.numpy as jnp
    eps = m["norm_eps"]
    x = p["embed"][inputs]

    def layer(x, w, ffn):
        x = x + _mla(_rmsnorm(x, w["ln1"], eps), _leaves(w, "mixer/"), m, ein)
        return ffn(x, _rmsnorm(x, w["ln2"], eps))

    for i in range(m["first_k_dense"]):
        w = _leaves(p, f"prefix/{i}/")
        x = jax.checkpoint(lambda x, w: layer(
            x, w, lambda x, y: x + _swiglu(y, _leaves(w, "ffn/"), ein)))(x, w)

    def moe_layer(x, wb):
        w, b = wb
        out = {}

        def ffn(x, y):
            moe = _leaves(w, "ffn/")
            tree = {"router": moe["router"], "shared": _leaves(moe, "shared/"),
                    "experts": _leaves(moe, "experts/")}
            dy, out["load"], out["bal"] = _moe(y, tree, b, m, ein)
            return x + dy

        x = layer(x, w, ffn)
        return x, (out["load"], out["bal"])

    stack = _leaves(p, "stack/pos0/")
    x, (loads, bal) = jax.lax.scan(jax.checkpoint(moe_layer), x,
                                   (stack, bias))
    x = _rmsnorm(x, p["final_norm"], eps)
    logits = ein("sd,dv->sv", x, p["head"])
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)
    return ce, (loads, alpha * jnp.sum(bal))


@functools.lru_cache(maxsize=None)
def _step_fns(m_items: tuple, o_items: tuple, alpha: float, gamma: float,
              matmul: str):
    import jax
    import jax.numpy as jnp
    m, o = dict(m_items), dict(o_items)
    ein = einsum_for(matmul)

    @jax.jit
    def grads(p, bias, inputs, labels):
        n, rows = inputs.size, inputs.shape[0]

        def body(acc, xs):
            def obj(p):
                ce, (loads, bal) = loss_seq(p, bias, xs[0], xs[1], m, alpha,
                                            ein)
                return ce / n + bal / rows, (ce, loads)

            (_, (ce, loads)), g = jax.value_and_grad(obj, has_aux=True)(p)
            return jax.tree.map(jnp.add, acc, (ce, loads, g)), None

        zero = (jnp.zeros((), jnp.float32), jnp.zeros(bias.shape),
                jax.tree.map(jnp.zeros_like, p))
        (ce, loads, g), _ = jax.lax.scan(body, zero, (inputs, labels))
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(norm, 1e-9))
        return ce / n, jax.tree.map(lambda x: x * scale, g), loads

    @jax.jit
    def update(p, g, mu, nu, t, lr, bias, loads):
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
            if k.rsplit("/", 1)[-1] not in FLOAT32_LEAVES:
                new[k] = stored(m, new[k])
        bias = bias + gamma * jnp.sign(
            loads.mean(-1, keepdims=True) - loads)
        return new, mu, nu, bias

    return grads, update


def reference(m: dict, traffic: dict, seed: int, steps: int,
              matmul: str = "highest", keep_rows: int = 0) -> dict:
    """``steps`` training steps from the seed's weights on the seed's
    batches.  Returns each step's loss and copies routed to held experts
    (summed over the layers), the norm of each leaf's first (clipped)
    gradient, and the norm of each leaf's change after the last step, the
    bias's among them.  Sequences run one at a time; ``keep_rows`` > 0
    keeps only that many sequences of each batch (a fault reading).

    The gradients are computed on the accelerator; AdamW's moments and its
    update live on the host's CPU device, so that the accelerator holds
    only the weights, the gradient and the backward pass's temporaries."""
    import jax
    import jax.numpy as jnp
    o = traffic["optimizer"]
    data = batches(seed, traffic["batch"], traffic["seq"], m["vocab"],
                   traffic["zipf_alpha"], steps)
    grads, update = _step_fns(
        tuple(sorted((k, v) for k, v in m.items() if not isinstance(v, list))),
        tuple(sorted(o.items())), traffic["balance_alpha"],
        traffic["bias_speed"], matmul)
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in t.items()})
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    dev, cpu = jax.devices()[0], jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        host = jax.device_put(init(m, seed_key(seed)), cpu)
        bias = host.pop(BIAS)
        p0, bias0 = host, bias
        mu, nu = zeros(host), zeros(host)
        losses, rows_here, first = [], [], None
        for t, b in enumerate(data, start=1):
            inputs, labels = b["inputs"], b["labels"]
            if keep_rows:
                inputs, labels = inputs[:keep_rows], labels[:keep_rows]
            loss, g, loads = grads(jax.device_put(host, dev),
                                   jax.device_put(bias, dev),
                                   jnp.asarray(inputs), jnp.asarray(labels))
            if first is None:
                first = {k: float(v) for k, v in norms(g).items()}
            host, mu, nu, bias = update(
                host, jax.device_put(g, cpu), mu, nu, float(t), lr_at(t, o),
                bias, jax.device_put(loads, cpu))
            del g
            losses.append(float(loss))
            rows_here.append(float(jnp.sum(loads[:, :m["n_experts"]])))
        change = norms(dict(jax.tree.map(jnp.subtract, host, p0),
                            **{BIAS: bias - bias0}))
        return {"losses": losses, "rows_here": rows_here,
                "grad_norms": first,
                "change_norms": {k: float(v) for k, v in change.items()}}


def main(argv) -> int:
    """``python3 moonlight-16b-a3b.py JOB OUT``: the references a JSON job
    asks for (``model``, ``traffic``, ``seeds``, ``steps`` and keyword
    arguments of :func:`reference` under ``kw``), one per seed, written to
    OUT as JSON keyed by seed; with ``require_tpu`` exit 3 without a TPU.
    The cell's driver runs this in a child process before the program
    under test takes the chip."""
    import json

    import jax
    with open(argv[1]) as f:
        job = json.load(f)
    if job["require_tpu"] and jax.devices()[0].platform != "tpu":
        print(f"{argv[0]}: needs a TPU; JAX found "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 3
    out = {str(seed): reference(job["model"], job["traffic"], seed,
                                job["steps"], **job.get("kw", {}))
           for seed in job["seeds"]}
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

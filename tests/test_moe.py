"""The mixture-of-experts layer (``repro.models.moe``) and latent attention
without a low-rank query: held experts, dropless dispatch, the router's
selection, the correction bias's load rule, and the parameter count of the
moonlight-16b-a3b cut."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro.configs import get
from repro.launch.steps import make_train_step
from repro.models import layers, model_api, moe
from repro.models.module import param_count
from repro.optim.optimizers import adamw

SMOKE = get("moonlight_16b_a3b", smoke=True)


def _layer(cfg, seed=0):
    p = moe.moe_init(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.d_model))
    return p, x


def _dense_routed(p, x, idx, gates, cfg):
    """Every held expert over every token, weighted by its gate."""
    xf = x.reshape(-1, cfg.d_model)
    w = jnp.zeros((xf.shape[0], cfg.n_router)).at[
        jnp.arange(xf.shape[0])[:, None], idx].add(gates)[:, :cfg.n_experts]
    ex = p["experts"]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, ex["gate"])) \
        * jnp.einsum("td,edf->etf", xf, ex["up"])
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", h,
                                               ex["down"]), w)


def test_held_experts_compute_only_their_copies():
    cfg = SMOKE                      # 4 of 16 router experts held, top 3
    p, x = _layer(cfg)
    y, _, stats = moe.moe_apply(p, x, cfg)
    idx, gates, load, _ = moe.route(p, x, cfg)
    want = _dense_routed(p, x, idx, gates, cfg) \
        + layers.mlp_apply(p["shared"], x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               np.asarray(want), atol=1e-5, rtol=1e-5)
    held = np.asarray(idx) < cfg.n_experts
    assert int(stats["rows_here"]) == held.sum()
    assert int(stats["max_rows"]) == max(
        (np.asarray(idx) == e).sum() for e in range(cfg.n_experts))
    assert int(stats["dropped"]) == 0
    assert load.shape == (cfg.n_router,) and int(load.sum()) == idx.size


def test_dropless_under_skew_loses_no_copy():
    # a bias that sends every token to held experts 0, 1 and 2
    cfg = SMOKE
    p, x = _layer(cfg)
    p["router_bias"] = jnp.zeros(cfg.n_router).at[:3].set(100.0)
    y, _, stats = moe.moe_apply(p, x, cfg)
    t = x.shape[0] * x.shape[1]
    idx, gates, _, _ = moe.route(p, x, cfg)
    assert set(np.unique(np.asarray(idx))) == {0, 1, 2}
    assert int(stats["rows_here"]) == t * cfg.top_k
    assert int(stats["max_rows"]) == t and int(stats["dropped"]) == 0
    want = _dense_routed(p, x, idx, gates, cfg) \
        + layers.mlp_apply(p["shared"], x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("skew", [False, True])
def test_megablox_lowering_matches_ragged_dot(monkeypatch, skew):
    # the TPU's grouped matmul (megablox, interpreted here), whose unwritten
    # rows past the last group read NaN, gives the layer's output and
    # gradients as ragged_dot does, balanced and with every copy on three
    # held experts
    cfg = SMOKE
    p, x = _layer(cfg)
    if skew:
        p["router_bias"] = jnp.zeros(cfg.n_router).at[:3].set(100.0)
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def loss(p, x):
        return jnp.sum(moe.moe_apply(p, x, cfg)[0] * r)

    want = jax.value_and_grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(moe, "grouped_matmul", lambda a, w, s: moe._megablox(
        a, w, s, interpret=True))
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


def _no_bias(cfg):
    return None


def _held_bias(cfg):
    # every token to held experts 0, 1 and 2
    return jnp.zeros(cfg.n_router).at[:3].set(100.0)


def _absent_bias(cfg):
    # every token to absent experts: no copy is held
    e = cfg.n_experts
    return jnp.zeros(cfg.n_router).at[e:e + cfg.top_k].set(100.0)


@pytest.mark.parametrize("bias, remat", [
    (_no_bias, False), (_held_bias, False), (_absent_bias, False),
    (_no_bias, True)], ids=["uniform", "all_held", "none_held", "remat"])
def test_dropless_gradients_match_the_dense_reference(bias, remat):
    # the gathers both ways (and their custom gradients) against every held
    # expert over every token, in float32: the gradients of the input, the
    # router and the three expert weights
    cfg = SMOKE
    p, x = _layer(cfg)
    if bias(cfg) is not None:
        p["router_bias"] = bias(cfg)
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer(p, x):
        return moe.moe_apply(p, x, cfg)[0]

    def dense(p, x):
        idx, gates, _, _ = moe.route(p, x, cfg)
        xf = x.reshape(-1, cfg.d_model)
        return _dense_routed(p, x, idx, gates, cfg) \
            + layers.mlp_apply(p["shared"], xf)

    if remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)

    def grads(f):
        return jax.grad(lambda p, x: jnp.sum(f(p, x).reshape(r.shape) * r),
                        (0, 1))(p, x)

    (gp, gx), (wp, wx) = grads(layer), grads(dense)
    held = np.asarray(moe.route(p, x, cfg)[0]) < cfg.n_experts
    if bias is _held_bias:
        assert held.all()
    elif bias is _absent_bias:
        assert not held.any()
    else:
        assert held.any() and not held.all()
    pairs = [(gx, wx), (gp["router"], wp["router"])] + [
        (gp["experts"][w], wp["experts"][w]) for w in ("gate", "up", "down")]
    for got, want in pairs:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)
    if bias is _absent_bias:
        for w in ("gate", "up", "down"):
            assert not np.any(np.asarray(gp["experts"][w]))


def _slots(key, n, k):
    """A random order of n tokens' k copies, as ``_dropless`` gives it to
    the gathers: each sorted copy's slot-major place, and the inverse."""
    order = jax.random.permutation(key, n * k)
    slot = (order % k) * n + order // k
    return slot, jnp.zeros_like(slot).at[slot].set(jnp.arange(n * k))


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_copies_match_plain_indexing(k, dtype):
    # values and gradient of the custom-gradient gather against autodiff of
    # x[slot % n] (a scatter-add over each token's repeated row), masked to
    # the held copies; the cotangent of a copy not held holds NaN, as the
    # TPU's unwritten gradient rows may, and reaches no token
    n, d = 16, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
    slot, inv = _slots(jax.random.PRNGKey(1), n, k)
    held = jnp.ones((k, n), bool).at[0, :4].set(False)
    here = held.reshape(-1)[slot][:, None]
    nan_rows = inv.reshape(k, n)[0, :4]
    g = jax.random.normal(jax.random.PRNGKey(2), (n * k, d)).astype(dtype)

    def copies(x):
        return moe._copies(x, slot, inv, held)

    got, got_vjp = jax.vjp(copies, x)
    want, want_vjp = jax.vjp(
        lambda x: jnp.where(here, x.astype(jnp.float32)[slot % n], 0), x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want))
    assert not np.any(np.asarray(got, np.float32)[nan_rows])
    (gx,) = got_vjp(g.at[nan_rows].set(jnp.nan))
    (wx,) = want_vjp(g.astype(jnp.float32))
    assert gx.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(gx, np.float32), np.asarray(wx),
                               atol=tol, rtol=tol)
    check_grads(lambda x: moe._copies(x, slot, inv, held),
                (x.astype(jnp.float32),), order=1, modes=["rev"])


@pytest.mark.parametrize("k", [1, 3])
def test_combine_matches_plain_indexing(k):
    # the weighted sum over each token's held copies, and its gradients in
    # the results and the weights, against autodiff of plain indexing; a
    # copy not held reads a row that holds NaN, as the TPU's unwritten rows
    # may, and gives nothing and takes a gradient of 0; a held copy of
    # weight 0 still takes its weight's gradient
    n, d = 16, 8
    slot, inv = _slots(jax.random.PRNGKey(1), n, k)
    ys = jax.random.normal(jax.random.PRNGKey(0), (n * k, d))
    w = jax.random.uniform(jax.random.PRNGKey(3), (k, n)).at[-1, 5].set(0.0)
    held = jnp.ones((k, n), bool).at[0, :4].set(False)
    nan_rows = inv.reshape(k, n)[0, :4]
    g = jax.random.normal(jax.random.PRNGKey(2), (n, d))

    def plain(ys, w):
        back = ys[inv].reshape(k, n, d)
        return jnp.sum(back * (w * held)[..., None], axis=0)

    def combine(ys, w):
        return moe._combine(ys, w, held, slot, inv)

    got, got_vjp = jax.vjp(combine, ys.at[nan_rows].set(jnp.nan), w)
    want, want_vjp = jax.vjp(plain, ys.at[nan_rows].set(0.0), w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    g_ys, g_w = got_vjp(g)
    assert not np.any(np.asarray(g_ys)[nan_rows])
    assert np.asarray(g_w)[-1, 5] != 0
    check_grads(combine, (ys, w), order=1, modes=["rev"])


def test_bias_chooses_but_does_not_gate():
    cfg = SMOKE
    p, x = _layer(cfg)
    idx0, gates0, _, _ = moe.route(p, x, cfg)
    p["router_bias"] = jnp.zeros(cfg.n_router).at[5].set(100.0)
    idx, gates, _, _ = moe.route(p, x, cfg)
    assert np.all(np.any(np.asarray(idx) == 5, axis=-1))
    # gates: the chosen sigmoid scores without the bias, normalised, scaled
    s = jax.nn.sigmoid(x.reshape(-1, cfg.d_model) @ p["router"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                      * cfg.routed_scale), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates0.sum(-1)), cfg.routed_scale,
                               rtol=1e-6)


def test_group_limited_selection_by_hand():
    # 8 experts in 4 groups of 2; keep the 2 groups whose two best scores
    # sum highest: group 1 (0.5 + 0.45) and group 0 (0.9 + 0.0), not group
    # 2 (0.6 + 0.1); then the top 2 of experts 0-3
    cfg = dataclasses.replace(SMOKE, n_experts=8, router_experts=8, top_k=2,
                              n_group=4, topk_group=2)
    choice = jnp.array([[0.9, 0.0, 0.5, 0.45, 0.6, 0.1, 0.3, 0.3]])
    assert sorted(np.asarray(moe.select(choice, cfg))[0]) == [0, 2]
    ungrouped = dataclasses.replace(cfg, n_group=1, topk_group=1)
    assert sorted(np.asarray(moe.select(choice, ungrouped))[0]) == [0, 4]


def test_bias_update_rule_on_given_loads():
    cfg = dataclasses.replace(SMOKE, router_bias_speed=0.01)
    old = {"stack": {"pos0": {"ffn": {"router_bias": jnp.array(
        [[0.5, 0.0, -0.5, 0.0], [0.0, 0.0, 0.0, 0.0]])}}}}
    # whatever the optimizer made of the bias is replaced
    new = jax.tree.map(lambda b: b * 0.9 + 7.0, old)
    loads = {"stack/pos0": jnp.array([[3, 1, 2, 2], [0, 8, 0, 0]])}
    got = moe.update_router_bias(new, old, loads, cfg)
    np.testing.assert_allclose(
        np.asarray(got["stack"]["pos0"]["ffn"]["router_bias"]),
        [[0.49, 0.01, -0.5, 0.0], [0.01, -0.01, 0.01, 0.01]], atol=1e-7)


def test_train_step_moves_the_bias_by_its_rule_only():
    cfg = SMOKE
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-3)
    state = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"inputs": toks, "labels": toks}
    new, state, met = jax.jit(make_train_step(cfg, opt))(params, state,
                                                         batch)
    step = np.asarray(new["stack"]["pos0"]["ffn"]["router_bias"]) \
        / cfg.router_bias_speed
    np.testing.assert_allclose(step, np.round(step), atol=1e-4)
    assert np.abs(step).max() == pytest.approx(1.0)
    assert not np.any(np.asarray(state["mu"]["stack"]["pos0"]["ffn"]
                                 ["router_bias"]))
    assert int(met["moe_dropped"]) == 0
    assert 0 < int(met["moe_rows_here"]) <= 2 * 32 * cfg.top_k * cfg.n_periods
    assert "moe_load" not in met


@pytest.mark.parametrize("q_chunk", [16, 64])
def test_mla_without_q_lora(q_chunk):
    # a direct query projection, and causal attention by chunks of queries
    # (each recomputed in the backward pass) equal to one call over all
    cfg = dataclasses.replace(SMOKE, q_chunk=q_chunk, kv_chunk=16)
    p = layers.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert "wq" in p and not {"wdq", "wuq", "q_norm"} & set(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    pos = jnp.arange(64)

    def loss(p, x, cfg):
        return jnp.sum(layers.mla_apply(p, x, cfg, pos) ** 2)

    whole = dataclasses.replace(cfg, q_chunk=64, kv_chunk=64)
    got = jax.value_and_grad(loss)(p, x, cfg)
    want = jax.value_and_grad(loss)(p, x, whole)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)
    # by hand: q from wq; a query attends to itself alone at position 0
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    q = (x[:, :1] @ p["wq"]).reshape(2, 1, cfg.n_heads, qk)
    assert q.shape == (2, 1, 4, qk)
    c = layers.rmsnorm(x[:, :1] @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
    v = (c @ p["wukv"]).reshape(2, 1, cfg.n_heads, -1)[..., 16:]
    first = v.reshape(2, -1) @ p["wo"]
    np.testing.assert_allclose(
        np.asarray(layers.mla_apply(p, x, cfg, pos)[:, 0]),
        np.asarray(first), atol=1e-5, rtol=1e-5)


def test_param_count_of_the_moonlight_cut_by_hand():
    cfg = dataclasses.replace(get("moonlight_16b_a3b"), n_layers=5,
                              n_experts=8, router_experts=64, vocab=20480)
    d, h = 2048, 16
    mla = (d * h * 192 + d * 512 + d * 64 + 512 * h * 256 + h * 128 * d
           + 512)                                   # + kv norm
    assert mla == 13_763_072
    norms = 2 * d
    dense = mla + 3 * d * 11264 + norms
    expert = 3 * d * 1408
    moe_layer = mla + 8 * expert + 2 * expert + d * 64 + 64 + norms
    total = dense + 4 * moe_layer + 2 * 20480 * d + d
    assert total == 568_484_608                     # 568.5M
    assert cfg.param_count()[0] == total
    shapes = jax.eval_shape(lambda: model_api(cfg).init(
        jax.random.PRNGKey(0), cfg))
    assert param_count(shapes) == total

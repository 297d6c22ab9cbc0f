"""The moonlight-16b-a3b training cell on the CPU, at a tiny float32 size:
the program against the plain reference (the loss and every leaf's
gradient through ``lm_loss``, and a run that reads ``correct``); the int8
control, a step that returns its state unchanged and a step that leaves
out half of the batch fail the run's checks; the held shares of a layer
add up to the uncut layer; the FLOP and byte counts, and the new metrics'
readers."""
import dataclasses
import json

import pytest

from yardstick import mla_moe, spec, train_moe

CELL = "moonlight-16b-a3b.train-s8192"
TINY_MODEL = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
                  vocab=256, n_experts=4, router_experts=16, top_k=3,
                  d_expert=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, dtype="float32")
TINY = dict(batch=2, seq=64)


def failed(out):
    return [c.name for c in out.checks if not c.ok]


@pytest.fixture(scope="module")
def ref():
    return spec.resolve(CELL).driver()


def _tiny():
    cell = spec.resolve(CELL)
    return dict(cell.config["model"], **TINY_MODEL), dict(cell.traffic,
                                                          **TINY)


def test_program_matches_reference(run_tiny):
    out = run_tiny(CELL, seconds=0.5, model=TINY_MODEL, **TINY)
    assert out.correct, out.checks
    assert out.counters["steps"] >= 1 and out.counters["dropped"] == 0
    prog, want = out.counters["program"], out.counters["reference"]
    assert prog["rows_here"] == want["rows_here"]
    # the bias moved by its load rule alike in both
    bias = "stack/pos0/ffn/router_bias"
    assert prog["change_norms"][bias] == pytest.approx(
        want["change_norms"][bias], rel=1e-6)
    assert prog["change_norms"][bias] > 0


def test_loss_and_gradients_through_lm_loss(ref):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model_api
    from yardstick.train_lm import _path

    m, tr = _tiny()
    cfg = train_moe.model_config(m, tr)
    key = jnp.asarray(ref.seed_key(7))
    params = model_api(cfg).init(key, cfg)
    b = ref.batches(7, 2, 64, m["vocab"], tr["zipf_alpha"], 1)[0]
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    (loss, _), grads = jax.value_and_grad(
        lambda p: model_api(cfg).loss(p, batch, cfg), has_aux=True)(params)
    got = {_path(p): x for p, x in
           jax.tree_util.tree_flatten_with_path(grads)[0]}

    p = ref.init(m, key)
    bias = p.pop(ref.BIAS)
    ein = ref.einsum_for("highest")

    def total(p):
        out = 0.0
        for i in range(2):
            ce, (_, bal) = ref.loss_seq(p, bias, batch["inputs"][i],
                                        batch["labels"][i], m,
                                        tr["balance_alpha"], ein)
            out = out + ce / batch["inputs"].size + bal / 2
        return out

    want_loss, want = jax.value_and_grad(total)(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert set(want) == set(got) - {ref.BIAS}
    assert not np.any(np.asarray(got[ref.BIAS]))      # state, no gradient
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-4, atol=1e-6, err_msg=k)


def test_int8_control_fails(ref):
    m, tr = _tiny()
    want = ref.reference(m, tr, 3, 3)
    got = ref.reference(m, tr, 3, 3, matmul="int8")
    assert any(not c.ok for c in train_moe.compare(got, want, tr["limits"]))


def _broken_step(monkeypatch, wrap):
    import repro.launch.steps as steps
    make = steps.make_train_step
    monkeypatch.setattr(steps, "make_train_step",
                        lambda cfg, opt, **kw: wrap(make(cfg, opt, **kw)))


def test_state_left_unchanged_is_not_correct(run_tiny, monkeypatch):
    def unchanged(step):
        def f(params, opt_state, batch):
            _, _, met = step(params, opt_state, batch)
            return params, opt_state, met
        return f
    _broken_step(monkeypatch, unchanged)
    out = run_tiny(CELL, seconds=0.2, model=TINY_MODEL, **TINY)
    assert "change_norm_gap" in failed(out)


def test_half_batch_left_out_is_not_correct(run_tiny, monkeypatch):
    def half(step):
        def f(params, opt_state, batch):
            n = batch["inputs"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()})
        return f
    _broken_step(monkeypatch, half)
    out = run_tiny(CELL, seconds=0.2, model=TINY_MODEL, **TINY)
    assert "rows_here_gap" in failed(out)


def test_held_shares_add_up_to_the_uncut_layer(ref):
    # 64 router experts over 4 shards of 16: each shard holds its 16 as
    # experts [0, 16) (the router's columns rotated to put them first),
    # routes over all 64 and computes its part; the parts, with the shared
    # experts counted once, are the reference's layer holding all 64
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import moe

    m = dict(_tiny()[0], router_experts=64, n_experts=64, top_k=6)
    cfg = dataclasses.replace(train_moe.model_config(m, _tiny()[1]),
                              n_experts=16)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    d, f = m["d_model"], m["d_expert"]
    w = {"router": jax.random.normal(ks[0], (d, 64)) * 0.3,
         "experts": {"gate": jax.random.normal(ks[1], (64, d, f)) * 0.1,
                     "up": jax.random.normal(ks[2], (64, d, f)) * 0.1,
                     "down": jax.random.normal(ks[3], (64, f, d)) * 0.1},
         "shared": {"gate": jax.random.normal(ks[4], (d, 2 * f)) * 0.1,
                    "up": jax.random.normal(ks[5], (d, 2 * f)) * 0.1,
                    "down": jax.random.normal(ks[6], (2 * f, d)) * 0.1}}
    bias = jax.random.normal(ks[7], (64,)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (48, d))
    with jax.default_matmul_precision("highest"):
        want, want_load, _ = ref._moe(x, w, bias, m,
                                      ref.einsum_for("highest"))
        total, shared = 0.0, None
        for s in range(4):
            roll = lambda a: jnp.roll(a, -16 * s, axis=-1)  # noqa: E731
            p = {"router": roll(w["router"]), "router_bias": roll(bias),
                 "experts": {k: v[16 * s:16 * (s + 1)]
                             for k, v in w["experts"].items()},
                 "shared": w["shared"]}
            y, _, stats = moe.moe_apply(p, x[None], cfg)
            total = total + y[0]
            assert int(stats["load"].sum()) == 48 * 6
            np.testing.assert_array_equal(np.asarray(stats["load"]),
                                          np.asarray(roll(want_load)))
        from repro.models.layers import mlp_apply
        shared = mlp_apply(w["shared"], x)
        np.testing.assert_allclose(np.asarray(total - 3 * shared),
                                   np.asarray(want), atol=1e-4, rtol=1e-4)


def test_flops_of_the_cut_by_hand():
    with open(spec.HERE / "configs" / "moonlight-16b-a3b.json") as f:
        m = json.load(f)["model"]
    d, h = 2048, 16
    mla = d * h * 192 + d * 512 + d * 64 + 512 * h * 256 + h * 128 * d
    assert mla == 13_762_560
    every = 5 * mla + 3 * d * 11264 + 4 * (d * 64 + 3 * d * 2816) \
        + d * 20480
    per_copy = 3 * d * 1408
    # 6 of 64 experts a token, 8 held: 0.75 copies a layer, 3 over the 4
    attn = 3 * 5 * 8192 * 16 * (192 + 128)
    want = 6 * (every + 3 * per_copy) + attn
    assert mla_moe.train_flops_per_token(m, 8192, 3.0) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(2.28e9, rel=2e-3)
    flops, nbytes = mla_moe.grouped_swiglu_cost(49152, m)
    assert flops == 4 * 3 * 2 * 49152 * d * 1408
    assert nbytes == 4 * 2 * (3 * 49152 * (d + 1408)
                              + 3 * 4 * 8 * d * 1408)


class _Out:
    """A traced run's outcome, as the readers see it."""

    def __init__(self, experts_s, steps):
        self.trace = {"busy_s": 1.0}
        self.device = {"kind": "TPU v5 lite"}
        self.end_to_end = {"train_tokens_per_s": 10_000.0}
        self.counters = {
            "scopes": {"busy_s": 1.0, "unscoped_s": 0.0,
                       "scopes": {"experts": experts_s, "router": 0.004}},
            "rows_here_traced": [49152.0] * steps,
            "rows_here_window": [49152.0, 49150.0],
            "tokens_per_step": 16384}


def test_new_readers():
    cell = spec.resolve(CELL)
    out = _Out(experts_s=0.4, steps=cell.traffic["trace_steps"])
    m = cell.config["model"]
    flops, nbytes = mla_moe.grouped_swiglu_cost(49152, m)
    least = max(flops / 197e12, nbytes / 819e9)
    assert spec.metric_reader("experts_roofline_pct.train")(out, cell) == \
        pytest.approx(100 * least / 0.1)
    assert spec.metric_reader("experts_ms.train")(out, cell) == \
        pytest.approx(100.0)
    assert spec.metric_reader("router_ms.train")(out, cell) == \
        pytest.approx(1.0)
    # a program without the scope: no number, no error
    assert spec.metric_reader("dispatch_ms.train")(out, cell) is None
    fpt = mla_moe.train_flops_per_token(m, 8192, 49151.0 / 16384)
    assert spec.metric_reader("train_mfu.mla_moe")(out, cell) == \
        pytest.approx(100 * 10_000 * fpt / 197e12)

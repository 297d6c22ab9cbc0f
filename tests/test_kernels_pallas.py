"""Pallas kernels (interpret=True on CPU) vs pure-jnp oracles.

Shape/dtype sweeps per the brief; hypothesis drives randomised GQA/window
combinations for the attention kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 1, 64, 128, 32),          # MQA, q shorter than kv
    (1, 8, 2, 128, 128, 128),        # GQA 4:1
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (False, None), (True, 48),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_matches_ref(b, hq, hkv, sq, skv, d, causal, window,
                                  dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    offset = skv - sq
    q = rand(ks[0], (b, hq, sq, d), dtype)
    k = rand(ks[1], (b, hkv, skv, d), dtype)
    v = rand(ks[2], (b, hkv, skv, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 offset=offset, q_blk=32, kv_blk=32)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window,
                            offset=offset)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([1, 2]), st.sampled_from([1, 2, 4]),
       st.sampled_from([64, 128]), st.booleans(),
       st.sampled_from([None, 32, 64]))
def test_flash_pallas_hypothesis_sweep(b, group, s, causal, window):
    hkv = 2
    hq = hkv * group
    d = 32
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = rand(ks[0], (b, hq, s, d))
    k = rand(ks[1], (b, hkv, s, d))
    v = rand(ks[2], (b, hkv, s, d))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_blk=32, kv_blk=32)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5,
                               rtol=3e-5)


def _grads(fn, q, k, v, ct):
    """Gradients of <fn(q, k, v), ct> with respect to q, k and v."""
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (b, hq, hkv, sq, skv, d, causal, offset): the training calls the splash
# kernel takes, at tiny sizes
SPLASH_CALLS = {
    "gqa_causal": (1, 6, 2, 256, 256, 64, True, 0),          # smollm-like
    "causal_offset_chunk": (1, 2, 2, 128, 384, 64, True, 256),  # MLA chunk
    "non_causal": (2, 4, 2, 128, 256, 64, False, 0),
    "head_dim_192": (1, 2, 2, 128, 256, 192, True, 128),     # MLA widths
}


@pytest.mark.parametrize("call", sorted(SPLASH_CALLS))
def test_splash_kernel_matches_ref(call):
    """The kernel path, interpreted, against the oracle: outputs and the
    q, k and v gradients, bf16 operands."""
    b, hq, hkv, sq, skv, d, causal, offset = SPLASH_CALLS[call]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = rand(ks[0], (b, hq, sq, d), jnp.bfloat16)
    k = rand(ks[1], (b, hkv, skv, d), jnp.bfloat16)
    v = rand(ks[2], (b, hkv, skv, d), jnp.bfloat16)
    ct = rand(ks[3], (b, hq, sq, d))
    scale = d ** -0.5

    def kernel(q, k, v):
        return ops._flash_splash(q, k, v, causal, offset, scale,
                                 interpret=True)

    def oracle(q, k, v):
        return ref.attention_ref(q, k, v, causal, None, offset, scale)

    np.testing.assert_allclose(np.asarray(kernel(q, k, v), np.float32),
                               np.asarray(oracle(q, k, v), np.float32),
                               atol=2e-2, rtol=2e-2)
    for g, e in zip(_grads(kernel, q, k, v, ct), _grads(oracle, q, k, v, ct)):
        g, e = np.asarray(g, np.float32), np.asarray(e, np.float32)
        np.testing.assert_allclose(g, e, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(e).max()))


def _cp_call(q, k, v):
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    return ops.cp_flash_attention(q, k, v, mesh, causal=True)


# (q shape, kv shape, call): which path each caller's call takes
ATTENTION_PATHS = {
    "train_gqa": ((2, 6, 256, 64), (2, 2, 256, 64),
                  lambda q, k, v: ops.flash_attention(q, k, v), "kernel"),
    "mla_chunk": ((1, 4, 128, 192), (1, 4, 384, 192),
                  lambda q, k, v: ops.flash_attention(q, k, v, offset=256),
                  "kernel"),
    "encoder_1500": ((1, 4, 1500, 64), (1, 4, 1500, 64),
                     lambda q, k, v: ops.flash_attention(q, k, v,
                                                         causal=False),
                     "blockwise"),
    "prefill_ragged": ((1, 4, 200, 64), (1, 4, 200, 64),
                       lambda q, k, v: ops.flash_attention(q, k, v),
                       "blockwise"),
    "sliding_window": ((1, 4, 256, 64), (1, 4, 256, 64),
                       lambda q, k, v: ops.flash_attention(q, k, v,
                                                           window=128),
                       "blockwise"),
    "kv_valid_lo_ring": ((1, 4, 256, 64), (1, 4, 256, 64), _cp_call,
                         "blockwise"),
}


@pytest.mark.parametrize("call", sorted(ATTENTION_PATHS))
def test_attention_call_takes_its_path(call):
    """The trace-time counts say which path a call takes: a window, a
    length that is not a multiple of 128 or a context-parallel ring bound
    stays blockwise; the training calls can take the kernel."""
    qs, ks, fn, want = ATTENTION_PATHS[call]
    other = {"kernel": "blockwise", "blockwise": "kernel"}[want]
    before = obs.counts()
    jax.eval_shape(fn, *(jax.ShapeDtypeStruct(s, jnp.bfloat16)
                         for s in (qs, ks, ks)))
    after = obs.counts()
    delta = {n: after.get(f"attention.{n}", 0)
             - before.get(f"attention.{n}", 0) for n in (want, other)}
    assert delta == {want: 1, other: 0}


@pytest.mark.parametrize("with_cpu_mesh", [False, True])
def test_kernel_choice_waits_for_lowering_without_tpu_devices(with_cpu_mesh):
    """With no mesh, or a mesh of CPU devices, an eligible call stages both
    paths behind lax.platform_dependent: the platform is chosen when the
    program is lowered, so a CPU lowering gets the blockwise path."""
    from repro.launch.mesh import make_mesh
    kw = ({"mesh": make_mesh((1, 1), ("data", "model")),
           "batch_axes": ("data",)} if with_cpu_mesh else {})
    s = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(
        q, k, v, **kw).astype(jnp.float32)), argnums=(0, 1, 2))
    traced = jax.jit(grad).trace(s, s, s)
    assert "platform_index" in str(traced.jaxpr)
    assert "tpu_custom_call" not in traced.lower().as_text()


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 4, 256, 64),
    (3, 8, 2, 128, 32),
    (1, 16, 1, 512, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_pallas_matches_ref(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = rand(ks[0], (b, hq, d), dtype)
    k = rand(ks[1], (b, hkv, s, d), dtype)
    v = rand(ks[2], (b, hkv, s, d), dtype)
    length = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention_pallas(q, k, v, length=length, kv_blk=64)
    exp = ref.decode_attention_ref(q, k, v, length=length)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("rows,d", [(64, 128), (100, 256), (8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_matches_ref(rows, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = rand(ks[0], (rows, d), dtype)
    s = rand(ks[1], (d,))
    out = rmsnorm_pallas(x, s, rows_blk=32)
    exp = ref.rmsnorm_ref(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("bt,t,d_in,n,d_blk", [
    (2, 16, 64, 8, 32),
    (1, 32, 128, 16, 64),
    (3, 8, 32, 4, 32),
])
def test_mamba_pallas_matches_ref(bt, t, d_in, n, d_blk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    u = rand(ks[0], (bt, t, d_in))
    dt = jax.nn.softplus(rand(ks[1], (bt, t, d_in)))
    A = -jax.nn.softplus(rand(ks[2], (d_in, n)))
    B = rand(ks[3], (bt, t, n))
    C = rand(ks[4], (bt, t, n))
    D = jnp.ones((d_in,))
    y, hT = mamba_scan_pallas(u, dt, A, B, C, D, d_blk=d_blk)
    y_ref, h_ref = ref.mamba_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref), atol=1e-4,
                               rtol=1e-4)


def test_mamba_pallas_carries_initial_state():
    bt, t, d_in, n = 1, 8, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    u = rand(ks[0], (bt, t, d_in))
    dt = jax.nn.softplus(rand(ks[1], (bt, t, d_in)))
    A = -jax.nn.softplus(rand(ks[2], (d_in, n)))
    B = rand(ks[3], (bt, t, n))
    C = rand(ks[4], (bt, t, n))
    D = jnp.ones((d_in,))
    h0 = rand(ks[5], (bt, d_in, n))
    y, hT = mamba_scan_pallas(u, dt, A, B, C, D, h0=h0, d_blk=32)
    y_ref, h_ref = ref.mamba_scan_ref(u, dt, A, B, C, D, h0=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref), atol=1e-4)

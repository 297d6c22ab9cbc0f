"""Blockwise-jnp kernel paths vs. naive oracles (shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


SHAPES = [
    (1, 4, 4, 64, 64, 32),
    (2, 8, 2, 128, 128, 64),       # GQA 4:1
    (1, 4, 1, 64, 256, 32),        # MQA, kv longer than q (prefill tail)
]
MASKS = [(True, None), (False, None), (True, 32)]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", SHAPES)
@pytest.mark.parametrize("causal,window", MASKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_jnp_matches_ref(b, hq, hkv, sq, skv, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    offset = skv - sq
    q = rand(ks[0], (b, hq, sq, d), dtype)
    k = rand(ks[1], (b, hkv, skv, d), dtype)
    v = rand(ks[2], (b, hkv, skv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              offset=offset, q_chunk=32, kv_chunk=64)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window,
                            offset=offset)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def _grads(fn, q, k, v, ct):
    """Gradients of <fn(q, k, v), ct> with respect to q, k and v."""
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_grads_close(got, exp, tol):
    for g, e in zip(got, exp):
        g, e = np.asarray(g, np.float32), np.asarray(e, np.float32)
        np.testing.assert_allclose(g, e, rtol=tol,
                                   atol=tol * float(np.abs(e).max()))


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", SHAPES + [
    (1, 4, 2, 48, 100, 32),        # ragged kv: padded columns masked
])
@pytest.mark.parametrize("causal,window", MASKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_jnp_grad_matches_ref(b, hq, hkv, sq, skv, d, causal, window,
                                    dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    offset = skv - sq
    q = rand(ks[0], (b, hq, sq, d), dtype)
    k = rand(ks[1], (b, hkv, skv, d), dtype)
    v = rand(ks[2], (b, hkv, skv, d), dtype)
    ct = rand(ks[3], (b, hq, sq, d))
    got = _grads(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=window, offset=offset,
        q_chunk=32, kv_chunk=64), q, k, v, ct)
    exp = _grads(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=causal, window=window, offset=offset), q, k, v, ct)
    _assert_grads_close(got, exp, 2e-2 if dtype == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("window", [None, 48])
def test_flash_jnp_grad_with_kv_valid_lo(window):
    """The traced lower bound of the context-parallel ring: columns below
    it get no gradient, the rest match the oracle on the kept columns."""
    b, h, hkv, sq, skv, d, lo = 1, 4, 2, 64, 128, 32, 40
    offset = skv - sq
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = rand(ks[0], (b, h, sq, d))
    k = rand(ks[1], (b, hkv, skv, d))
    v = rand(ks[2], (b, hkv, skv, d))
    ct = rand(ks[3], (b, h, sq, d))
    got = _grads(lambda q, k, v: ops._flash_jnp(
        q, k, v, True, window, offset, d ** -0.5, 32, 32,
        kv_valid_lo=jnp.int32(lo)), q, k, v, ct)
    dq, dk, dv = _grads(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=True, window=window, offset=offset - lo),
        q, k[:, :, lo:], v[:, :, lo:], ct)
    zeros = jnp.zeros((b, hkv, lo, d))
    exp = (dq, jnp.concatenate([zeros, dk], 2),
           jnp.concatenate([zeros, dv], 2))
    _assert_grads_close(got, exp, 2e-5)


def test_flash_jnp_grad_keeps_no_max_location_mask():
    """The running max is held constant in the backward, so the gradient
    program builds no ``argmax`` location mask (an EQ compare yielding a
    pred of the score-block shape) for ``logits.max``."""
    b, h, hkv, s, d, chunk = 1, 4, 2, 128, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = rand(ks[0], (b, h, s, d))
    k = rand(ks[1], (b, hkv, s, d))
    v = rand(ks[2], (b, hkv, s, d))
    attn = jax.checkpoint(lambda q, k, v: ops._flash_jnp(
        q, k, v, True, None, 0, d ** -0.5, chunk, chunk))
    loss = lambda q, k, v: jnp.sum(attn(q, k, v))
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    block = f"pred[{b},{hkv},{h // hkv},{chunk},{chunk}]"
    masks = [ln for ln in hlo.splitlines()
             if block in ln and "compare(" in ln and "direction=EQ" in ln]
    assert not masks, f"{len(masks)} max-location masks, e.g. {masks[0]}"


def test_flash_jnp_block_skipping_reduces_flops():
    """Causal block skipping must show up in compiled FLOPs (~2x saving)."""
    b, h, s, d = 1, 4, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (rand(ks[i], (b, h, s, d)) for i in range(3))

    def cost(causal):
        fn = lambda q, k, v: ops.flash_attention(
            q, k, v, causal=causal, q_chunk=64, kv_chunk=64)
        return jax.jit(fn).lower(q, k, v).compile().cost_analysis()["flops"]

    assert cost(True) < 0.65 * cost(False)


def test_decode_attention_matches_ref_lengths():
    b, hq, hkv, s, d = 4, 8, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (b, hq, d))
    k = rand(ks[1], (b, hkv, s, d))
    v = rand(ks[2], (b, hkv, s, d))
    length = jnp.array([128, 64, 1, 100], jnp.int32)
    out = ops.decode_attention(q, k, v, length=length)
    exp = ref.decode_attention_ref(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_matches_ref(dtype):
    bt, t, d_in, n = 2, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    u = rand(ks[0], (bt, t, d_in), dtype)
    dt = jax.nn.softplus(rand(ks[1], (bt, t, d_in), dtype))
    A = -jax.nn.softplus(rand(ks[2], (d_in, n)))
    B = rand(ks[3], (bt, t, n), dtype)
    C = rand(ks[4], (bt, t, n), dtype)
    D = jnp.ones((d_in,))
    y, h = ops.mamba_scan(u, dt, A, B, C, D)
    y_ref, h_ref = ref.mamba_scan_ref(u, dt, A, B, C, D)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4)


def test_mamba_step_consistent_with_scan():
    bt, t, d_in, n = 2, 8, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    u = rand(ks[0], (bt, t, d_in))
    dt = jax.nn.softplus(rand(ks[1], (bt, t, d_in)))
    A = -jax.nn.softplus(rand(ks[2], (d_in, n)))
    B = rand(ks[3], (bt, t, n))
    C = rand(ks[4], (bt, t, n))
    D = jnp.ones((d_in,))
    y_scan, h_scan = ops.mamba_scan(u, dt, A, B, C, D)
    h = jnp.zeros((bt, d_in, n), jnp.float32)
    ys = []
    for i in range(t):
        y, h = ops.mamba_step(u[:, i], dt[:, i], A, B[:, i], C[:, i], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_scan), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_scan), atol=1e-5)


def test_rmsnorm_shapes_dtypes():
    for shape in [(4, 8), (2, 16, 32)]:
        for dtype in [jnp.float32, jnp.bfloat16]:
            x = rand(jax.random.PRNGKey(0), shape, dtype)
            s = jnp.ones(shape[-1])
            out = ops.rmsnorm(x, s)
            assert out.shape == shape and out.dtype == dtype


def test_cp_flash_attention_matches_ref():
    """Ring context-parallel attention == naive oracle (1-device mesh uses
    the same code path structure; multi-shard covered by the dry-run)."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    b, h, s, d = 2, 4, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (rand(ks[i], (b, h, s, d)) for i in range(3))
    for window in [None, 48]:
        out = ops.cp_flash_attention(q, k, v, mesh, causal=True,
                                     window=window, q_chunk=32, kv_chunk=32)
        exp = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

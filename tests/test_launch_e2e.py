"""End-to-end launch-layer tests: train/resume determinism, batched serving,
and a real dry-run cell in a 512-device subprocess."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.launch.serve import Request, serve_batch
from repro.launch.train import train
from repro.models import model_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaves_allclose(a, b, tol=1e-5):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=tol,
                                   rtol=tol)


def test_train_resume_is_deterministic(tmp_path):
    """crash/restart mid-run == uninterrupted run (fault tolerance)."""
    d1 = str(tmp_path / "run_ab")
    out_a = train("smollm_360m", steps=6, batch=2, seq=32,
                  ckpt_dir=d1, ckpt_every=3, log_every=100)
    # second process: resume from step 3's checkpoint... simulate by a fresh
    # train() pointed at a dir holding only the step-3 checkpoint
    d2 = str(tmp_path / "run_b")
    train("smollm_360m", steps=3, batch=2, seq=32,
          ckpt_dir=d2, ckpt_every=3, log_every=100)
    out_b = train("smollm_360m", steps=6, batch=2, seq=32,
                  ckpt_dir=d2, ckpt_every=3, log_every=100)
    _leaves_allclose(out_a["params"], out_b["params"], tol=5e-3)


def test_serve_batch_generates():
    cfg = get("h2o_danube_1_8b", smoke=True)
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, 4, dtype=np.int32), 6)
            for i in range(3)]
    reqs, dt = serve_batch(cfg, params, reqs, max_len=16)
    for r in reqs:
        assert r.out.shape == (6,)
        assert np.all((0 <= r.out) & (r.out < cfg.vocab))


def test_serve_greedy_matches_decode_loop():
    """serve_batch's generation equals a hand-rolled greedy loop."""
    cfg = get("smollm_360m", smoke=True)
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(1), cfg)
    prompt = np.array([5, 9, 2], np.int32)
    reqs, _ = serve_batch(cfg, params, [Request(0, prompt, 4)], max_len=16)
    # manual loop
    cache = api.init_cache(cfg, 1, max_len=16)
    toks = list(prompt)
    for t in range(len(prompt)):
        logits, cache = api.decode_step(
            params, cache, jnp.asarray([toks[t]], jnp.int32), jnp.int32(t),
            cfg)
    out = []
    cur = int(jnp.argmax(logits[0]))
    for t in range(len(prompt), len(prompt) + 4):
        out.append(cur)
        logits, cache = api.decode_step(
            params, cache, jnp.asarray([cur], jnp.int32), jnp.int32(t), cfg)
        cur = int(jnp.argmax(logits[0]))
    np.testing.assert_array_equal(reqs[0].out, np.asarray(out, np.int32))


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """One real dry-run cell on the 512-device production mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "smollm_360m", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert "-> ok" in out.stdout, out.stdout + out.stderr


def test_gradient_int8_cross_pod_allreduce_single_device():
    """shard_map int8 exchange compiles + is unbiased on a 1x1x1 mesh."""
    from repro.launch.mesh import make_mesh
    from repro.optim.compress import cross_pod_allreduce_int8
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    grads = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
    out = cross_pod_allreduce_int8(grads, mesh, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(grads["w"]), atol=2e-2)


@pytest.mark.slow
def test_cp_attention_multishard_subprocess():
    """Ring CP attention numerics on a real 8-shard mesh."""
    code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "%s")
import jax, numpy as np
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 8), ("data", "model"))
ks = jax.random.split(jax.random.PRNGKey(5), 3)
q, k, v = (jax.random.normal(ks[i], (2, 4, 256, 32)) for i in range(3))
for window in [None, 64, 100]:
    out = ops.cp_flash_attention(q, k, v, mesh, causal=True, window=window,
                                 q_chunk=32, kv_chunk=32)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=3e-5, rtol=3e-5)
print("OK")
''' % os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=540)
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache sits at the fixed <checkout>/.jax_cache."""
    from repro.launch.compile_cache import use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

"""The yardstick's small pieces: the process environment, the per-leaf
comparison and the control's rounding."""
import os

import numpy as np
import pytest

from yardstick import env, spec, train_lm

ENV = ("JAX_COMPILATION_CACHE_DIR", "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
       "TPU_LOG_DIR")


def test_compile_cache_at_a_fixed_path_in_the_checkout(monkeypatch):
    for k in ENV:
        monkeypatch.delenv(k, raising=False)
    env.prepare()
    cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert cache == str(spec.ROOT / ".jax_cache")
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_compile_cache_named_by_the_environment_is_kept(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    env.prepare()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_leaf_gap_is_measured_against_the_median_leaf_at_least():
    ref = {"big": 10.0, "mid": 1.0, "tiny": 1e-6}
    prog = {"big": 10.1, "mid": 1.0, "tiny": 2e-6}
    # the tiny leaf doubles, but against the median leaf's norm that is 1e-6
    gap, leaf = train_lm.leaf_gap(prog, ref, ref)
    assert leaf == "big" and gap == pytest.approx(0.01)


def test_leaves_with_no_gradient_are_not_moved_leaves():
    grads = {"a": 1.0, "b": 0.5, "c": 2.0, "key_bias": 1e-7}
    assert train_lm.moved_leaves(grads) == ["a", "b", "c"]


def test_control_rounds_to_int8_steps():
    import jax.numpy as jnp
    ref = spec.resolve("smollm-360m.train-s2048").driver()
    x = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    q = np.asarray(ref._int8(jnp.asarray(x)))
    step = np.abs(x).max() / 127
    assert 0 < np.abs(q - x).max() <= step / 2 * (1 + 1e-6)
    assert len(np.unique(np.round(q / step))) <= 255

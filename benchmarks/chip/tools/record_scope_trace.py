#!/usr/bin/env python3
"""Record the scoped trace that ``tests/test_chipbench_scopes.py`` reads.

  python3 benchmarks/chip/tools/record_scope_trace.py OUT_DIR

On the chip: a tiny jitted gradient of a function with two nested program
scopes (``attention`` inside ``mlp``) and an unscoped loss, four steps under
``bench.window``.  Copy the ``.xplane.pb`` it prints to ``tests/data/``.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from yardstick import scopes  # noqa: E402
from yardstick import trace as T  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from repro import obs

    def loss(w, x):
        with obs.scope("mlp"):
            h = jnp.tanh(x @ w[0])
            with obs.scope("attention"):
                h = jax.nn.softmax(h @ w[1], axis=-1)
        return jnp.sum(h * h)

    out = sys.argv[1]
    f = jax.jit(jax.grad(loss))
    w = jnp.full((2, 512, 512), 0.01, jnp.float32)
    x = jnp.ones((512, 512), jnp.float32)
    f(w, x).block_until_ready()
    T.start(out)
    with T.span("bench.window"):
        for _ in range(4):
            with T.span("bench.step"):
                f(w, x).block_until_ready()
    T.stop()
    path = T.find_xplane(out)
    print(path)
    got = scopes.attribute(path, "bench.window")
    print({k: got[k] for k in ("busy_s", "unscoped_s", "scopes")})
    for op in sorted(got["ops"], key=lambda o: -o[3]):
        print(op)


if __name__ == "__main__":
    main()

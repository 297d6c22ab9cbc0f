#!/usr/bin/env python3
"""Which grouped matmul lowers better on the chip: ``jax.lax.ragged_dot``
(XLA's own) or the Pallas ``megablox`` grouped matmul.

  python3 benchmarks/chip/tools/grouped_matmul.py [--iters 10]

Times, on one chip, the held experts' SwiGLU of one expert layer of the
moonlight-16b-a3b.train-s8192 cell as the dropless dispatch runs it --
three grouped matmuls over ``T*k`` sorted token copies, of which the held
experts' groups cover only some -- forward and backward (the input's and
the weights' gradients), for each lowering and each number of covered
rows.  One JSON line per reading: milliseconds per forward and backward,
and the achieved TFLOP/s over the covered rows.  Refuses without a TPU.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from yardstick import env  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    env.prepare()
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if jax.devices()[0].platform != "tpu":
        print("grouped_matmul.py: needs a TPU", file=sys.stderr)
        return 3
    tokens, k, held, d, f = 2 * 8192, 6, 8, 2048, 1408
    m = tokens * k
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (m, d), jnp.bfloat16)
    w = {n: (jax.random.normal(kk, shape, jnp.float32) * 0.02)
         .astype(jnp.bfloat16) for n, kk, shape in
         (("gate", ks[1], (held, d, f)), ("up", ks[2], (held, d, f)),
          ("down", ks[3], (held, f, d)))}

    def ragged(a, b, sizes):
        return jax.lax.ragged_dot(a, b, sizes)

    def tile(m, k, n):          # 512 where it divides, else the whole dim
        return tuple(512 if x % 512 == 0 else x for x in (m, k, n))

    def megablox(a, b, sizes):
        return gmm(a, b, sizes, preferred_element_type=jnp.bfloat16,
                   tiling=tile)

    def swiglu(dot):
        def loss(x, w, sizes):
            h = jax.nn.silu(dot(x, w["gate"], sizes)) * dot(x, w["up"], sizes)
            y = dot(h, w["down"], sizes)
            rows = jnp.arange(m) < sizes.sum()
            return jnp.sum(jnp.where(rows[:, None], y, 0).astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    for covered in (m // 8, m // 2, m):
        sizes = jnp.full((held,), covered // held, jnp.int32)
        flops = 3 * 3 * 2.0 * covered * d * f          # forward + backward
        want = None
        for name, dot in (("ragged_dot", ragged), ("megablox", megablox)):
            fn = swiglu(dot)
            val, _ = jax.block_until_ready(fn(x, w, sizes))
            t = time.perf_counter()
            for _ in range(args.iters):
                out = fn(x, w, sizes)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - t) / args.iters
            want = float(val) if want is None else want
            print(json.dumps({
                "lowering": name, "rows": m, "covered": covered,
                "ms": ms, "tflops_covered": flops / (ms * 1e-3) / 1e12,
                "loss": float(val), "loss_vs_ragged_dot":
                    abs(float(val) - want) / max(abs(want), 1e-30)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

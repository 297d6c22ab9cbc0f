#!/usr/bin/env python3
"""Record the small trace that ``tests/test_chipbench_trace.py`` reduces.

  python3 benchmarks/chip/tools/record_test_trace.py OUT_DIR

On the chip: a handful of small jitted calls between benchmark spans, with
a deliberate host pause, so the trace has device operations, idle gaps and
host spans.  Copy the ``.xplane.pb`` it prints to ``tests/data/``.
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from yardstick import trace as T  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    out = sys.argv[1]
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    T.start(out)
    with T.span("bench.window"):
        for i in range(4):
            with T.span("bench.step"):
                f(x).block_until_ready()
            with T.span("bench.pause"):
                time.sleep(0.002)
    T.stop()
    path = T.find_xplane(out)
    print(path)
    print(T.reduce(path, "bench.window"))


if __name__ == "__main__":
    main()

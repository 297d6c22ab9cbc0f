#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Builds the cell's inputs and weights from ``--seed``, warms up the shapes
the cell uses (set-up), measures for ``--seconds`` seconds, then checks what
the timed path produced against the configuration's plain reference.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.
The same checks are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  JAX's compile cache sits at ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from yardstick import env  # noqa: E402
from yardstick import outcome as O  # noqa: E402
from yardstick import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    cell = spec.resolve(args.workload)
    env.prepare()
    ctx = O.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace),
                out_dir=HERE / ".out" / args.workload,
                t_process=T_PROCESS)
    ctx.out_dir.mkdir(parents=True, exist_ok=True)
    try:
        out = cell.driver().run(ctx)
    except O.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    line = O.result_line(ctx, out, spec.metric_reader)
    O.print_checks(out)
    O.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control and fault readings of a mixture-of-experts training cell,
on the chip: the numbers its limits are set against.

  python3 benchmarks/chip/tools/train_moe_readings.py --workload <cell> \
      --seeds 1 2 3 [--faults] [--out FILE]

For each seed, in this one process: the reference, and the reference with
every matmul operand rounded to int8 (the control) put in the program's
place and compared as a run compares the program (``train_moe.compare``);
with ``--faults`` also the reference with half of each batch left out.
Each seed gives one JSON line of the compared numbers by name and the
copies computed here.  The program's own readings are the checks of its
runs (``run.py``): the program and the reference cannot share the chip's
memory in one process.
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
from yardstick import spec, train_moe  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    env.prepare()
    cell = spec.resolve(args.workload)
    ref = cell.driver()
    m, tr = cell.config["model"], cell.traffic
    steps = tr["checked_steps"]
    readings = [("control_int8", dict(matmul="int8"))]
    if args.faults:
        readings.append(("fault_half_batch", dict(keep_rows=tr["batch"] // 2)))
    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t = time.perf_counter()
        want = ref.reference(m, tr, seed, steps)
        row = {"seed": seed, "reference": {
            "rows_here": want["rows_here"], "losses": want["losses"],
            "s": time.perf_counter() - t}}
        for what, kw in readings:
            t = time.perf_counter()
            got = ref.reference(m, tr, seed, steps, **kw)
            row[what] = dict(
                {c.name: c.value
                 for c in train_moe.compare(got, want, tr["limits"])},
                rows_here=got["rows_here"], s=time.perf_counter() - t)
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()


if __name__ == "__main__":
    main()

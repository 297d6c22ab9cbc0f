#!/usr/bin/env python3
"""Readings that set a training cell's correctness limits, on the chip.

  python3 benchmarks/chip/tools/train_readings.py --workload <cell> \
      --seeds 1 2 3 [--control] [--faults] [--out FILE]

For each seed, in one process: the program's checked steps through the
cell's own path (a one-step window) against the reference, as a run checks
them; with ``--control`` the reference computed with every matmul operand
rounded to int8, put in the program's place; with
``--faults`` the reference with half of each batch left out.  Each seed
gives one JSON line: the compared numbers of each reading by name, and the
raw losses and per-leaf norms they were taken from.
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
from yardstick import outcome as O  # noqa: E402
from yardstick import spec, train_lm  # noqa: E402


def numbers(checks):
    return {c.name: c.value for c in checks}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    env.prepare()
    cell = spec.resolve(args.workload)
    ref = cell.driver()
    m, tr = cell.config["model"], cell.traffic
    limits = tr["limits"]
    checked = tr["checked_steps"]
    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = O.Ctx(cell=cell, seed=seed, seconds=0.0, trace=False,
                    out_dir=HERE / ".out" / "readings",
                    t_process=time.perf_counter())
        out = ref.run(ctx)
        want = out.counters["reference"]
        row = {"seed": seed,
               "memory_peak_bytes": out.device.get("memory_peak_bytes"),
               "program": dict(numbers(out.checks),
                               raw=out.counters["program"],
                               s=time.perf_counter() - t),
               "reference": want}
        readings = [("control_int8", dict(matmul="int8"))] if args.control \
            else []
        if args.faults:
            readings.append(("fault_half_batch",
                             dict(keep_rows=tr["batch"] // 2)))
        for what, kw in readings:
            t = time.perf_counter()
            got = ref.reference(m, tr, seed, checked, **kw)
            row[what] = dict(numbers(train_lm.compare(got, want, limits)),
                             raw=got, s=time.perf_counter() - t)
        line = json.dumps(row)
        print(json.dumps({k: ({kk: vv for kk, vv in v.items() if kk != "raw"}
                              if isinstance(v, dict) and k != "reference"
                              else v)
                          for k, v in row.items() if k != "reference"}),
              flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()


if __name__ == "__main__":
    main()

"""What a cell's driver hands back, and the run's last line built from it."""
from __future__ import annotations

import dataclasses
import json
import math
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""

    cell: object                 # spec.Cell
    seed: int
    seconds: float
    trace: bool
    out_dir: Path                # scratch for this run (gitignored)
    t_process: float             # perf_counter at process start
    require_tpu: bool = True     # tests drive the rest of a run on the CPU

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """A number compared with its limit; it passes while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]      # host_clock metrics, setup_s included
    checks: List[Check]
    device: dict                      # platform, kind, count,
                                      # memory_peak_bytes
    trace: Optional[dict] = None      # trace.reduce() of the traced window
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def require_chips(device: dict, chips: int) -> None:
    if device["platform"] != "tpu" or device["count"] < chips:
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); JAX found {device['count']} "
            f"{device['platform']} device(s)")


def result_line(ctx: Ctx, out: Outcome,
                reader: Callable[[str], Callable]) -> dict:
    """The last line of standard output."""
    cell = ctx.cell
    metrics: Dict[str, dict] = {}
    if ctx.trace:
        for m in cell.per_layer:
            v = reader(m["name"])(out, cell)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = dict(out.device)
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if ctx.trace:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        line["breakdown"] = {"device_ops": out.trace["device_ops"],
                             "idle_gaps": out.trace["idle_gaps"]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_checks(out: Outcome) -> None:
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} <= {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)

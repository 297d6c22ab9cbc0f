"""Tests of the chip benchmark's yardstick; they run on the CPU and touch
no TPU."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import copy  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def run_tiny(tmp_path):
    """Drive the rest of a run of a cell on the CPU, skipping the harness's
    look for a chip: ``run_tiny(cell, seconds, model=..., **traffic)``."""
    from yardstick import outcome as O
    from yardstick import spec

    def run(name, seconds=1.0, model=None, seed=2 ** 31 + 11, **traffic):
        cell = spec.resolve(name)
        cell.config = copy.deepcopy(cell.config)
        if model:
            cell.config["model"].update(model)
        cell.traffic = dict(copy.deepcopy(cell.traffic), **traffic)
        ctx = O.Ctx(cell=cell, seed=seed, seconds=seconds, trace=False,
                    out_dir=tmp_path, t_process=time.perf_counter(),
                    require_tpu=False)
        return cell.driver().run(ctx)

    return run

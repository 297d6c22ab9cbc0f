"""experts_roofline_pct.train: the held experts' grouped matmuls against
their roofline.  The least time the chip could take for them -- the larger
of their FLOPs over the bf16 peak and their bytes over HBM bandwidth
(``yardstick.mla_moe.grouped_swiglu_cost`` of the traced steps' counted
copies) -- over the device self time of the ``experts`` scope in the same
steps.  Which bound holds is logged."""
import sys

from yardstick import mla_moe, peaks


def read(out, cell):
    ms = mla_moe.scope_ms(out, cell, "experts", "experts_roofline_pct.train")
    rows = out.counters.get("rows_here_traced")
    if not ms or not rows:
        return None
    p = peaks.peaks(out.device["kind"])
    flops, nbytes = mla_moe.grouped_swiglu_cost(sum(rows) / len(rows),
                                                cell.config["model"])
    t_flops = flops / p["bf16_flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    print(f"experts_roofline_pct.train: {flops:.4g} FLOPs and {nbytes:.4g} "
          f"bytes a step, {'compute' if t_flops >= t_bytes else 'memory'}"
          f" bound; {ms:.4f} ms a step", file=sys.stderr, flush=True)
    return 100.0 * max(t_flops, t_bytes) / (ms * 1e-3)

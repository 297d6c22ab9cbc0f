"""step_compile_s.train: host seconds the program spent on its train step's
compiles in the run -- tracing, lowering, and compiling or loading from the
compile cache -- from the program's own compile log (``repro.obs``)."""
import sys


def read(out, cell):
    try:
        from repro import obs
    except ImportError:
        print("step_compile_s.train: the program keeps no compile log",
              file=sys.stderr, flush=True)
        return None
    logged = obs.compiles("train_step")
    if not logged:
        print("step_compile_s.train: the compile log holds no train_step",
              file=sys.stderr, flush=True)
        return None
    for c in logged:
        print(f"step_compile_s.train: trace {c.trace_s} s, lower "
              f"{c.lower_s} s, compile or load {c.compile_s} s, cache hit "
              f"{c.cache_hit}", file=sys.stderr, flush=True)
    return sum(c.total_s for c in logged)

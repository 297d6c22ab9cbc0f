"""Training cell of a mixture-of-experts LM through the system's normal
training path, checked against the configuration's plain reference.

The program's run is ``train_lm``'s -- the jitted, donating train step of
``repro.launch.steps.make_train_step`` with its parameters and AdamW state,
wired as ``repro.launch.train.train`` wires them, driven through its
``checked_steps`` in set-up and then one step after another for the window,
a few more steps traced with ``--trace 1`` -- except that the window keeps
``AHEAD`` steps queued on the chip behind the one whose counters the host
waits for, as a trainer that reads each step's metrics late does, so that a
pause of the host's shorter than that does not idle the chip; and it keeps
the step's MoE
counters: the token copies computed by held experts (``moe_rows_here``),
the largest held expert's copies and the copies dropped, per step.

The reference runs first, in a child process that has the chip to itself
(``references``): the program's step keeps several GB of the chip's memory
reserved while it is loaded, and the reference does not fit beside it.
``setup_s`` leaves the child's time out: it is the yardstick's, not the
system's.

Checked, besides ``train_lm.compare``'s gradient and change norms: no copy
dropped in any step (``dropped_assignments``), and each checked step's
copies computed here against the reference's count (``rows_here_gap``).
The traffic file's ``bias_speed`` and ``balance_alpha`` are the model's
correction-bias speed and balance-loss weight.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from . import outcome as O
from . import trace as T
from .train_lm import _path, compare as compare_norms

#: steps kept queued on the chip during the window
AHEAD = 2


def model_config(m: dict, tr: dict):
    """The system's ``ModelConfig`` of a cell: the configuration's model
    with the traffic's routing hyperparameters."""
    from repro.models.config import ModelConfig
    m = dict(m, period=tuple(tuple(b) for b in m["period"]),
             router_bias_speed=tr["bias_speed"],
             router_aux_weight=tr["balance_alpha"])
    return ModelConfig(**m)


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """``train_lm.compare``'s norms, and the worst checked step's relative
    gap in copies computed here."""
    rows = max(abs(p - r) / r for p, r in zip(prog["rows_here"],
                                              ref["rows_here"]))
    return compare_norms(prog, ref, limits) + [
        O.Check("rows_here_gap", rows, limits["rows_here_gap"])]


def references(ref, m: dict, tr: dict, seeds, steps: int, work_dir,
               require_tpu: bool = True, **kw) -> dict:
    """``ref.reference`` of each seed, computed in a child process
    (``python3 <configuration module> JOB OUT``), keyed by seed.  Call it
    before this process takes the chip: a chip belongs to one process.
    With ``require_tpu`` the child refuses to run without one."""
    stem = os.path.join(str(work_dir), f"reference-{os.getpid()}")
    job, out = stem + "-job.json", stem + "-out.json"
    with open(job, "w") as f:
        json.dump({"model": m, "traffic": tr, "seeds": list(seeds),
                   "steps": steps, "kw": kw, "require_tpu": require_tpu}, f)
    try:
        done = subprocess.run([sys.executable, ref.__file__, job, out],
                              stdout=sys.stderr)
        if done.returncode == 3:
            raise O.NoAccelerator("the reference found no TPU")
        done.check_returncode()
        with open(out) as f:
            return {int(k): v for k, v in json.load(f).items()}
    finally:
        for path in (job, out):
            if os.path.exists(path):
                os.remove(path)


def run_cell(ctx: O.Ctx, ref) -> O.Outcome:
    cell, tr = ctx.cell, ctx.cell.traffic
    m = cell.config["model"]
    model_config(m, tr)                     # refuse an unknown key first
    t_ref = time.perf_counter()
    want = references(ref, m, tr, [ctx.seed], tr["checked_steps"],
                      ctx.out_dir, ctx.require_tpu)[ctx.seed]
    ref_s = time.perf_counter() - t_ref
    ctx.log(f"train: reference {ref_s:.2f} s (a child process), losses "
            f"{want['losses']}, copies here {want['rows_here']}")
    got = program(ctx, ref)
    prog, window, traced = got["program"], got["window"], got["traced"]
    checks = compare(prog, want, tr["limits"])
    dropped = sum(prog["dropped"]) + sum(s["moe_dropped"]
                                         for s in window + traced)
    checks.append(O.Check("dropped_assignments", float(dropped),
                          tr["limits"]["dropped_assignments"]))
    losses = prog["losses"] + [s["loss"] for s in window + traced]
    bad = sum(not np.isfinite(x) for x in losses)
    checks.append(O.Check("nonfinite_losses", float(bad), 0.0))
    n, elapsed, tokens = len(window), got["window_s"], got["tokens_per_step"]
    return O.Outcome(
        attempted=tr["checked_steps"] + n, failed=bad,
        end_to_end={"train_tokens_per_s": n * tokens / elapsed,
                    "setup_s": got["t0"] - ctx.t_process - ref_s},
        checks=checks, device=got["device"], trace=got["trace"],
        counters={"steps": n, "window_s": elapsed, "tokens_per_step": tokens,
                  "rows_here_window": [s["moe_rows_here"] for s in window],
                  "rows_here_traced": [s["moe_rows_here"] for s in traced],
                  "max_rows": max(s["moe_max_rows"] for s in window),
                  "dropped": dropped, "program": prog, "reference": want})


def program(ctx: O.Ctx, ref) -> dict:
    """The program's part of a run: set-up with its checked steps, the
    window, and with ``ctx.trace`` the traced steps.  Returns the checked
    steps' readings (``program``), each window and traced step's counters,
    the window's seconds, its start ``t0`` (perf_counter), the device and
    the reduced trace."""
    cell, tr = ctx.cell, ctx.cell.traffic
    cfg = model_config(cell.config["model"], tr)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import Prefetcher, SyntheticLM
    from repro.launch import shardings as SH
    from repro.launch.mesh import batch_axes, make_mesh
    from repro.launch.steps import make_train_step
    from repro.models import model_api
    from repro.models import partitioning as part
    from repro.optim.optimizers import adamw, warmup_cosine

    o = tr["optimizer"]
    batch, seq, checked = tr["batch"], tr["seq"], tr["checked_steps"]
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if ctx.require_tpu:
        O.require_chips(device, cell.chips)
    span = T.span if ctx.trace else contextlib.nullcontext

    api = model_api(cfg)
    mesh = make_mesh((cell.chips, 1), ("data", "model"))
    part.set_mesh(mesh, batch_axes(mesh))
    optimizer = adamw(warmup_cosine(o["lr"], warmup=o["warmup"],
                                    total=o["total"]),
                      b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    key = jnp.asarray(ref.seed_key(ctx.seed))
    shapes = jax.eval_shape(lambda k: api.init(k, cfg), key)
    p_sh = SH.param_shardings(cfg, shapes, mesh, fsdp=False)
    o_sh = {"mu": p_sh, "nu": p_sh, "step": NamedSharding(mesh, P())}

    def make_state(k):
        p = api.init(k, cfg)
        return p, optimizer.init(p)

    # weights and optimizer state made on the device in one call
    params, opt_state = jax.jit(make_state, out_shardings=(p_sh, o_sh))(key)
    b_shard = NamedSharding(mesh, SH.batch_pspec(mesh, batch,
                                                  pure_dp=cfg.pure_dp))
    data = Prefetcher(SyntheticLM(batch, seq, cfg.vocab, seed=ctx.seed,
                                  alpha=tr["zipf_alpha"]))
    step_fn = jax.jit(make_train_step(cfg, optimizer,
                                      clip_norm=o["clip_norm"]),
                      donate_argnums=(0, 1))
    norms = jax.jit(lambda t: {
        _path(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for p, x in jax.tree_util.tree_flatten_with_path(t)[0]})

    def step(params, opt_state, wait=True):
        with span("bench.data"):
            raw = data.next_batch()
            b = jax.device_put({"inputs": raw["inputs"],
                                "labels": raw["labels"]}, b_shard)
        with span("bench.step"):
            out = step_fn(params, opt_state, b)
        if not wait:
            return out
        with span("bench.wait"):
            return jax.block_until_ready(out)

    keep = ("loss", "moe_rows_here", "moe_max_rows", "moe_dropped")
    window, traced = [], []
    try:
        with mesh:
            # set-up: the checked steps, through the window's own call
            p0 = jax.tree.map(jnp.copy, params)
            prog = {"losses": [], "rows_here": [], "max_rows": [],
                    "dropped": []}
            for i in range(checked):
                params, opt_state, met = step(params, opt_state)
                prog["losses"].append(float(met["loss"]))
                prog["rows_here"].append(float(met["moe_rows_here"]))
                prog["max_rows"].append(float(met["moe_max_rows"]))
                prog["dropped"].append(float(met["moe_dropped"]))
                if i == 0:
                    prog["grad_norms"] = {
                        k: float(v) / (1 - o["b1"])
                        for k, v in norms(opt_state["mu"]).items()}
            prog["change_norms"] = {k: float(v) for k, v in norms(
                jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), params, p0)).items()}
            del p0
            cache = step_fn._cache_size()

            # the window: AHEAD steps stay queued on the chip behind the one
            # the host waits for, so a host hiccup shorter than that leaves
            # the chip busy; no step is dispatched once one completes past
            # the window's seconds, and the window ends when the last
            # dispatched completes
            step_s, queued = [], collections.deque()
            t0 = t = time.perf_counter()
            wall0 = time.time()
            while True:
                if t - t0 < ctx.seconds:
                    params, opt_state, met = step(params, opt_state,
                                                  wait=False)
                    queued.append(met)
                    if len(queued) <= AHEAD:
                        continue
                elif not queued:
                    break
                met = jax.block_until_ready(queued.popleft())
                window.append({k: met[k] for k in keep})
                now = time.perf_counter()
                step_s.append(now - t)
                t = now
            n, elapsed = len(step_s), t - t0
            stats = devs[0].memory_stats() or {}
            device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use",
                                                        0))
            if step_fn._cache_size() != cache:
                ctx.log("train: the step compiled inside the window")
            reduced = None
            if ctx.trace:
                log_dir = str(ctx.out_dir / "trace")
                T.start(log_dir)
                with span("bench.window"):
                    for _ in range(tr["trace_steps"]):
                        params, opt_state, met = step(params, opt_state)
                        traced.append({k: met[k] for k in keep})
                T.stop()
                reduced = T.reduce(T.find_xplane(log_dir), "bench.window")
    finally:
        data.close()
        part.set_mesh(None)
    window = [{k: float(v) for k, v in s.items()} for s in window]
    traced = [{k: float(v) for k, v in s.items()} for s in traced]
    del params, opt_state, met
    for x in jax.live_arrays():
        x.delete()
    rows = [s["moe_rows_here"] for s in window]
    ctx.log(f"train: set-up {t0 - ctx.t_process:.2f} s; "
            f"set-up steps' losses {prog['losses']}, copies here "
            f"{prog['rows_here']}; window: {n} steps in {elapsed:.4f} s, "
            f"losses {window[0]['loss']:.4f} -> {window[-1]['loss']:.4f}, "
            f"copies here {min(rows):.0f}-{max(rows):.0f} a step, largest "
            f"held expert {max(s['moe_max_rows'] for s in window):.0f}; "
            f"step median {np.median(step_s):.4f} s, slowest "
            f"{max(step_s):.4f} s (step {int(np.argmax(step_s))})")
    ctx.log(f"train: window from {wall0:.3f} s (epoch), steps as each "
            f"completed (ms, copies here): " + " ".join(
        f"{1e3 * t:.1f}/{r:.0f}" for t, r in zip(step_s, rows)))
    return {"program": prog, "window": window, "traced": traced,
            "window_s": elapsed, "t0": t0, "tokens_per_step": batch * seq,
            "device": device, "trace": reduced}

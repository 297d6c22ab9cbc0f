"""Training cell of a decoder-only LM through the system's normal training
path, checked against the configuration's plain reference.

Set-up builds one object -- the jitted, donating train step of
``repro.launch.steps.make_train_step`` with its parameters and AdamW state,
wired as ``repro.launch.train.train`` wires them -- and drives it through
its first ``checked_steps`` steps with the same call and feed the window
uses.  The window then runs that same object, one step after another, each
ended by ``block_until_ready``.  With ``--trace 1`` a few more steps after
the window are traced.  Once the window has closed and the program's state
is freed, the reference repeats the checked steps; compared are each leaf's
first gradient norm (read back from the AdamW first moment after one step)
and each leaf's change after the checked steps.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict

import numpy as np

from . import outcome as O
from . import trace as T


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves) -> tuple:
    """Worst relative gap between two sets of per-leaf norms: |prog - ref|
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns (gap, leaf)."""
    leaves = list(leaves)
    med = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moved_leaves(ref_grads: Dict[str, float], floor: float = 1e-3):
    """Leaves whose reference gradient is above ``floor`` times the median
    leaf's.  The others (zero to rounding) move under Adam by round-off
    alone, so their change is not compared."""
    med = float(np.median(list(ref_grads.values())))
    return [k for k, v in ref_grads.items() if v >= floor * med]


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The worst leaf's first gradient norm and the worst moved leaf's
    change after the checked steps.  The losses are logged, not compared:
    neither the control nor a fault moves the first step's loss far enough
    from the program's own gap to set a limit between them, and the later
    steps' losses swing with rounding seed by seed (PERF.md, Findings)."""
    grad_gap, _ = leaf_gap(prog["grad_norms"], ref["grad_norms"],
                           ref["grad_norms"])
    change_gap, _ = leaf_gap(prog["change_norms"], ref["change_norms"],
                             moved_leaves(ref["grad_norms"]))
    return [O.Check("grad_norm_gap", grad_gap, limits["grad_norm_gap"]),
            O.Check("change_norm_gap", change_gap,
                    limits["change_norm_gap"])]


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def run_cell(ctx: O.Ctx, ref) -> O.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import Prefetcher, SyntheticLM
    from repro.launch import shardings as SH
    from repro.launch.mesh import batch_axes, make_mesh
    from repro.launch.steps import make_train_step
    from repro.models import model_api
    from repro.models import partitioning as part
    from repro.models.config import ModelConfig
    from repro.optim.optimizers import adamw, warmup_cosine

    cell, tr = ctx.cell, ctx.cell.traffic
    m = cell.config["model"]
    o = tr["optimizer"]
    batch, seq, checked = tr["batch"], tr["seq"], tr["checked_steps"]
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if ctx.require_tpu:
        O.require_chips(device, cell.chips)
    span = T.span if ctx.trace else contextlib.nullcontext

    cfg = ModelConfig(**m)
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

    def step(params, opt_state):
        with span("bench.data"):
            raw = data.next_batch()
            b = jax.device_put({"inputs": raw["inputs"],
                                "labels": raw["labels"]}, b_shard)
        with span("bench.step"):
            out = step_fn(params, opt_state, b)
        with span("bench.wait"):
            return jax.block_until_ready(out)

    losses = []
    try:
        with mesh:
            # set-up: the checked steps, through the window's own call
            p0 = jax.tree.map(jnp.copy, params)
            prog = {"losses": []}
            for i in range(checked):
                params, opt_state, met = step(params, opt_state)
                prog["losses"].append(float(met["loss"]))
                if i == 0:
                    prog["grad_norms"] = {
                        k: float(v) / (1 - o["b1"])
                        for k, v in norms(opt_state["mu"]).items()}
            prog["change_norms"] = {k: float(v) for k, v in norms(
                jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), params, p0)).items()}
            del p0
            cache = step_fn._cache_size()

            # the window
            step_s = []
            t0 = t = time.perf_counter()
            while True:
                params, opt_state, met = step(params, opt_state)
                losses.append(met["loss"])
                now = time.perf_counter()
                step_s.append(now - t)
                t = now
                if t - t0 >= ctx.seconds:
                    break
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
                T.stop()
                reduced = T.reduce(T.find_xplane(log_dir), "bench.window")
    finally:
        data.close()
        part.set_mesh(None)
    window_losses = [float(x) for x in losses]
    del params, opt_state, met, losses
    for x in jax.live_arrays():
        x.delete()

    tokens = batch * seq
    ctx.log(f"train: set-up steps' losses {prog['losses']}; window: {n} "
            f"steps in {elapsed:.4f} s, losses {window_losses[0]:.4f} -> "
            f"{window_losses[-1]:.4f}; step median {np.median(step_s):.4f} s, "
            f"slowest {max(step_s):.4f} s (step {int(np.argmax(step_s))})")
    t_ref = time.perf_counter()
    want = ref.reference(m, tr, ctx.seed, checked)
    ctx.log(f"train: reference {time.perf_counter() - t_ref:.2f} s, losses "
            f"{want['losses']}")
    checks = compare(prog, want, tr["limits"])
    bad = sum(not np.isfinite(x) for x in window_losses + prog["losses"])
    checks.append(O.Check("nonfinite_losses", float(bad), 0.0))
    return O.Outcome(
        attempted=checked + n, failed=bad,
        end_to_end={"train_tokens_per_s": n * tokens / elapsed,
                    "setup_s": t0 - ctx.t_process},
        checks=checks, device=device, trace=reduced,
        counters={"steps": n, "window_s": elapsed, "tokens_per_step": tokens,
                  "program": prog, "reference": want})

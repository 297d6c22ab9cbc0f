#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, checked against plain
references.

  python3 chip_smoke.py              # one chip: the four phases below
  python3 chip_smoke.py --chips 4    # four chips: data-parallel trainer only

Phases, in this order (a chip belongs to one process at a time, so the
process-backend phase runs while this process has not touched JAX):

1. stream processor, process backend: ``streaming_inference`` planned by
   RLAS, 200 batches of 1024 events per spout replica; the device predictor
   replicas share the one worker that owns the chip;
2. stream processor, thread backend: the same plan in this process, checked
   against a float64 NumPy tanh-MLP over the same seeded batches;
3. train: smollm-360m at its published widths, 8 steps of 8 x 1024 tokens,
   with a float32 CPU check of the initial loss;
4. serve: smollm-360m, 4 requests, 16 prompt + 8 new tokens, with a float32
   CPU check of the first generated step's logits.

Weights are random, from fixed seeds (no pretrained weights ship with the
repository).  Every time printed is a smoke timing of a single run, not a
device metric.  The last line of standard output is a JSON object, printed
only when every check passed; any failure exits non-zero.  Without a TPU the
script refuses to run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm_360m"
STREAM_BATCHES, STREAM_BATCH = 200, 1024
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 8, 8, 1024
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 4, 16, 8

# Tolerances, fixed before the first chip run.
#
# Stream score: the sum over every event of a 4-layer tanh-MLP score.  The
# TPU's default precision for a float32 matmul rounds its operands to
# bfloat16 (8 significant bits); emulating that rounding in NumPy over one
# spout replica's 200 batches moves the sum by 8.4e-6 of sum(|score|).  The
# bound is 3.5x that.
STREAM_REL_TOL = 3e-5
# First training loss: random logits of standard deviation s give an
# expected cross-entropy of ln(V) + s^2/2; at init s ~ sqrt(960) * 0.02
# = 0.62 (embedding std 0.02, unit-RMS final norm), i.e. ln(V) + 0.19.
FIRST_LOSS_TOL = 0.5
# One sequence's loss at the initial parameters, chip (bfloat16 weights and
# activations) against CPU float32 at highest matmul precision: bfloat16
# keeps 8 significant bits, the per-token errors are unbiased and average
# over 1024 tokens, so 2e-2 of a loss near 11 leaves a wide margin.
LOSS_ABS_TOL = 2e-2
# First generated step's logits, chip (bfloat16) against CPU float32:
# relative L2 error.  Rounding to 8 significant bits through 32 residual
# layers drifts the hidden state by a few percent at most.
LOGITS_REL_TOL = 5e-2
# Per-step losses, data parallel on four chips against one device with the
# same global batch: the forward pass differs only in the order of the
# cross-device reductions, the updates in bfloat16 gradient sums.
DP_LOSS_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def probe_device() -> dict:
    """Ask a short-lived child for the device, so this process stays off
    JAX (and off the chip) until the process-backend phase is done."""
    code = ("import jax, json; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    cp = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, timeout=300)
    if cp.returncode != 0:
        raise SystemExit("chip_smoke: JAX could not list devices:\n"
                         + cp.stderr[-2000:])
    return json.loads(cp.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Stream processor
# ---------------------------------------------------------------------------

def stream_plan():
    from repro.core import server_a
    from repro.streaming import Job
    from repro.streaming.apps import streaming_inference
    # the machine and optimizer settings of examples/quickstart.py
    return Job(streaming_inference(model_versions=1)).plan(
        server_a(), optimizer="rlas", compress_ratio=5, bestfit=True,
        max_nodes=5000)


def stream_run(plan, backend: str) -> dict:
    t = time.perf_counter()
    raw = plan.execute(backend=backend, batches=STREAM_BATCHES,
                       batch=STREAM_BATCH).raw
    wall = time.perf_counter() - t
    sinks = raw.states["sink"]
    out = {"seen": sum(st.get("seen", 0) for st in sinks),
           "score": sum(st.get("score", 0.0) for st in sinks),
           "spouts": len(raw.states["spout"]),
           "offsets": raw.spout_offsets}
    log(f"  {backend}: {out['spouts']} spout replica(s), seen={out['seen']} "
        f"score={out['score']!r} (smoke timing {wall:.3f} s for the run)")
    return out


def stream_reference(spouts: int) -> tuple:
    """(sum, sum of |score|) of the float64 tanh-MLP over every event.
    Spout replica i emits batch b from default_rng(7919 * i + b), the
    runtime's replay seeds (seed 0)."""
    from repro.streaming.apps import INF_FEATURES, inf_model_weights
    w = inf_model_weights(0).astype(np.float64)
    total = absum = 0.0
    for i in range(spouts):
        for b in range(STREAM_BATCHES):
            x = np.random.default_rng(7919 * i + b).normal(
                size=(STREAM_BATCH, INF_FEATURES)).astype(np.float32)
            y = x.astype(np.float64)
            for layer in w:
                y = np.tanh(y @ layer)
            s = y.sum(axis=1)
            total += float(s.sum())
            absum += float(np.abs(s).sum())
    return total, absum


def check_stream(run: dict) -> None:
    check(run["seen"] == run["spouts"] * STREAM_BATCHES * STREAM_BATCH,
          f"sink seen {run['seen']} == {run['spouts']} spout replica(s) x "
          f"{STREAM_BATCHES} x {STREAM_BATCH}")
    check(all(v == STREAM_BATCHES for v in run["offsets"].values()),
          f"spout offsets {run['offsets']} all == {STREAM_BATCHES}")


def phase_stream_processes(plan) -> dict:
    log("[1/4] stream processor, process backend")
    run = stream_run(plan, "processes")
    check_stream(run)
    return run


def phase_stream_threads(plan, procs: dict) -> None:
    log("[2/4] stream processor, thread backend")
    run = stream_run(plan, "threads")
    check_stream(run)
    from repro.streaming import apps
    devs = {d.platform for w in apps._INF_DEVICE_W.values()
            for d in w.devices()}
    check(devs == {"tpu"}, f"predictor weights live on {sorted(devs)}")
    ref, absum = stream_reference(run["spouts"])
    err = abs(run["score"] - ref)
    check(err <= STREAM_REL_TOL * absum,
          f"score {run['score']!r} vs float64 reference {ref!r}: "
          f"|diff| {err:.6g} <= {STREAM_REL_TOL} x sum|score| "
          f"({STREAM_REL_TOL * absum:.6g})")
    check(run["seen"] == procs["seen"],
          f"seen identical across backends ({run['seen']})")
    same = run["score"] == procs["score"]
    log(f"  score byte-identical across backends: {same}")
    if not same:
        # each sink adds per-batch float64 sums in arrival order, and with
        # replicated predictors that order follows thread scheduling
        perr = abs(run["score"] - procs["score"])
        check(perr <= STREAM_REL_TOL * absum,
              f"scores differ by {perr:.6g} (arrival order of batches at "
              f"the sinks), within {STREAM_REL_TOL * absum:.6g}")


# ---------------------------------------------------------------------------
# Model stack
# ---------------------------------------------------------------------------

def _f32_cpu(params):
    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    return jax.tree.map(lambda a: jax.device_put(a, cpu).astype(jnp.float32),
                        params)


def phase_train():
    """Returns the initial parameters, for the serving phase."""
    import jax
    from repro.configs import get
    from repro.data.pipeline import SyntheticLM
    from repro.launch.train import train
    from repro.models import model_api
    log(f"[3/4] train {ARCH} (published widths), {TRAIN_STEPS} steps of "
        f"{TRAIN_BATCH} x {TRAIN_SEQ}")
    cfg = get(ARCH)
    api = model_api(cfg)
    t = time.perf_counter()
    out = train(ARCH, smoke=False, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                seq=TRAIN_SEQ, log_every=1)
    jax.block_until_ready(out["params"])
    wall = time.perf_counter() - t
    losses = out["losses"]
    log(f"  losses {losses}")
    log(f"  smoke timings: train() {wall:.3f} s in all, first step "
        f"{out['step_s'][0]:.3f} s (compile), later steps "
        f"{[round(s, 4) for s in out['step_s'][1:]]} s; peak_bytes_in_use "
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    check(all(math.isfinite(x) for x in losses), "losses finite")
    ln_v = math.log(cfg.vocab)
    check(abs(losses[0] - ln_v) <= FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} within {FIRST_LOSS_TOL} of "
          f"ln({cfg.vocab}) = {ln_v:.4f}")

    # one sequence of the first batch at the initial parameters (train()
    # initialises from PRNGKey(0) and draws its first batch from seed 0)
    raw = SyntheticLM(TRAIN_BATCH, TRAIN_SEQ, cfg.vocab, seed=0).next_batch()
    one = {k: v[:1] for k, v in raw.items()}
    params = api.init(jax.random.PRNGKey(0), cfg)
    loss_fn = jax.jit(lambda p, b: api.loss(p, b, cfg)[0])
    chip = float(loss_fn(params, jax.device_put(one)))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        cpu = float(jax.jit(lambda p, b: api.loss(p, b, cfg32)[0])(
            _f32_cpu(params),
            jax.device_put(one, jax.devices("cpu")[0])))
    check(abs(chip - cpu) <= LOSS_ABS_TOL,
          f"sequence-0 loss on chip {chip:.6f} vs CPU float32 {cpu:.6f}: "
          f"|diff| {abs(chip - cpu):.2e} <= {LOSS_ABS_TOL}")
    return params


def phase_serve(params) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get
    from repro.launch.serve import Request, serve_batch
    from repro.launch.steps import make_decode_step
    from repro.models import model_api
    n_req, prompt, new = SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW
    log(f"[4/4] serve {ARCH}: {n_req} requests, prompt {prompt}, "
        f"{new} new tokens")
    cfg = get(ARCH)
    api = model_api(cfg)
    max_len = prompt + new + 1
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (n_req, prompt), dtype=np.int32)
    reqs = [Request(i, prompts[i], new) for i in range(n_req)]
    t = time.perf_counter()
    reqs, dt = serve_batch(cfg, params, reqs, max_len=max_len)
    wall = time.perf_counter() - t
    toks = np.stack([r.out for r in reqs])
    log(f"  tokens {toks.tolist()}")
    log(f"  smoke timing: serve_batch {wall:.3f} s with compile "
        f"({dt:.3f} s by its own clock)")
    check(toks.shape == (n_req, new) and bool(np.all(
        (toks >= 0) & (toks < cfg.vocab))), "generated tokens in vocabulary")

    def prefill(step, p, cache):
        for t in range(prompt):
            _, logits, cache = step(p, cache, jnp.asarray(prompts[:, t]),
                                    jnp.int32(t))
        return logits

    chip = np.asarray(jax.block_until_ready(prefill(
        jax.jit(make_decode_step(cfg)), params,
        api.init_cache(cfg, n_req, max_len=max_len))), np.float64)
    check(np.array_equal(chip.argmax(-1), toks[:, 0]),
          "first generated tokens are the argmax of the decode step's logits")
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"), \
            jax.default_device(cpu_dev):
        cpu = np.asarray(prefill(jax.jit(make_decode_step(cfg32)),
                                 _f32_cpu(params),
                                 api.init_cache(cfg32, n_req,
                                                max_len=max_len)),
                         np.float64)
    rel = float(np.linalg.norm(chip - cpu) / np.linalg.norm(cpu))
    log(f"  logits: max |diff| {np.abs(chip - cpu).max():.4g}, argmax "
        f"agreement {float(np.mean(chip.argmax(-1) == cpu.argmax(-1)))}")
    check(rel <= LOGITS_REL_TOL,
          f"first-step logits vs CPU float32: relative L2 {rel:.3e} <= "
          f"{LOGITS_REL_TOL}")


def phase_data_parallel(n: int) -> None:
    import jax
    from repro.launch.train import train
    batch, seq = TRAIN_BATCH, TRAIN_SEQ
    log(f"[dp] train {ARCH} data parallel on a ({n}, 1) mesh vs one device, "
        f"{TRAIN_STEPS} steps of {batch} x {seq}")
    check(len(jax.devices()) == n, f"{n} devices visible")
    runs = {}
    for shape in ((n, 1), (1, 1)):
        t = time.perf_counter()
        out = train(ARCH, smoke=False, steps=TRAIN_STEPS, batch=batch,
                    seq=seq, mesh_shape=shape, log_every=1)
        leaves = jax.tree.leaves(jax.block_until_ready(out["params"]))
        log(f"  mesh {shape}: losses {out['losses']} (smoke timings: "
            f"{time.perf_counter() - t:.3f} s in all, later steps "
            f"{[round(s, 4) for s in out['step_s'][1:]]} s)")
        want = shape[0]
        check(all(len(x.sharding.device_set) == want for x in leaves),
              f"mesh {shape}: every parameter is placed on {want} device(s)")
        shard = out["batch_sharding"].shard_shape((batch, seq))
        check(shard == (batch // want, seq),
              f"mesh {shape}: each device holds a {shard} slice of the "
              f"({batch}, {seq}) batch")
        runs[shape] = out["losses"]
    diffs = [abs(a - b) for a, b in zip(runs[(n, 1)], runs[(1, 1)])]
    check(max(diffs) <= DP_LOSS_TOL,
          f"per-step losses match one device: max |diff| {max(diffs):.2e} "
          f"<= {DP_LOSS_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel trainer on four "
                         "chips against one device")
    args = ap.parse_args()
    t_all = time.perf_counter()
    dev = probe_device()
    log(f"device: {dev}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev['platform']!r}; refusing (no CPU fallback)")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{dev['count']} device(s)")
    if args.chips == 1:
        plan = stream_plan()
        procs = phase_stream_processes(plan)
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    import jax
    if args.chips == 1:
        phase_stream_threads(plan, procs)
        params = phase_train()
        phase_serve(params)
    else:
        phase_data_parallel(args.chips)
    d = jax.devices()
    log(f"smoke timing: whole script {time.perf_counter() - t_all:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

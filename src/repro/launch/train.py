"""End-to-end trainer (example driver; runs real steps on CPU or TPU).

Wires together: config -> mesh + shardings -> data pipeline -> jitted train
step -> async checkpointing with resume.  The same path the dry-run lowers is
the path that executes here.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import checkpoint as ckpt
from repro.configs import get
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch import shardings as SH
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import batch_axes, make_mesh
from repro.launch.steps import make_train_step
from repro.models import frontends, model_api
from repro.models import partitioning as part
from repro.optim.optimizers import adamw, warmup_cosine


def train(arch: str, smoke: bool = True, steps: int = 50, batch: int = 8,
          seq: int = 128, lr: float = 3e-4, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 25, mesh_shape=None, log_every: int = 10,
          width_mult: int = 1, seed: int = 0):
    cfg = get(arch, smoke=smoke)
    if width_mult > 1:                          # scale toward ~100M on demand
        cfg = dataclasses.replace(
            cfg, d_model=cfg.d_model * width_mult,
            d_ff=cfg.d_ff * width_mult)
    api = model_api(cfg)
    n_dev = len(jax.devices())
    mesh = make_mesh(mesh_shape or (n_dev, 1), ("data", "model"))
    part.set_mesh(mesh, batch_axes(mesh))

    optimizer = adamw(warmup_cosine(lr, warmup=max(steps // 10, 1),
                                    total=steps))
    key = jax.random.PRNGKey(seed)
    params = api.init(key, cfg)
    p_shards = SH.param_shardings(cfg, params, mesh, fsdp=False)
    params = jax.device_put(params, p_shards)
    # the moments inherit the param shardings; scalars (the step count) are
    # replicated over the mesh, as the step returns them — otherwise the
    # second step would compile again
    opt_state = jax.tree.map(
        lambda x: x if x.ndim else jax.device_put(x, NamedSharding(mesh, P())),
        optimizer.init(params))
    # the global batch splits over the data axes (data parallelism)
    b_shard = NamedSharding(mesh, SH.batch_pspec(mesh, batch,
                                                  pure_dp=cfg.pure_dp))

    source = SyntheticLM(batch, seq, cfg.vocab, seed=seed)
    start_step = 0
    if ckpt_dir:
        last = ckpt.latest_step(ckpt_dir)
        if last is not None:
            opt_shards = jax.tree.map(lambda x: x.sharding, opt_state)
            (params, opt_state), extra = ckpt.restore(
                ckpt_dir, last, (params, opt_state),
                shardings=(p_shards, opt_shards))
            source.restore(extra["data"])
            start_step = last
            print(f"[train] resumed from step {last}")
    data = Prefetcher(source)
    saver = ckpt.AsyncCheckpointer()

    step_fn = jax.jit(make_train_step(cfg, optimizer),
                      donate_argnums=(0, 1))
    losses, step_s = [], []
    t0 = time.time()
    with mesh:
        for step in range(start_step, steps):
            ts = time.perf_counter()
            raw = data.next_batch()
            b = jax.device_put({"inputs": raw["inputs"],
                                "labels": raw["labels"]}, b_shard)
            if cfg.family == "vlm":
                emb = frontends.image_patches(
                    jax.random.fold_in(key, step), cfg, batch)
                text = params["embed"][b["inputs"][:, :seq - cfg.img_tokens]]
                b = {"embeds": jnp.concatenate(
                        [emb.astype(text.dtype), text], axis=1),
                     "labels": b["labels"]}
            elif cfg.family == "audio":
                b["frames"] = frontends.audio_frames(
                    jax.random.fold_in(key, step), cfg, batch)
            params, opt_state, metrics = jax.block_until_ready(
                step_fn(params, opt_state, b))
            step_s.append(time.perf_counter() - ts)
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                print(f"[train] step={step} loss={losses[-1]:.4f} "
                      f"grad_norm={float(metrics['grad_norm']):.3f} "
                      f"({dt:.1f}s)", flush=True)
            if ckpt_dir and (step + 1) % ckpt_every == 0:
                saver.save(ckpt_dir, step + 1, (params, opt_state),
                           extra={"data": source.state(),
                                  "loss": losses[-1]})
    saver.join()
    data.close()
    part.set_mesh(None)
    return {"losses": losses, "step_s": step_s, "params": params, "cfg": cfg,
            "batch_sharding": b_shard}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (TPU scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--width-mult", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()
    use_compile_cache()
    out = train(args.arch, smoke=not args.full, steps=args.steps,
                batch=args.batch, seq=args.seq, lr=args.lr,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                width_mult=args.width_mult)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    print(f"[train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()

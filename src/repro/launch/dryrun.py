"""Multi-pod dry-run driver (deliverable e).

For every (arch x input-shape x mesh) cell: build ShapeDtypeStruct inputs,
``jax.jit(step, in_shardings, out_shardings).lower(...).compile()`` on the
production mesh, record ``memory_analysis()`` / ``cost_analysis()`` and the
collective schedule parsed from the partitioned HLO.

Scan correction (EXPERIMENTS.md §Roofline methodology): XLA cost_analysis
counts a while-loop body ONCE regardless of trip count, and layer stacks run
under ``lax.scan``.  The driver therefore additionally lowers the *period
body* (fwd+bwd for train, decode body for serve) under the same shardings and
reports   total = full_step + (n_repeats - 1) * body   for flops, bytes and
collective bytes.  sLSTM's time-scan gets an analytic recurrent-FLOPs
correction (the only non-associative recurrence in the zoo).

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
Cells already present in --out are skipped (resumable sweep).
"""
import argparse
import dataclasses
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from jax.tree_util import DictKey

from repro.configs import all_archs, get
from repro.launch import shardings as SH
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, cell_plan, input_specs
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models import model_api
from repro.models.config import ModelConfig
from repro.optim.optimizers import pick_optimizer

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(
    r"\b(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
          "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1}


def _first_shape_bytes(line: str, op: str = None) -> float:
    """Bytes of the (possibly tuple) result shape on an HLO op line."""
    total = 0.0
    # result shape sits between '=' and the op name; tuple shapes are
    # parenthesised so we cut at the op token, not the first '('
    lhs = line.split("=", 1)
    hay = lhs[1] if len(lhs) > 1 else line
    if op is not None and f"{op}(" in hay:
        hay = hay.split(f"{op}(", 1)[0]
    else:
        hay = hay.split("(", 1)[0]
    for m in _SHAPE_RE.finditer(hay):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op, by kind.

    Uses the *partitioned* module text, so shapes are per-device; bytes are
    per-device traffic (result size ~= payload for AG/AR/A2A/CP; RS result is
    the reduced shard — we scale by the group factor conservatively below in
    roofline, not here)."""
    out = {k: 0.0 for k in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("%") or ls.startswith("ROOT"):
            for kind in COLLECTIVES:
                # match ' all-reduce(' / ' all-gather(' etc as the op
                if f" {kind}(" in ls or f"= {kind}(" in ls or \
                        re.search(rf"\b{kind}(\.\d+)?\(", ls):
                    out[kind] += _first_shape_bytes(ls, kind)
                    out["count"] += 1
                    break
    return out


def _sharded_specs(tree, shards):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shards)


def _bytes_per_device(tree, shards, mesh) -> float:
    n_dev = int(np.prod(list(mesh.shape.values())))
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(
            shards, is_leaf=lambda x: hasattr(x, "spec"))):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        shard_frac = 1.0
        spec = sh.spec
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            for a in axes:
                shard_frac /= mesh.shape[a]
        total += n * leaf.dtype.itemsize * shard_frac
    return total


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str                     # ok | skipped | error
    reason: str = ""
    wall_s: float = 0.0
    flops: float = 0.0              # scan-corrected, whole step, all devices
    bytes_accessed: float = 0.0
    coll: Optional[Dict[str, float]] = None
    peak_bytes_per_device: float = 0.0
    param_bytes_per_device: float = 0.0
    opt_bytes_per_device: float = 0.0
    cache_bytes_per_device: float = 0.0
    n_params: float = 0.0
    n_active: float = 0.0
    optimizer: str = ""
    body_repeats: int = 0
    extra_flops: float = 0.0        # analytic corrections (sLSTM time scan)

    def to_json(self):
        return json.dumps(dataclasses.asdict(self))


def _cost(compiled) -> Dict[str, float]:
    try:
        c = compiled.cost_analysis()
        return {"flops": float(c.get("flops", 0.0)),
                "bytes": float(c.get("bytes accessed", 0.0))}
    except Exception:
        return {"flops": 0.0, "bytes": 0.0}


def _memory(compiled) -> float:
    try:
        m = compiled.memory_analysis()
        return float(getattr(m, "temp_size_in_bytes", 0) +
                     getattr(m, "argument_size_in_bytes", 0) +
                     getattr(m, "output_size_in_bytes", 0) / 2)
    except Exception:
        return 0.0


def _slstm_extra_flops(cfg: ModelConfig, seq: int, batch: int) -> float:
    """Analytic recurrent FLOPs for sLSTM time-scan (counted once by XLA)."""
    n_slstm = sum(1 for mixer, _ in cfg.blocks() if mixer == "slstm")
    if n_slstm == 0:
        return 0.0
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    per_step = 4 * h * dh * dh * 2 + 10 * d        # recurrent matvecs + gates
    return float(n_slstm * batch * (seq - 1) * per_step)


# --------------------------------------------------------------------------

def _slice_param_shards(slice_shapes, cfg, mesh, fsdp):
    """Shardings for one scan-body slice: compute the stacked spec under a
    fake ('stack', ...) path and strip the leading layer axis."""
    def one(path, leaf):
        fake = jax.ShapeDtypeStruct((1,) + leaf.shape, leaf.dtype)
        spec = SH.param_pspec((DictKey("stack"),) + path, fake, cfg, mesh,
                              fsdp)
        return NamedSharding(mesh, PartitionSpec(*tuple(spec)[1:]))
    return jax.tree_util.tree_map_with_path(one, slice_shapes)


def _slice_cache_shards(slice_shapes, cfg, mesh):
    def one(path, leaf):
        fake = jax.ShapeDtypeStruct((1,) + leaf.shape, leaf.dtype)
        spec = SH.cache_pspec((DictKey("stack"),) + path, fake, cfg, mesh)
        return NamedSharding(mesh, PartitionSpec(*tuple(spec)[1:]))
    return jax.tree_util.tree_map_with_path(one, slice_shapes)


def _stack_slice_shapes(cfg):
    from repro.models import transformer
    stack = jax.eval_shape(
        lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0))["stack"]
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stack)


def _body_x_shard(cfg, mesh, batch, extra_dims):
    """x sharding for body lowering — must mirror the full step's batch
    sharding (incl. pure_dp / seq_shard modes) or the x(n_periods-1)
    correction is computed at the wrong parallelism."""
    spec = SH.batch_pspec(mesh, batch, extra_dims, pure_dp=cfg.pure_dp)
    if cfg.seq_shard and extra_dims >= 2:
        spec = PartitionSpec(spec[0], "model",
                             *([None] * (extra_dims - 1)))
    return NamedSharding(mesh, spec)


def lower_body_train(cfg, mesh, seq, batch, fsdp, wrt="both"):
    """Lower one period super-block fwd+bwd under matching shardings.

    ``wrt="both"`` (params + activations) is used for the FLOPs/bytes
    correction.  ``wrt="x"`` is used for the *collective* correction: the
    parameter-gradient all-reduce/reduce-scatter happens ONCE per step on the
    stacked gradients (outside the layer scan) and is already present in the
    full-step HLO, so the per-layer body must not re-count it; per-layer
    activation collectives (TP psums, FSDP weight gathers) remain."""
    from repro.models import transformer
    slice_shapes = _stack_slice_shapes(cfg)
    shards = _slice_param_shards(slice_shapes, cfg, mesh, fsdp)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_shard = _body_x_shard(cfg, mesh, batch, 2)

    def body_loss(stack_slice, x):
        positions = jnp.arange(x.shape[1])
        for i, spec in enumerate(cfg.period):
            x, _ = transformer.block_apply(stack_slice[f"pos{i}"], x, spec,
                                           cfg, positions)
        return jnp.sum(x.astype(jnp.float32))

    grad_fn = jax.grad(body_loss, argnums=(0, 1) if wrt == "both" else (1,))
    lowered = jax.jit(grad_fn, in_shardings=(shards, x_shard)).lower(
        _sharded_specs(slice_shapes, shards),
        jax.ShapeDtypeStruct((batch, seq, cfg.d_model), dt,
                             sharding=x_shard))
    return lowered.compile()


def lower_body_prefill(cfg, mesh, seq, batch, fsdp):
    from repro.models import transformer
    slice_shapes = _stack_slice_shapes(cfg)
    shards = _slice_param_shards(slice_shapes, cfg, mesh, fsdp)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_shard = _body_x_shard(cfg, mesh, batch, 2)

    def body(stack_slice, x):
        positions = jnp.arange(x.shape[1])
        for i, spec in enumerate(cfg.period):
            x, _ = transformer.block_apply(stack_slice[f"pos{i}"], x, spec,
                                           cfg, positions)
        return x

    lowered = jax.jit(body, in_shardings=(shards, x_shard)).lower(
        _sharded_specs(slice_shapes, shards),
        jax.ShapeDtypeStruct((batch, seq, cfg.d_model), dt,
                             sharding=x_shard))
    return lowered.compile()


def lower_body_decode(cfg, mesh, seq, batch):
    from repro.models import transformer
    slice_shapes = _stack_slice_shapes(cfg)
    p_shards = _slice_param_shards(slice_shapes, cfg, mesh, False)
    cache_full = jax.eval_shape(
        lambda: model_api(cfg).init_cache(cfg, batch, max_len=seq))
    cache_slice = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        cache_full["stack"])
    c_shards = _slice_cache_shards(cache_slice, cfg, mesh)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_shard = _body_x_shard(cfg, mesh, batch, 1)

    def body(stack_slice, cache_slice, x, pos):
        new_c = {}
        for i, spec in enumerate(cfg.period):
            x, new_c[f"pos{i}"] = transformer.block_decode(
                stack_slice[f"pos{i}"], x, cache_slice[f"pos{i}"], spec,
                cfg, pos)
        return x, new_c

    lowered = jax.jit(body,
                      in_shardings=(p_shards, c_shards, x_shard, None)
                      ).lower(
        _sharded_specs(slice_shapes, p_shards),
        _sharded_specs(cache_slice, c_shards),
        jax.ShapeDtypeStruct((batch, cfg.d_model), dt, sharding=x_shard),
        jax.ShapeDtypeStruct((), jnp.int32))
    return lowered.compile()


# --------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fsdp_threshold: float = 8e9) -> CellResult:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = get(arch)
    t0 = time.time()
    skip = cell_plan(cfg, shape_name)
    if skip:
        return CellResult(arch, shape_name, mesh_name, "skipped", skip)
    seq, batch, kind = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_params, n_active = cfg.param_count()
    fsdp = cfg.force_fsdp or n_params > fsdp_threshold
    api = model_api(cfg)
    res = CellResult(arch, shape_name, mesh_name, "ok",
                     n_params=float(n_params), n_active=float(n_active))

    params_shapes = jax.eval_shape(lambda k: api.init(k, cfg),
                                   jax.random.PRNGKey(0))
    p_shards = SH.param_shardings(cfg, params_shapes, mesh, fsdp)
    res.param_bytes_per_device = _bytes_per_device(params_shapes, p_shards,
                                                   mesh)

    from repro.models import partitioning as part
    from repro.launch.mesh import batch_axes as _ba
    part.set_mesh(mesh, _ba(mesh))
    with mesh:
        if kind == "train":
            opt_name, optimizer = pick_optimizer(n_params, 1e-4)
            res.optimizer = opt_name
            opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
            o_shards = SH.param_shardings(cfg, opt_shapes, mesh, fsdp)
            # moments mirror params; stats trees reuse the param rule per leaf
            res.opt_bytes_per_device = _bytes_per_device(opt_shapes, o_shards,
                                                         mesh)
            batch_specs = input_specs(cfg, shape_name)
            b_shards = SH.input_shardings(cfg, batch_specs, mesh)
            step = make_train_step(cfg, optimizer)
            lowered = jax.jit(
                step, in_shardings=(p_shards, o_shards, b_shards),
                donate_argnums=(0, 1)).lower(
                _sharded_specs(params_shapes, p_shards),
                _sharded_specs(opt_shapes, o_shards),
                _sharded_specs(batch_specs, b_shards))
            compiled = lowered.compile()
            cost = _cost(compiled)
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
            res.body_repeats = cfg.n_periods
            body_cost = {"flops": 0.0, "bytes": 0.0}
            body_coll = {k: 0.0 for k in coll}
            if cfg.scan_layers and cfg.n_periods > 1 and not cfg.is_encdec:
                body = lower_body_train(cfg, mesh, seq, batch, fsdp)
                body_cost = _cost(body)
                body_x = lower_body_train(cfg, mesh, seq, batch, fsdp,
                                          wrt="x")
                body_coll = collective_bytes(body_x.as_text())
            rep = max(cfg.n_periods - 1, 0)
            res.flops = cost["flops"] + rep * body_cost["flops"]
            res.bytes_accessed = cost["bytes"] + rep * body_cost["bytes"]
            res.coll = {k: coll.get(k, 0.0) + rep * body_coll.get(k, 0.0)
                        for k in coll}
            res.extra_flops = _slstm_extra_flops(cfg, seq, batch) * 3  # fwd+bwd
            res.peak_bytes_per_device = _memory(compiled)
        elif kind == "prefill":
            batch_specs = input_specs(cfg, shape_name)
            b_shards = SH.input_shardings(cfg, batch_specs, mesh)
            step = make_prefill_step(cfg)
            lowered = jax.jit(step, in_shardings=(p_shards, b_shards)).lower(
                _sharded_specs(params_shapes, p_shards),
                _sharded_specs(batch_specs, b_shards))
            compiled = lowered.compile()
            cost = _cost(compiled)
            coll = collective_bytes(compiled.as_text())
            res.body_repeats = cfg.n_periods
            body_cost = {"flops": 0.0, "bytes": 0.0}
            body_coll = {k: 0.0 for k in coll}
            if cfg.scan_layers and cfg.n_periods > 1 and not cfg.is_encdec:
                body = lower_body_prefill(cfg, mesh, seq, batch, fsdp)
                body_cost = _cost(body)
                body_coll = collective_bytes(body.as_text())
            rep = max(cfg.n_periods - 1, 0)
            res.flops = cost["flops"] + rep * body_cost["flops"]
            res.bytes_accessed = cost["bytes"] + rep * body_cost["bytes"]
            res.coll = {k: coll.get(k, 0.0) + rep * body_coll.get(k, 0.0)
                        for k in coll}
            res.extra_flops = _slstm_extra_flops(cfg, seq, batch)
            res.peak_bytes_per_device = _memory(compiled)
        else:                                     # decode
            cache_specs, tok_spec, pos_spec = input_specs(cfg, shape_name)
            c_shards = SH.cache_shardings(cfg, cache_specs, mesh)
            res.cache_bytes_per_device = _bytes_per_device(
                cache_specs, c_shards, mesh)
            t_shard = NamedSharding(mesh, SH.batch_pspec(mesh, batch, 0))
            step = make_decode_step(cfg)
            lowered = jax.jit(
                step, in_shardings=(p_shards, c_shards, t_shard, None),
                donate_argnums=(1,)).lower(
                _sharded_specs(params_shapes, p_shards),
                _sharded_specs(cache_specs, c_shards),
                jax.ShapeDtypeStruct(tok_spec.shape, tok_spec.dtype,
                                     sharding=t_shard),
                jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
            cost = _cost(compiled)
            coll = collective_bytes(compiled.as_text())
            res.body_repeats = cfg.n_periods
            body_cost = {"flops": 0.0, "bytes": 0.0}
            body_coll = {k: 0.0 for k in coll}
            if cfg.scan_layers and cfg.n_periods > 1 and not cfg.is_encdec:
                body = lower_body_decode(cfg, mesh, seq, batch)
                body_cost = _cost(body)
                body_coll = collective_bytes(body.as_text())
            rep = max(cfg.n_periods - 1, 0)
            res.flops = cost["flops"] + rep * body_cost["flops"]
            res.bytes_accessed = cost["bytes"] + rep * body_cost["bytes"]
            res.coll = {k: coll.get(k, 0.0) + rep * body_coll.get(k, 0.0)
                        for k in coll}
            res.extra_flops = _slstm_extra_flops(cfg, 1, batch)
            res.peak_bytes_per_device = _memory(compiled)
    part.set_mesh(None)
    res.wall_s = time.time() - t0
    return res


def lower_body_prefill(cfg, mesh, seq, batch, fsdp):
    from repro.models import transformer

    key = jax.random.PRNGKey(0)
    stack_shapes = jax.eval_shape(
        lambda k: transformer.init(k, cfg), key)["stack"]
    slice_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stack_shapes)
    shards = jax.tree_util.tree_map_with_path(
        lambda p, l: jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*SH.param_pspec(
                (jax.tree_util.DictKey("stack"),
                 jax.tree_util.DictKey("pos0"),) + p,
                jax.ShapeDtypeStruct((1,) + l.shape, l.dtype),
                cfg, mesh, fsdp)[1:])),
        slice_shapes)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_shard = NamedSharding(mesh, SH.batch_pspec(mesh, batch, 2))

    def body(stack_slice, x):
        positions = jnp.arange(x.shape[1])
        for i, spec in enumerate(cfg.period):
            x, _ = transformer.block_apply(stack_slice[f"pos{i}"], x, spec,
                                           cfg, positions)
        return x

    lowered = jax.jit(body, in_shardings=(shards, x_shard)).lower(
        _sharded_specs(slice_shapes, shards),
        jax.ShapeDtypeStruct((batch, seq, cfg.d_model), dt,
                             sharding=x_shard))
    return lowered.compile()


def main():
    # a compile-only tool over 512 virtual host devices: the flag must be
    # in place before the first backend use, and the platform is the CPU
    # even on a host with an accelerator (the mesh is never the local chip)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = all_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    done = set()
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                key = (arch.replace("_", "-"), shape, mesh_name)
                norm_key = (get(arch).name, shape, mesh_name)
                if args.out and (key in done or norm_key in done):
                    print(f"[skip existing] {arch} {shape} {mesh_name}")
                    continue
                print(f"[dryrun] {arch} {shape} {mesh_name} ...",
                      flush=True)
                try:
                    res = run_cell(arch, shape, mp)
                except Exception as e:
                    res = CellResult(arch, shape, mesh_name, "error",
                                     reason=f"{type(e).__name__}: {e}\n"
                                     + traceback.format_exc()[-2000:])
                res.arch = get(arch).name
                print(f"  -> {res.status} flops={res.flops:.3e} "
                      f"peak/dev={res.peak_bytes_per_device/2**30:.2f}GiB "
                      f"wall={res.wall_s:.1f}s "
                      f"{res.reason.splitlines()[0] if res.reason else ''}",
                      flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(res.to_json() + "\n")


if __name__ == "__main__":
    main()

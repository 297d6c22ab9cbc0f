"""PartitionSpec rules: parameter, optimizer-state, input and cache sharding.

Layout (DESIGN.md §5):
* TP over ``model``: attention heads / MoE experts / FFN hidden / SSM inner.
* FSDP (ZeRO-3) over ``data`` for parameters + optimizer state when
  ``fsdp=True`` (the non-TP dim of each large matrix) — gathered per scanned
  layer by GSPMD.
* Batch over ``pod`` x ``data``.
* Decode caches: batch-sharded when divisible; KV heads over ``model`` when
  divisible, otherwise cache *length* over ``model`` (flash-decoding style);
  batch-1 long-context shards the length/state over every axis available.

Rules are path-based and block-type aware (the same leaf name 'wq' is an
output-sharded head projection in attention but an input-sharded d_inner
matrix in mLSTM).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.models.config import ModelConfig
from .mesh import batch_axes, fsdp_axis

NORMS = {"ln1", "ln2", "ln_x", "final_norm", "enc_norm", "norm", "q_norm",
         "kv_norm", "norm_h", "norm_e"}
REPLICATED = NORMS | {"b", "gate_bias", "dt_bias", "router", "router_bias",
                      "w_gates",
                      "enc_pos", "dec_pos", "r", "wkr"}
ATTN_QKV = {"wq", "wk", "wv", "wuq", "wukv", "wdq", "wdkv"}


def _names(path) -> list:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
    return out


def _mixer_of(names, cfg: ModelConfig) -> Optional[str]:
    if any(n in ("self", "cross", "attn") for n in names):
        return "attn"
    if "mixer" in names:
        pos = [n for n in names if n.startswith("pos")]
        if pos:
            return cfg.period[int(pos[0][3:])][0]
        return cfg.period[0][0]          # prefix / mtp block
    return None


def _divisible(mesh: Mesh, axis, size: int) -> bool:
    if axis is None:
        return True
    axes = (axis,) if isinstance(axis, str) else axis
    prod = int(np.prod([mesh.shape[a] for a in axes]))
    return size % prod == 0


def _guard(spec: Tuple, shape, mesh: Mesh) -> P:
    """Drop axes that don't divide the corresponding dim."""
    fixed = []
    for dim, ax in zip(shape, spec):
        fixed.append(ax if ax is not None and _divisible(mesh, ax, dim)
                     else (ax if ax is None else None))
    return P(*fixed)


def param_pspec(path, leaf, cfg: ModelConfig, mesh: Mesh,
                fsdp: bool) -> P:
    if cfg.pure_dp:
        return P(*([None] * leaf.ndim))
    names = _names(path)
    n = names[-1]
    f = fsdp_axis(mesh) if fsdp else None
    stacked = any(x in names for x in ("stack", "encoder", "decoder"))
    core = leaf.ndim - (1 if stacked else 0)

    def out(*spec):
        spec = (None,) * (core - len(spec)) + spec if len(spec) < core else spec
        full = ((None,) if stacked else ()) + tuple(spec)
        return _guard(full, leaf.shape, mesh)

    if n in REPLICATED or core == 0:
        # SSM per-channel vectors still shard over model when sized d_inner
        if n in ("A_log",):
            return out("model", None)
        if n in ("D",) and core == 1:
            return out("model")
        return out(*([None] * core))
    if n == "embed":
        return out("model", f)
    if n == "head":
        return out(f, "model")
    if n == "proj" and "mtp" in names:
        return out(f, "model")
    if "experts" in names:
        if n in ("gate", "up"):
            return out("model", f, None)
        if n == "down":
            return out("model", None, f)
    mixer = _mixer_of(names, cfg)
    if n in ATTN_QKV and mixer in ("attn", "mla", None):
        return out(f, "model")
    if n == "wo":
        return out("model", f)
    if n in ("gate", "up"):                      # dense MLP / shared expert
        return out(f, "model")
    if n == "down":
        return out("model", f)
    if mixer == "mamba":
        table = {"in_proj": (f, "model"), "conv": ("model", None),
                 "x_proj": ("model", None), "dt_proj": (None, "model"),
                 "A_log": ("model", None), "D": ("model",),
                 "out_proj": ("model", f)}
        if n in table:
            return out(*table[n])
    if mixer == "mlstm":
        table = {"in_proj": (f, "model"), "conv": ("model", None),
                 "wq": ("model", None), "wk": ("model", None),
                 "wv": ("model", None), "out_proj": ("model", f)}
        if n in table:
            return out(*table[n])
    if mixer == "slstm":
        table = {"w": (f, "model"), "out_proj": ("model", f)}
        if n in table:
            return out(*table[n])
    if n in ("wq", "wk", "wv"):                  # whisper enc/dec attention
        return out(f, "model")
    return out(*([None] * core))


def param_shardings(cfg: ModelConfig, tree, mesh: Mesh, fsdp: bool):
    """Tree of NamedShardings matching ``tree`` (params or shape pytree)."""
    def one(path, leaf):
        return NamedSharding(mesh, param_pspec(path, leaf, cfg, mesh, fsdp))
    return jax.tree_util.tree_map_with_path(one, tree)


# --------------------------------------------------------------------------
# Inputs and caches
# --------------------------------------------------------------------------

def batch_pspec(mesh: Mesh, batch: int, extra_dims: int = 1,
                pure_dp: bool = False) -> P:
    ba = tuple(mesh.axis_names) if pure_dp else batch_axes(mesh)
    if not _divisible(mesh, ba, batch):
        ba = batch_axes(mesh)
        if not _divisible(mesh, ba, batch):
            ba = None
    return P(ba, *([None] * extra_dims))


def input_shardings(cfg: ModelConfig, batch_tree, mesh: Mesh):
    """Shardings for a train batch of ShapeDtypeStructs."""
    def one(path, leaf):
        spec = batch_pspec(mesh, leaf.shape[0], leaf.ndim - 1,
                           pure_dp=cfg.pure_dp)
        if cfg.seq_shard and leaf.ndim >= 2 and \
                _divisible(mesh, "model", leaf.shape[1]):
            # context parallelism: tokens sharded over 'model'
            spec = P(spec[0], "model", *([None] * (leaf.ndim - 2)))
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, batch_tree)


def cache_pspec(path, leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    names = _names(path)
    n = names[-1]
    # 'stack' (decoder-only) and encdec 'self'/'cross' carry a leading L dim
    stacked = "stack" in names or ("self" in names or "cross" in names)
    lead = (None,) if stacked else ()
    core_shape = leaf.shape[1:] if stacked else leaf.shape
    b = core_shape[0]
    ba = batch_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    b_ok = _divisible(mesh, ba, b) and b >= int(
        np.prod([mesh.shape[a] for a in ba]))

    def guard(*spec):
        return _guard(lead + spec, leaf.shape, mesh)

    if n in ("k", "v"):                          # (B, Hkv, C, hd)
        hkv, c = core_shape[1], core_shape[2]
        if b_ok:
            if _divisible(mesh, "model", hkv):
                return guard(ba, "model", None, None)
            return guard(ba, None, "model", None)
        # batch-1 long context: shard the cache length over everything
        if _divisible(mesh, all_axes, c):
            return guard(None, None, all_axes, None)
        return guard(None, None, ("data", "model"), None)
    if n in ("c_kv", "k_rope"):                  # MLA (B, S, r)
        if b_ok:
            return guard(ba, "model", None)
        return guard(None, ("data", "model"), None)
    if n == "conv":                              # (B, K-1, di)
        if b_ok:
            return guard(ba, None, "model")
        return guard(None, None, all_axes)
    if n == "h" and len(core_shape) == 3:        # mamba (B, di, N)
        if b_ok:
            return guard(ba, "model", None)
        return guard(None, all_axes, None)
    if n == "h" and len(core_shape) == 2:        # slstm (B, D)
        if b_ok:
            return guard(ba, "model")
        return guard(None, all_axes)
    if n == "C":                                 # mLSTM (B, H, dk, dv)
        if b_ok:
            return guard(ba, None, None, "model")
        return guard(None, None, None, "model")
    if n in ("n",):                              # mLSTM (B, H, dk) | slstm
        if b_ok:
            return guard(*((ba,) + (None,) * (len(core_shape) - 1)))
        return guard(*((None,) * len(core_shape)))
    if n == "m":
        if b_ok:
            return guard(*((ba,) + (None,) * (len(core_shape) - 1)))
        return guard(*((None,) * len(core_shape)))
    if n in ("c",):                              # slstm scalars (B, D)
        if b_ok:
            return guard(ba, "model")
        return guard(None, all_axes)
    # whisper cross kv tuple leaves: (L, B, Hkv, S_enc, hd)
    if leaf.ndim == 5:
        return _guard((None, ba if b_ok else None, None, None, None),
                      leaf.shape, mesh)
    if b_ok:
        return guard(*((ba,) + (None,) * (len(core_shape) - 1)))
    return guard(*((None,) * len(core_shape)))


def cache_shardings(cfg: ModelConfig, cache_tree, mesh: Mesh):
    def one(path, leaf):
        return NamedSharding(mesh, cache_pspec(path, leaf, cfg, mesh))
    return jax.tree_util.tree_map_with_path(one, cache_tree)

"""Public kernel entry points used by the model zoo.

Every op has three interchangeable implementations:

* ``impl="ref"``    — the naive oracle from :mod:`.ref` (tests, tiny shapes);
* ``impl="jnp"``    — the default: memory-bounded blockwise jnp, which for
  attention becomes a Pallas flash kernel with a backward where the call
  allows it and the program is lowered for a TPU (see below);
* ``impl="pallas"`` — the Pallas TPU kernels (interpret mode off-TPU).

Attention under ``impl="jnp"`` is one algorithm, online-softmax attention,
with two implementations chosen by what the call shows.  A call with no
sliding window, both sequence lengths multiples of 128 and whole groups of
query heads per kv head runs, when lowered for a TPU, in JAX's shipped
splash attention kernel, whose forward, dq and dkv kernels keep every score
block in VMEM.  Every other call, and every call lowered for another
platform (the CPU tests, the multi-device dry-run), runs the blockwise jnp
path.  The choice is ``lax.platform_dependent``'s, made when the program is
lowered, unless the caller passes a mesh of TPU devices: the program is
then built for a TPU and the call takes the kernel at trace time.
:func:`repro.obs.counts` counts both at trace time (``attention.kernel``,
``attention.blockwise``).

The blockwise jnp path implements *causal block skipping*: for causal and
sliding-window attention, key/value blocks that are entirely masked for a
query chunk are statically sliced away, so the compiled FLOPs reflect the
~2x triangle saving (visible in ``cost_analysis``).  The kernel skips fully
masked blocks too, but a Mosaic custom call carries no FLOP count: lowered
for a TPU, ``cost_analysis`` (and the dry-run that reads it) no longer sees
the attention's FLOPs.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
    splash_attention_mask as _splash_mask,
)

from repro import obs
from . import ref as _ref

NEG_INF = -1e30


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            impl: str = "jnp") -> jax.Array:
    if impl == "pallas":
        from .rmsnorm import rmsnorm_pallas
        return rmsnorm_pallas(x, scale, eps)
    return _ref.rmsnorm_ref(x, scale, eps)


# ---------------------------------------------------------------------------
# Blockwise (flash) attention
# ---------------------------------------------------------------------------

def _attend_block(qg, kc, vc, qpos, kpos, causal, window, scale, state,
                  kv_valid=None, kv_valid_lo=None):
    """Online-softmax update for one (q chunk, kv chunk) pair.

    qg: (B, Hkv, G, Qc, D); kc/vc: (B, Hkv, Kc, D); state = (acc, m, l).
    ``kv_valid``: exclusive upper bound on valid kv positions (padding).
    """
    acc, m, l = state
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kc.astype(jnp.float32)) * scale
    mask = jnp.ones((qg.shape[3], kc.shape[2]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    if kv_valid is not None:
        mask &= kpos[None, :] < kv_valid
    if kv_valid_lo is not None:          # traced lower bound (CP ring edges)
        mask = mask & (kpos[None, :] >= kv_valid_lo)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    # The running max only shifts the exponent, and softmax is unchanged by
    # a shift of its argument, so the output's gradient through m is zero.
    # Held constant, the backward keeps no max-location mask per block.
    m_new = jax.lax.stop_gradient(jnp.maximum(m, logits.max(axis=-1)))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
    return acc_new, m_new, l_new


def _flash_jnp(q, k, v, causal, window, offset, scale, q_chunk, kv_chunk,
               kv_valid_lo=None):
    b, h, sq0, d = q.shape
    _, hkv, skv0, _ = k.shape
    g = h // hkv
    q_chunk = min(q_chunk, sq0)
    kv_chunk = min(kv_chunk, skv0)
    # pad ragged sequence lengths up to chunk multiples (whisper's 1500
    # frames etc); padded kv columns are masked out, padded q rows dropped
    pq = (-sq0) % q_chunk
    pkv = (-skv0) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    sq, skv = sq0 + pq, skv0 + pkv
    nq = sq // q_chunk
    outs = []
    for i in range(nq):
        qg = q[:, :, i * q_chunk:(i + 1) * q_chunk].reshape(
            b, hkv, g, q_chunk, d).astype(jnp.float32)
        q_lo = offset + i * q_chunk
        q_hi = offset + (i + 1) * q_chunk - 1
        # static kv range: causal upper bound, sliding-window lower bound
        kv_end = skv if not causal else max(0, min(skv, q_hi + 1))
        kv_start = 0 if window is None else max(0, q_lo - window + 1)
        kv_start = (kv_start // kv_chunk) * kv_chunk
        kv_end = min(skv, math.ceil(kv_end / kv_chunk) * kv_chunk)
        n_blocks = (kv_end - kv_start) // kv_chunk
        if n_blocks <= 0:                     # fully-masked chunk (offset<0)
            outs.append(jnp.zeros((b, h, q_chunk, d), q.dtype))
            continue
        qpos = q_lo + jnp.arange(q_chunk)
        state = (jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32),
                 jnp.full((b, hkv, g, q_chunk), -jnp.inf, jnp.float32),
                 jnp.zeros((b, hkv, g, q_chunk), jnp.float32))
        # Static python loop over kv blocks: the compiled HLO contains only
        # the blocks that survive causal/window skipping, so cost_analysis
        # reflects the true triangle/window FLOPs (lax.scan would count the
        # body once regardless of trip count).
        for j in range(n_blocks):
            base = kv_start + j * kv_chunk
            kc = k[:, :, base:base + kv_chunk]
            vc = v[:, :, base:base + kv_chunk]
            kpos = base + jnp.arange(kv_chunk)
            state = _attend_block(qg, kc, vc, qpos, kpos, causal, window,
                                  scale, state,
                                  kv_valid=skv0 if pkv else None,
                                  kv_valid_lo=kv_valid_lo)
        acc, m, l = state
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(
            b, h, q_chunk, d)
        outs.append(out.astype(q.dtype))
    out = jnp.concatenate(outs, axis=2)
    return out[:, :, :sq0] if pq else out


# ---------------------------------------------------------------------------
# The flash kernel with a backward (splash attention)
# ---------------------------------------------------------------------------

def _splash_block_sizes(sq: int, skv: int) -> _splash.BlockSizes:
    """One block size for every kernel: the largest of 1024/512/256/128
    that divides both lengths.  The dkv kernel computes its tile 512 keys at
    a time: beside the tile it holds float32 dk and dv accumulators and
    double-buffered q, k, v and do blocks, and at 1024 x 1024 it overflows
    the 16 MiB of scoped VMEM at head dim 192 (moonlight's chunks over 6144
    and 7168 keys, compiled for a v5e)."""
    blk = next(b for b in (1024, 512, 256, 128)
               if sq % b == 0 and skv % b == 0)
    return _splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk,
        block_kv_dkv_compute=min(blk, 512),
        block_q_dq=blk, block_kv_dq=blk)


@functools.lru_cache(maxsize=64)
def _splash_kernel(heads: int, sq: int, skv: int, causal: bool, offset: int,
                   interpret: bool):
    """The splash kernel for one mask, built once: its mask info (which
    blocks are full, partial or empty) is numpy work on the host, and the
    layer scan, a remat retrace and an MLA layer's query chunks ask for the
    same masks again."""
    mask = (_splash_mask.CausalMask((sq, skv), offset=offset) if causal
            else _splash_mask.FullMask((sq, skv)))
    # concrete mask info even when first asked for inside a trace, so the
    # cached kernel holds no tracer
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(
            _splash_mask.MultiHeadMask([mask] * heads),
            block_sizes=_splash_block_sizes(sq, skv), head_shards=1,
            q_seq_shards=1, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "offset", "scale",
                                             "interpret"))
def _splash_attend(q, k, v, *, causal, offset, scale, interpret):
    """The splash kernel over one device's q (B, Hq, Sq, D), k (B, Hkv, Skv,
    D) and v (B, Hkv, Skv, Dv).  The batch folds into the heads: query head
    b*Hq + i reads kv head (b*Hq + i) // G = b*Hkv + i // G, as GQA asks."""
    b, h, sq, _ = q.shape
    kernel = _splash_kernel(b * h, sq, k.shape[2], causal, offset, interpret)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    fold = lambda x: x.reshape(-1, *x.shape[2:])
    return kernel(fold(q), fold(k), fold(v)).reshape(b, h, sq, v.shape[-1])


def _flash_splash(q, k, v, causal, offset, scale, mesh=None, batch_axes=(),
                  interpret=False):
    """Attention in the splash kernel: q (B, Hq, Sq, D), k (B, Hkv, Skv, D),
    v (B, Hkv, Skv, Dv), Hq a multiple of Hkv, both lengths multiples of
    128.  Operands stay in their dtype; the kernel accumulates and keeps its
    softmax statistics in float32.  ``mesh``: the devices the program runs
    on, with the batch split over ``batch_axes``."""
    attend = functools.partial(_splash_attend, causal=causal, offset=offset,
                               scale=scale, interpret=interpret)
    if mesh is None or mesh.size == 1:
        return attend(q, k, v)
    # XLA cannot partition a Mosaic kernel: on a mesh of several devices
    # each takes its share of the batch (and of the heads, split over the
    # "model" axis where the batch does not use it and they divide evenly)
    # in a call of its own
    ba = tuple(batch_axes) or None
    if ba and q.shape[0] % math.prod(mesh.shape[a] for a in ba):
        ba = None
    m = mesh.shape.get("model", 1)
    heads = ("model" if "model" not in (ba or ()) and q.shape[1] % m == 0
             and k.shape[1] % m == 0 else None)
    spec = jax.sharding.PartitionSpec(ba, heads, None, None)
    return jax.shard_map(attend, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _splash_eligible(q, k, window) -> bool:
    """Whether the kernel can take a call, from its shapes and mask."""
    return (window is None and q.shape[2] % 128 == 0
            and k.shape[2] % 128 == 0 and q.shape[1] % k.shape[1] == 0)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    offset: int = 0, scale: Optional[float] = None,
                    impl: str = "jnp", q_chunk: int = 512,
                    kv_chunk: int = 1024, mesh=None,
                    batch_axes: tuple = ()) -> jax.Array:
    """Blockwise attention with GQA, causal masking and sliding windows.

    q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D); returns (B, Hq, Sq, D).
    ``offset``: absolute position of q[0] relative to kv[0] (prefill chunks).
    Under ``impl="jnp"`` a call the splash kernel can take runs in it when
    lowered for a TPU (module docstring); ``q_chunk``/``kv_chunk`` size the
    blockwise path only.  ``mesh``: the mesh the program is sharded over,
    with the batch split over ``batch_axes``; the kernel runs per shard in
    it, and a mesh of TPU devices settles the platform at trace time.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    with obs.scope("attention"):
        if impl == "ref":
            return _ref.attention_ref(q, k, v, causal, window, offset, scale)
        if impl == "pallas":
            from .flash_attention import flash_attention_pallas
            return flash_attention_pallas(q, k, v, causal=causal,
                                          window=window, offset=offset,
                                          scale=scale)
        if not _splash_eligible(q, k, window):
            obs.count("attention.blockwise")
            return _flash_jnp(q, k, v, causal, window, offset, scale,
                              q_chunk, kv_chunk)
        obs.count("attention.kernel")
        kernel = functools.partial(_flash_splash, causal=causal,
                                   offset=offset, scale=scale, mesh=mesh,
                                   batch_axes=batch_axes)
        if mesh is not None and all(d.platform == "tpu"
                                    for d in mesh.devices.flat):
            # The program is built for these TPUs, so only the kernel is
            # traced.  Differentiating platform_dependent's conditional
            # traces the kernel's Pallas bodies again in each of its passes:
            # moonlight's whole step then traced in 16.06 s, against 10.39 s
            # with the blockwise path alone (one v5e host).
            return kernel(q, k, v)
        # Without TPU devices to go by, both branches are traced and
        # differentiated, each as a jitted function of the call's statics:
        # JAX caches the trace and derivatives of a jitted function for
        # the calls that repeat its shapes.
        return jax.lax.platform_dependent(
            q, k, v, tpu=kernel,
            default=functools.partial(
                _blockwise_jit, causal=causal, offset=offset, scale=scale,
                q_chunk=q_chunk, kv_chunk=kv_chunk))


_blockwise_jit = jax.jit(
    functools.partial(_flash_jnp, window=None),
    static_argnames=("causal", "offset", "scale", "q_chunk", "kv_chunk"))


def cp_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       mesh, axis: str = "model", causal: bool = True,
                       window: Optional[int] = None,
                       scale: Optional[float] = None,
                       q_chunk: int = 1024, kv_chunk: int = 1024,
                       batch_axes=None) -> jax.Array:
    """Context-parallel blockwise attention (shard_map ring gather).

    q/k/v: (B, H/ Hkv, S, D) with S sharded over ``axis``.  Each shard pulls
    the ``r`` previous shards' K/V via collective-permute — r = ceil(window/L)
    for sliding windows, n-1 for full causal — and runs the blockwise kernel
    in a *relative* frame (q row 0 sits at offset r*L), so causal/window
    block skipping stays static while a traced validity bound masks the
    ring edges.  Per-shard work is uniform (striped-attention-style balance);
    the collectives are the small K/V blocks instead of activation psums
    (EXPERIMENTS.md §Perf H2).
    """
    from jax.sharding import PartitionSpec as P
    n = mesh.shape[axis]
    sq = q.shape[2]
    assert sq % n == 0
    L = sq // n
    r = n - 1 if window is None else min(n - 1, -(-window // L))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # batch stays sharded over the data axes inside the shard_map (leaving it
    # unsharded forces a full-batch regather on entry — observed 16x blowup)
    ba = batch_axes
    if ba is None:
        axes = [a for a in mesh.axis_names if a != axis]
        ba = tuple(axes) if axes else None
    b = q.shape[0]
    import numpy as _np
    if ba and b % int(_np.prod([mesh.shape[a] for a in ba])) != 0:
        ba = None
    spec = P(ba, None, axis, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    def f(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        kparts, vparts = [kl], [vl]
        for step in range(1, r + 1):
            perm = [(i, i + step) for i in range(n - step)]
            kparts.insert(0, jax.lax.ppermute(kl, axis, perm))
            vparts.insert(0, jax.lax.ppermute(vl, axis, perm))
        kg = jnp.concatenate(kparts, axis=2)      # ((r+1)*L,) kv window
        vg = jnp.concatenate(vparts, axis=2)
        # relative frame: local q row j is absolute idx*L + j; extended kv
        # col c is absolute (idx-r)*L + c -> valid iff c >= (r - idx)*L
        lo = jnp.maximum((r - idx) * L, 0)
        return _flash_jnp(ql, kg, vg, causal, window, r * L, scale,
                          q_chunk, kv_chunk, kv_valid_lo=lo)

    with obs.scope("attention"):
        obs.count("attention.blockwise")
        return f(q, k, v)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: Optional[jax.Array] = None,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     impl: str = "jnp") -> jax.Array:
    """One-token attention vs. a KV cache. q: (B, Hq, D); k/v: (B, Hkv, S, D)."""
    if impl == "pallas":
        from .decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k, v, length=length, window=window,
                                       scale=scale)
    return _ref.decode_attention_ref(q, k, v, length, window, scale)


# ---------------------------------------------------------------------------
# Mamba selective scan
# ---------------------------------------------------------------------------

def mamba_scan(u: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, h0: Optional[jax.Array] = None,
               impl: str = "jnp"):
    """Selective SSM scan. Shapes as :func:`repro.kernels.ref.mamba_scan_ref`.

    The jnp path is a `lax.scan` over time — O(T) sequential, O(1) state
    memory; the Pallas path tiles d_inner into VMEM blocks.
    Returns (y (Bt,T,d_in), h_T (Bt,d_in,N)).
    """
    if impl == "ref":
        return _ref.mamba_scan_ref(u, dt, A, B, C, D, h0)
    if impl == "pallas":
        from .mamba_scan import mamba_scan_pallas
        return mamba_scan_pallas(u, dt, A, B, C, D, h0)
    bt, t, d_in = u.shape
    n = A.shape[1]
    h_init = jnp.zeros((bt, d_in, n), jnp.float32) if h0 is None else \
        h0.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Df = D.astype(jnp.float32)

    def step(h, xs):
        ut, dtt, Bt_, Ct = xs
        da = jnp.exp(dtt[..., None] * Af[None])            # (Bt, d_in, N)
        db = dtt[..., None] * Bt_[:, None, :]              # (Bt, d_in, N)
        h = da * h + db * ut[..., None]
        y = jnp.einsum("bdn,bn->bd", h, Ct) + Df * ut
        return h, y

    xs = (jnp.moveaxis(u, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(B, 1, 0).astype(jnp.float32),
          jnp.moveaxis(C, 1, 0).astype(jnp.float32))
    h_last, ys = jax.lax.scan(step, h_init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), h_last


def mamba_step(u, dt, A, B, C, D, h):
    """Single decode step: u/dt (Bt, d_in); B/C (Bt, N); h (Bt, d_in, N)."""
    da = jnp.exp(dt.astype(jnp.float32)[..., None] * A.astype(jnp.float32))
    db = dt.astype(jnp.float32)[..., None] * B.astype(jnp.float32)[:, None, :]
    h = da * h + db * u.astype(jnp.float32)[..., None]
    y = jnp.einsum("bdn,bn->bd", h, C.astype(jnp.float32)) \
        + D.astype(jnp.float32) * u.astype(jnp.float32)
    return y.astype(u.dtype), h

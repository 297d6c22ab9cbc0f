"""The Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests (test_kernels_pallas.py) check numerics but never meet
Mosaic's tiling and layout rules.  Here each kernel is lowered with
``interpret=False`` against a *described* v5e (no chip attached) and
compiled by the TPU compiler that ships with libtpu.  Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and the test runner's workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def test_flash_attention_compiles_at_smollm_widths(one_chip):
    # smollm-360m's prefill: 15 query heads over 5 kv heads, head_dim 64;
    # the entry point, lowered for the chip, takes the splash kernel
    _compile(lambda q, k, v: ops.flash_attention(q, k, v),
             [((1, 15, 2048, 64), BF16), ((1, 5, 2048, 64), BF16),
              ((1, 5, 2048, 64), BF16)], one_chip)


def test_jnp_attention_backward_bytes_at_smollm_cell(one_chip):
    # one smollm-360m layer of the train cell on the blockwise path: batch
    # 8 x 2048, chunks of 1024, forward, remat recompute and backward as the
    # model's checkpoint runs them.  Held constant in the backward, the
    # running max leaves no max-location mask per score block: 11.7e9 bytes
    # (15.6e9 without).  The entry point would take the splash kernel here
    # (1.5e9 bytes), so the blockwise path is called directly.
    attn = jax.checkpoint(
        lambda q, k, v: ops._flash_jnp(q, k, v, True, None, 0, 64 ** -0.5,
                                       1024, 1024),
        policy=jax.checkpoint_policies.nothing_saveable)

    def loss(q, k, v, ct):
        return jnp.sum(attn(q, k, v).astype(F32) * ct)

    shapes = [((8, 15, 2048, 64), BF16), ((8, 5, 2048, 64), BF16),
              ((8, 5, 2048, 64), BF16), ((8, 15, 2048, 64), F32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    assert compiled.cost_analysis()["bytes accessed"] < 13e9


def _attention_grad(attend, shapes, sharding):
    """The gradient of one checkpointed attention call, as a remat layer
    runs it (forward, recompute, backward), compiled."""
    attn = jax.checkpoint(attend,
                          policy=jax.checkpoint_policies.nothing_saveable)

    def loss(q, k, v, ct):
        return jnp.sum(attn(q, k, v).astype(F32) * ct)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()


# (q, kv, offset): smollm-360m's layer in the train cell; moonlight's
# 1024-query chunks at head dim 192 (v padded to it): the last, over 8192
# keys, and the one over 7168, whose dkv kernel overflowed the scoped VMEM
# at 1024 x 1024 tiles
TRAIN_ATTENTION = {
    "smollm_360m": ((8, 15, 2048, 64), (8, 5, 2048, 64), 0),
    "moonlight_last_chunk": ((2, 16, 1024, 192), (2, 16, 8192, 192), 7168),
    "moonlight_chunk_7168": ((2, 16, 1024, 192), (2, 16, 7168, 192), 6144),
}


@pytest.mark.parametrize("cell", sorted(TRAIN_ATTENTION))
def test_attention_kernel_backward_compiles_in_less_temp(one_chip, cell):
    # the train cells' attention takes the splash kernel on a TPU: its
    # forward, dq and dkv kernels keep the score blocks in VMEM, so its
    # temp is below the blockwise path's at the same call
    qs, kvs, off = TRAIN_ATTENTION[cell]
    shapes = [(qs, BF16), (kvs, BF16), (kvs, BF16), (qs, F32)]
    kernel = _attention_grad(
        lambda q, k, v: ops.flash_attention(q, k, v, offset=off), shapes,
        one_chip)
    assert kernel.as_text().count("tpu_custom_call") >= 3
    blockwise = _attention_grad(
        lambda q, k, v: ops._flash_jnp(q, k, v, True, None, off,
                                       qs[-1] ** -0.5, 1024, 1024),
        shapes, one_chip)
    assert "tpu_custom_call" not in blockwise.as_text()
    assert (kernel.memory_analysis().temp_size_in_bytes
            < blockwise.memory_analysis().temp_size_in_bytes)


# (mesh shape, axes the batch splits over): data parallel on four chips;
# batch over "data" and heads over "model"; pure data parallelism over both
MESHES = [((4, 1), ("data",)), ((2, 2), ("data",)),
          ((2, 2), ("data", "model"))]


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_attention_kernel_compiles_on_a_mesh_of_four(topo, mesh_shape):
    # XLA cannot partition a Mosaic kernel: on a mesh of several chips,
    # passed in by the caller, each takes its share of the batch (and
    # heads) in a call of its own, or lowering fails
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    shape, batch_axes = mesh_shape
    mesh = Mesh(np.array(topo.devices).reshape(shape),
                ("data", "model"))
    q, kv = (8, 16, 1024, 64), (8, 4, 1024, 64)
    compiled = _attention_grad(
        functools.partial(ops.flash_attention, mesh=mesh,
                          batch_axes=batch_axes),
        [(q, BF16), (kv, BF16), (kv, BF16), (q, F32)],
        NamedSharding(mesh, P(batch_axes)))
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("pure_dp", [False, True])
def test_attention_layer_gives_the_kernel_its_mesh(topo, pure_dp):
    # the model's layer passes the registered mesh and its batch axes on,
    # so the training step on several chips lowers the kernel per shard,
    # and the kernel alone is traced
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get
    from repro.models import layers
    from repro.models import partitioning as part
    cfg = dataclasses.replace(get("smollm_360m", smoke=True), n_heads=6,
                              n_kv_heads=2, pure_dp=pure_dp)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    params = jax.eval_shape(
        lambda: layers.attn_init(jax.random.PRNGKey(0), cfg, BF16))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), params)
    x = jax.ShapeDtypeStruct((4, 256, cfg.d_model), BF16,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        out = layers.attn_apply(p, x, cfg, jnp.arange(256))
        return jnp.sum(out.astype(F32))

    with part.use_mesh(mesh, ("data",)):
        traced = jax.jit(jax.grad(loss)).trace(params, x)
    # a mesh of TPUs settles the platform when the step is traced: no
    # platform-dependent conditional, no blockwise branch
    assert "platform_index" not in str(traced.jaxpr)
    compiled = traced.lower().compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_decode_attention_compiles(one_chip):
    _compile(lambda q, k, v, n: decode_attention_pallas(
                 q, k, v, length=n, interpret=False),
             [((8, 15, 64), BF16), ((8, 5, 4096, 64), BF16),
              ((8, 5, 4096, 64), BF16), ((8,), I32)], one_chip)


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, s: rmsnorm_pallas(x, s, interpret=False),
             [((4096, 960), BF16), ((960,), BF16)], one_chip)


def test_mamba_scan_compiles_at_jamba_slice(one_chip):
    # Jamba-1.5: d_inner 16384, state 16; one 256-step slice of one sequence
    t, d_in, n = 256, 16384, 16
    _compile(lambda u, dt, a, b, c, d: mamba_scan_pallas(
                 u, dt, a, b, c, d, interpret=False),
             [((1, t, d_in), BF16), ((1, t, d_in), BF16), ((d_in, n), F32),
              ((1, t, n), BF16), ((1, t, n), BF16), ((d_in,), F32)],
             one_chip)


def test_moonlight_train_step_fits_one_chip(one_chip):
    # the moonlight-16b-a3b.train-s8192 cell's whole train step at its real
    # shapes: 568.5M parameters with AdamW state, batch 2 x 8192, blockwise
    # attention at head dim 192 and the dropless grouped matmuls.  5.69e9
    # bytes of arguments and 8.67e9 of temp (16.85e9 before latent
    # attention recomputed its query chunks)
    import json
    from pathlib import Path

    from repro.launch.steps import make_train_step
    from repro.models import model_api
    from repro.models.config import ModelConfig
    from repro.optim.optimizers import adamw

    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    m = json.loads((chip / "configs" / "moonlight-16b-a3b.json").read_text())
    tr = json.loads((chip / "traffic" / "train-s8192.json").read_text())
    m = dict(m["model"], period=tuple(map(tuple, m["model"]["period"])),
             router_bias_speed=tr["bias_speed"],
             router_aux_weight=tr["balance_alpha"])
    cfg = ModelConfig(**m)
    opt = adamw(3e-4)
    api = model_api(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), I32,
                                     sharding=one_chip)
             for k in ("inputs", "labels")}
    compiled = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1)) \
        .lower(on_chip(params), on_chip(state), batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9, mem

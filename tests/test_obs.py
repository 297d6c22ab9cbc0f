"""The program's own records: named scopes on the compiled train step, and
the compile log (``repro.obs``)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get
from repro.launch.steps import make_train_step
from repro.models import model_api
from repro.optim.optimizers import adamw

# Two KV heads: XLA's CPU batch-dot simplification drops the metadata of a
# dot whose batch dimension has size 1, as the smoke config's single KV
# head gives; the TPU compiler keeps it.
CFG = dataclasses.replace(get("smollm_360m", smoke=True), n_heads=6,
                          n_kv_heads=2)
OPT = adamw(1e-3)


def _state():
    api = model_api(CFG)
    params = api.init(jax.random.PRNGKey(0), CFG)
    return params, OPT.init(params)


def _batch(b, s):
    x = jnp.zeros((b, s), jnp.int32)
    return {"inputs": x, "labels": x}


@pytest.fixture(scope="module")
def compiled_text():
    params, opt_state = _state()
    step = jax.jit(make_train_step(CFG, OPT))
    return step.lower(params, opt_state, _batch(2, 64)).compile().as_text()


def _instructions(text):
    """{computation: [instruction line]} of an HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)
    return comps


def _holds_dot(comps, name):
    for line in comps.get(name, []):
        if " dot(" in line:
            return True
        callee = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        if callee and _holds_dot(comps, callee.group(1)):
            return True
    return False


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else None


def _matmuls(text):
    comps = _instructions(text)
    matmuls = []
    for lines in comps.values():
        for line in lines:
            callee = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if " dot(" in line or (callee and _holds_dot(comps,
                                                         callee.group(1))):
                matmuls.append(line)
    return matmuls


def test_every_matmul_of_the_train_step_has_one_innermost_scope(
        compiled_text):
    matmuls = _matmuls(compiled_text)
    assert len(matmuls) >= 20
    for line in matmuls:
        name = _op_name(line)
        assert name is not None, line[:200]
        assert obs.innermost(name) in obs.SCOPES, name


def test_every_scope_reaches_the_forward_and_the_backward_ops(compiled_text):
    forward, backward = set(), set()
    for line in compiled_text.splitlines():
        name = _op_name(line)
        scope = name and obs.innermost(name)
        if scope:
            (backward if "transpose(" in name else forward).add(scope)
    # a dense model opens every scope but the mixture of experts' own
    model = set(obs.SCOPES) - {"optimizer"} - MOE_SCOPES
    assert model <= forward and model <= backward
    assert "optimizer" in forward          # after the gradient, not in it


MOE_SCOPES = {"router", "dispatch", "experts"}


@pytest.fixture(scope="module")
def moe_compiled_text():
    cfg = get("moonlight_16b_a3b", smoke=True)
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, OPT))
    return step.lower(params, OPT.init(params),
                      _batch(2, 32)).compile().as_text()


def test_every_matmul_of_a_moe_train_step_has_one_innermost_scope(
        moe_compiled_text):
    matmuls = _matmuls(moe_compiled_text)
    assert len(matmuls) >= 20
    for line in matmuls:
        name = _op_name(line)
        assert name is not None, line[:200]
        assert obs.innermost(name) in obs.SCOPES, name


@pytest.mark.parametrize("name", sorted(MOE_SCOPES) + ["attn_proj", "mlp",
                                                       "attention"])
def test_moe_scopes_reach_the_compiled_step(moe_compiled_text, name):
    # latent attention, the routed experts and the shared experts (mlp)
    # of a mixture-of-experts train step, forward and backward
    found = {"forward": False, "backward": False}
    for line in moe_compiled_text.splitlines():
        op = _op_name(line)
        if op and obs.innermost(op) == name:
            found["backward" if "transpose(" in op else "forward"] = True
    assert found == {"forward": True, "backward": True}


def _custom_call_names(module_text):
    """{kernel name: full op name} of the Mosaic calls in a lowered
    module's text (``as_text(debug_info=True)``).  A call's location names
    it within its function; the names of the calls that reach that function
    from ``main`` come first."""
    loc = {}
    for ref, body in re.findall(r"^(#loc\d+) = loc\((.*)\)$", module_text,
                                re.M):
        m = re.match(r'"([^"]*)"', body)
        loc[ref] = m.group(1) if m else ""
    caller, kernels, func = {}, [], None
    for line in module_text.splitlines():
        head = re.match(r"\s*func\.func (?:public |private )?@([\w.$-]+)\(",
                        line)
        if head:
            func = head.group(1)
            continue
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        name = loc.get(at.group(1), "") if at else ""
        call = re.search(r"\bcall @([\w.$-]+)\(", line)
        if call:
            caller[call.group(1)] = (func, name)
        elif "@tpu_custom_call" in line:
            kernels.append((func, name))

    def path(f):
        if f not in caller:
            return ""
        parent, name = caller[f]
        return f"{path(parent)}/{name}"

    return {name.split("/")[0]: path(f) + "/" + name for f, name in kernels}


@pytest.fixture(scope="module")
def attention_layer_grad():
    """One attention layer's gradient, recomputed in the backward as the
    model's remat runs it, traced once for either platform."""
    from repro.models import layers
    params = jax.eval_shape(
        lambda: layers.attn_init(jax.random.PRNGKey(0), CFG, jnp.bfloat16))
    x = jax.ShapeDtypeStruct((2, 128, CFG.d_model), jnp.bfloat16)
    layer = jax.checkpoint(
        lambda p, x: layers.attn_apply(p, x, CFG, jnp.arange(128)),
        policy=jax.checkpoint_policies.nothing_saveable)
    grad = jax.value_and_grad(
        lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)),
        argnums=(0, 1))
    return jax.jit(grad).trace(params, x)


def test_attention_kernels_lowered_for_a_tpu_keep_the_scope(
        attention_layer_grad):
    # attention_ms.train reads the kernels' time through this scope
    text = attention_layer_grad.lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = _custom_call_names(text)
    for phase in ("fwd", "dq", "dkv"):
        assert any(f"_{phase}_" in k for k in names), names
    for kernel, name in names.items():
        assert obs.innermost(name) == "attention", name
        if "_fwd_" not in kernel:           # the backward's kernels
            assert "transpose(" in name, name


def test_attention_lowered_for_the_cpu_has_no_kernel(attention_layer_grad):
    text = attention_layer_grad.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn_proj/dot_general",
     "attn_proj"),
    ("jit(train_step)/transpose(jvp(head_loss))/dot_general", "head_loss"),
    ("jit(f)/mlp/attention/bhgqd,bhkd->bhgqk/dot_general:", "attention"),
    ("jit(train_step)/optimizer/jit(norm)/sqrt", "optimizer"),
    ("jit(train_step)/jvp()/while/body/closed_call/router/top_k", "router"),
    ("jit(train_step)/transpose(jvp(experts))/ragged_dot_general",
     "experts"),
    ("jit(train_step)/jvp(dispatch)/scatter-add", "dispatch"),
    ("jit(norm)/sqrt", None),
    ("jit(train_step)/jvp()/while/body/squeeze", None),
    ("", None),
])
def test_innermost_scope_of_an_op_name(op_name, want):
    assert obs.innermost(op_name) == want


def test_scope_rejects_a_name_not_in_scopes():
    with pytest.raises(ValueError, match="unknown scope"):
        obs.scope("x")
    with obs.scope("mlp"):
        pass


def test_compile_log_counts_compiles_of_the_train_step():
    params, opt_state = _state()
    step = jax.jit(make_train_step(CFG, OPT))
    before = len(obs.compiles("train_step"))
    for shape in [(2, 32), (2, 32)]:
        params, opt_state, _ = step(params, opt_state, _batch(*shape))
    logged = obs.compiles("train_step")[before:]
    assert len(logged) == 1
    assert all(s > 0 for s in (logged[0].trace_s, logged[0].lower_s,
                               logged[0].compile_s))
    assert logged[0].total_s == pytest.approx(
        logged[0].trace_s + logged[0].lower_s + logged[0].compile_s)
    params, opt_state, _ = step(params, opt_state, _batch(4, 32))
    assert len(obs.compiles("train_step")[before:]) == 2


def test_compile_log_counts_a_nested_function_once():
    def _obs_inner(x):
        return x * 2

    def _obs_outer(x):
        return jax.jit(_obs_inner)(x) + 1

    jax.jit(_obs_outer)(jnp.ones(3))
    obs.log_compiles()                      # again: changes nothing
    assert len(obs.compiles("_obs_outer")) == 1
    assert obs.compiles("_obs_inner") == []
    assert jax._src.monitoring._event_time_span_listeners.count(
        obs._LOG.on_span) == 1


def test_compile_log_keeps_the_persistent_cache_outcome():
    # the events JAX records around one compile, as a cache load
    log = obs._LOG
    log.on_event("/jax/compilation_cache/compile_requests_use_cache")
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_span("/jax/core/compile/backend_compile_duration", 10.0, 10.5,
                fun_name="jit(_obs_cached)")
    log.on_span("/jax/core/compile/backend_compile_duration", 11.0, 13.0,
                fun_name="jit(_obs_cached)")
    first, second = obs.compiles("_obs_cached")
    assert first.cache_hit is True and first.compile_s == 0.5
    assert second.cache_hit is None and second.compile_s == 2.0

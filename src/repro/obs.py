"""What the program records about itself: names on its device work, and a
log of its compiles.

Scopes are ``jax.named_scope`` names drawn from :data:`SCOPES`, so the
program and a trace reader share one list.  They are compile-time metadata:
each compiled instruction carries the innermost scope in its ``op_name``
(forward ops as ``jvp(<scope>)``, backward ones as
``transpose(jvp(<scope>))``), and a profiler trace carries it on as the op's
``tf_op``.  They change no instruction.

Counts are kept at trace time: :func:`count` adds one each time traced code
passes it (``attention.kernel``, ``attention.blockwise``), so a jitted
function counts once per trace, not once per call.

The compile log keeps, for each top-level jitted function, the seconds it
spent tracing, lowering and compiling (or loading from JAX's persistent
cache), and whether that cache hit.  It listens to ``jax.monitoring`` from
the first :func:`log_compiles` call on and holds everything in memory.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import Dict, List, Optional

import jax

#: every scope the program opens, by what it covers
SCOPES = (
    "embed",        # the token gather (its scatter-add in the backward pass)
    "norm",         # every RMSNorm
    "attn_proj",    # the attention's q/k/v projections with RoPE, and output
    "attention",    # the attention kernel: forward, recompute, backward
    "mlp",          # the SwiGLU MLP, and a MoE layer's shared experts
    "router",       # a MoE router: scores, selection, gates, balance loss
    "dispatch",     # token copies sorted to their experts, gathered, and
                    # scatter-added back weighted (the combine)
    "experts",      # the held experts' grouped matmuls
    "head_loss",    # logits and cross-entropy
    "optimizer",    # gradient clipping and the optimizer's update
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name in :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; SCOPES is {SCOPES}")
    return jax.named_scope(name)


_COUNTS: Dict[str, int] = {}
_COUNTS_LOCK = threading.Lock()


def count(name: str) -> None:
    """Add one to the trace-time count ``name``."""
    with _COUNTS_LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + 1


def counts() -> Dict[str, int]:
    """Every trace-time count so far, by name."""
    with _COUNTS_LOCK:
        return dict(_COUNTS)


# a path component: a name, bare or wrapped in transforms (``jvp(mlp)``)
_COMPONENT = re.compile(r"^((?:\w+\()*)(\w+)\)*$")


def innermost(op_name: str) -> Optional[str]:
    """The innermost scope in an op's name path (a compiled instruction's
    ``op_name``, a trace's ``tf_op``), or None.  A scope is a whole path
    component, bare or inside transforms (``transpose(jvp(mlp))``); a jitted
    function's ``jit(<name>)`` is not one."""
    found = None
    for part in op_name.partition(":")[0].split("/"):
        m = _COMPONENT.match(part)
        if m and m.group(2) in SCOPES and "jit(" not in m.group(1):
            found = m.group(2)
    return found


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Compile:
    """One compile of one jitted function.  A phase that did not run stays
    ``None``: a record with only ``trace_s`` is a retrace whose lowering
    JAX found in its in-memory caches."""

    trace_s: Optional[float] = None
    lower_s: Optional[float] = None
    compile_s: Optional[float] = None       # backend compile, or cache load
    cache_hit: Optional[bool] = None        # None: persistent cache not used
    _span: tuple = dataclasses.field(default=(0.0, 0.0), repr=False)

    @property
    def total_s(self) -> float:
        return sum(x or 0.0 for x in (self.trace_s, self.lower_s,
                                      self.compile_s))


class _Log:
    def __init__(self):
        self.lock = threading.Lock()
        self.by_name: Dict[str, List[Compile]] = {}
        self.pending = threading.local()    # cache outcome of this thread's
        self.installed = False              # backend compile in progress

    def on_event(self, event: str, **kw) -> None:
        if event == _CACHE_USED:
            self.pending.hit = False
        elif event == _CACHE_HIT:
            self.pending.hit = True

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        if event not in (_TRACE, _LOWER, _COMPILE):
            return
        name = str(kw.get("fun_name", ""))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        secs = end - start
        with self.lock:
            if event == _TRACE:
                # a function traced inside this one finished first: it is
                # part of this trace, not a compile of its own
                for recs in self.by_name.values():
                    recs[:] = [r for r in recs if not (
                        r.lower_s is None and r.compile_s is None
                        and start <= r._span[0] and r._span[1] <= end)]
                self.by_name.setdefault(name, []).append(
                    Compile(trace_s=secs, _span=(start, end)))
                return
            recs = self.by_name.setdefault(name, [])
            field = "lower_s" if event == _LOWER else "compile_s"
            if not recs or getattr(recs[-1], field) is not None:
                recs.append(Compile(_span=(start, end)))
            setattr(recs[-1], field, secs)
            if event == _COMPILE:
                recs[-1].cache_hit = getattr(self.pending, "hit", None)
                self.pending.hit = None


_LOG = _Log()


def log_compiles() -> None:
    """Start the compile log (once per process; later calls do nothing)."""
    with _LOG.lock:
        if _LOG.installed:
            return
        _LOG.installed = True
    jax.monitoring.register_event_listener(_LOG.on_event)
    jax.monitoring.register_event_time_span_listener(_LOG.on_span)


def compiles(fun_name: str) -> List[Compile]:
    """The compiles of the jitted function ``fun_name`` logged so far."""
    with _LOG.lock:
        return [dataclasses.replace(r)
                for r in _LOG.by_name.get(fun_name, [])]

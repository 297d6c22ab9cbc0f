"""Device time by the program's scopes (``yardstick.scopes``), on a trace
recorded on one TPU v5e by ``tools/record_scope_trace.py``: four steps of a
tiny jitted gradient, ``attention`` nested inside ``mlp`` and an unscoped
loss; and on ``small.xplane.pb``, whose ops carry no scope.  Then the
compile log's reader on a run of the train cell on the CPU."""
import re
from pathlib import Path

import pytest

from repro.obs import SCOPES
from yardstick import scopes, spec
from yardstick import trace as T

DATA = Path(__file__).resolve().parent / "data"
SCOPED = str(DATA / "scoped.xplane.pb")
SMALL = str(DATA / "small.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return scopes.attribute(SCOPED, "bench.window")


def test_scoped_and_unscoped_time_add_up_to_busy_time(scoped):
    total = sum(scoped["scopes"].values()) + scoped["unscoped_s"]
    assert scoped["busy_s"] > 0
    assert total == pytest.approx(scoped["busy_s"], abs=1e-6)
    assert scoped["busy_s"] == pytest.approx(
        T.reduce(SCOPED, "bench.window")["busy_s"], abs=1e-9)


def _scopes_in(tf_op):
    return {w for w in re.findall(r"\w+", tf_op) if w in SCOPES}


def test_the_innermost_scope_wins(scoped):
    both = [op for op in scoped["ops"]
            if _scopes_in(op[1]) == {"mlp", "attention"}]
    assert both and all(op[2] == "attention" for op in both)
    assert scoped["scopes"]["attention"] > 0 and scoped["scopes"]["mlp"] > 0
    outer = [op for op in scoped["ops"] if op[2] == "mlp"]
    assert outer and all(_scopes_in(op[1]) == {"mlp"} for op in outer)


def test_backward_ops_keep_their_scope(scoped):
    assert any("transpose(" in op[1] and op[2] is not None
               for op in scoped["ops"])


def test_ops_outside_the_scopes_are_unscoped(scoped):
    assert scoped["unscoped_s"] > 0
    assert set(scoped["scopes"]) == set(SCOPES)


def test_a_trace_without_scopes_is_all_unscoped():
    got = scopes.attribute(SMALL, "bench.window")
    assert not any(got["scopes"].values())
    assert got["unscoped_s"] == pytest.approx(got["busy_s"], abs=1e-12)


def test_tf_op_read_from_the_event_metadata():
    table = scopes.tf_ops(SMALL)["/device:TPU:0"]
    fusion = [op for op in table if op.startswith("%fusion = ")]
    assert [table[op] for op in fusion] == ["jit(<lambda>)/dot_general:"]


class _Out:
    def __init__(self):
        self.trace = {"busy_s": 1.0}
        self.counters = {}


@pytest.mark.parametrize("path, metric, want", [
    (SCOPED, "attention_ms.train", "value"),
    (SCOPED, "unscoped_device_pct.train", "value"),
    (SMALL, "mlp_ms.train", None),
    (SMALL, "unscoped_device_pct.train", None),
])
def test_scope_metrics_read_the_run_trace(monkeypatch, path, metric, want):
    monkeypatch.setattr(scopes, "trace_path", lambda cell: path)
    cell = spec.resolve("smollm-360m.train-s2048")
    got = spec.metric_reader(metric)(_Out(), cell)
    if want is None:
        assert got is None                  # nothing to attribute
    else:
        assert got is not None and got > 0


def test_step_compile_seconds_come_from_the_compile_log(run_tiny):
    cell = "smollm-360m.train-s2048"
    out = run_tiny(cell, seconds=0.2, batch=2, seq=32, model=dict(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, vocab=256, dtype="float32"))
    got = spec.metric_reader("step_compile_s.train")(out, spec.resolve(cell))
    assert got is not None and got > 0

"""The training cell's run and check on the CPU, at a tiny float32 size:
the program passes against the plain reference; the int8 control, a
step that returns its state unchanged and a step that leaves out half of
the batch do not."""
import pytest

from yardstick import spec, train_lm

CELL = "smollm-360m.train-s2048"
TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=96, vocab=256, dtype="float32")
TINY = dict(batch=4, seq=32)


def failed(out):
    return [c.name for c in out.checks if not c.ok]


def test_program_matches_reference(run_tiny):
    out = run_tiny(CELL, seconds=0.5, model=TINY_MODEL, **TINY)
    assert out.correct, out.checks
    assert out.end_to_end["train_tokens_per_s"] > 0
    assert out.counters["steps"] >= 1


def test_int8_control_fails():
    cell = spec.resolve(CELL)
    ref = cell.driver()
    m = dict(cell.config["model"], **TINY_MODEL)
    tr = dict(cell.traffic, **TINY)
    want = ref.reference(m, tr, 3, 3)
    got = ref.reference(m, tr, 3, 3, matmul="int8")
    assert any(not c.ok for c in train_lm.compare(got, want, tr["limits"]))


def _broken_step(monkeypatch, wrap):
    import repro.launch.steps as steps
    make = steps.make_train_step
    monkeypatch.setattr(steps, "make_train_step",
                        lambda cfg, opt, **kw: wrap(make(cfg, opt, **kw)))


def test_state_left_unchanged_is_not_correct(run_tiny, monkeypatch):
    def unchanged(step):
        def f(params, opt_state, batch):
            _, _, met = step(params, opt_state, batch)
            return params, opt_state, met
        return f
    _broken_step(monkeypatch, unchanged)
    out = run_tiny(CELL, seconds=0.2, model=TINY_MODEL, **TINY)
    assert "change_norm_gap" in failed(out)


def test_half_batch_left_out_is_not_correct(run_tiny, monkeypatch):
    def half(step):
        def f(params, opt_state, batch):
            n = batch["inputs"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()})
        return f
    _broken_step(monkeypatch, half)
    out = run_tiny(CELL, seconds=0.2, model=TINY_MODEL, **TINY)
    assert failed(out)


@pytest.mark.parametrize("name", ["train_mfu"])
def test_mfu_reader_needs_a_known_chip(run_tiny, name):
    from yardstick import peaks
    out = run_tiny(CELL, seconds=0.2, model=TINY_MODEL, **TINY)
    with pytest.raises(peaks.UnknownDevice):
        spec.metric_reader(name)(out, spec.resolve(CELL))

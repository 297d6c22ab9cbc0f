"""Aligned-barrier checkpointing: kill-mid-stream restore is byte-identical.

The contract (ISSUE 9): spouts inject numbered barriers on the declared
cadence, every executor snapshots its state at the aligned cut (device
dispatch windows drained first, so spout offsets never cover unretired
batches), and resuming a killed run from any completed checkpoint produces
the same sink counters, keyed state bytes, pane multiset and late drops as
never having stopped — on the threads and the processes backend alike.
Satellites pinned here: event-time pane buffers survive ``migrate_states``
across a mid-run replan (suspend mode), and a kernel crash mid-batch
releases every pooled-buffer lease back to its arena.
"""
import glob
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.streaming.api import Topology, TopologyError
from repro.streaming.apps import (spike_detection_eventtime,
                                  spike_detection_keyed, word_count)
from repro.streaming.checkpoint import (Checkpoint, checkpoint_uids,
                                        list_checkpoints, restore_checkpoint,
                                        save_checkpoint)
from repro.streaming.procexec import run_app_processes
from repro.streaming.runtime import _Arena, run_app
from repro.streaming.state import merge_keyed, migrate_states

WC_PAR = {"spout": 2, "parser": 1, "splitter": 2, "counter": 2, "sink": 1}

_RUNNERS = {"threads": run_app, "processes": run_app_processes}


def _run(backend, app, parallelism=None, **kw):
    return _RUNNERS[backend](app, parallelism, **kw)


def _wc_sig(rt):
    """Order-insensitive word-count fingerprint: sink rows + keyed bytes."""
    seen = sum(st.get("seen", 0) for st in rt.states["sink"])
    keyed = merge_keyed([st.managed for st in rt.states["counter"]])
    return seen, keyed.tobytes()


def _et_sig(rt, win_op):
    """Event-time fingerprint: sink accumulators + pane/late counters."""
    sink = {}
    for st in rt.states["sink"]:
        for k, v in st.items():
            if np.isscalar(v):
                sink[k] = sink.get(k, 0) + v
    return tuple(sorted(sink.items())), rt.panes_fired, rt.late_drops


def _resume_batches(total, ckpt):
    off = set(ckpt.spout_offsets.values())
    assert len(off) == 1, "aligned barriers cut every spout at one offset"
    return total - off.pop()


# ---------------------------------------------------------------------------
# declaration + round structure
# ---------------------------------------------------------------------------

def test_topology_checkpoint_every_validation():
    for bad in (0, -3, 2.5, True):
        with pytest.raises(TopologyError, match="checkpoint_every"):
            Topology("t", checkpoint_every=bad)
    with pytest.raises(ValueError, match="checkpoint_every"):
        run_app(word_count(), WC_PAR, max_batches=2, checkpoint_every=0)


def test_declared_cadence_flows_from_topology():
    def src(batch, seed):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(batch, 2))

    app = (Topology("tiny", checkpoint_every=2)
           .spout("s", src, exec_ns=100.0)
           .sink("k", lambda b, st: st.__setitem__(
               "seen", st.get("seen", 0) + len(b)) or [], exec_ns=100.0)
           .build())
    assert app.checkpoint_every == 2
    rt = run_app(app, {}, batch=16, max_batches=6, seed=1)
    assert [c.ckpt_id for c in rt.checkpoints] == [1, 2, 3]
    # the run_app argument overrides the declaration
    rt = run_app(app, {}, batch=16, max_batches=6, seed=1, checkpoint_every=3)
    assert [c.ckpt_id for c in rt.checkpoints] == [1, 2]


def test_checkpoint_round_structure():
    app = word_count()
    rt = run_app(app, WC_PAR, batch=64, max_batches=20, seed=3,
                 checkpoint_every=4)
    assert [c.ckpt_id for c in rt.checkpoints] == [1, 2, 3, 4, 5]
    expected = checkpoint_uids(app, WC_PAR)
    for ck in rt.checkpoints:
        # a completed round holds one snapshot per replica of EVERY operator
        assert set(ck.states) == expected
        assert set(ck.spout_offsets) == {"spout#0", "spout#1"}
        assert all(off == 4 * ck.ckpt_id
                   for off in ck.spout_offsets.values())
        assert ck.app == "wc" and ck.batch == 64 and ck.seed == 3
        assert "wc" in ck.describe() and str(ck.ckpt_id) in ck.describe()


# ---------------------------------------------------------------------------
# resume parity: every checkpoint is a byte-identical continuation point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["threads", "processes"])
def test_resume_parity_word_count(backend):
    app = word_count()
    base = _run(backend, app, WC_PAR, batch=64, max_batches=20, seed=3,
                checkpoint_every=4)
    want = _wc_sig(base)
    assert len(base.checkpoints) == 5
    for ck in base.checkpoints:
        rt = _run(backend, app, batch=64, seed=3,
                  max_batches=_resume_batches(20, ck), from_checkpoint=ck)
        assert _wc_sig(rt) == want, f"divergence resuming from {ck.ckpt_id}"


@pytest.mark.parametrize("backend", ["threads", "processes"])
def test_resume_parity_event_time(backend):
    """Pane buffers + watermark frontier restore: the resumed run fires the
    same panes and classifies the same tuples late as the uninterrupted
    one, even with the input shuffled within the lateness bound."""
    app = spike_detection_eventtime()
    base = _run(backend, app, batch=64, max_batches=24, seed=5,
                checkpoint_every=3)
    want = _et_sig(base, "pane_stats")
    assert base.panes_fired > 0
    for ck in base.checkpoints:
        rt = _run(backend, spike_detection_eventtime(), batch=64, seed=5,
                  max_batches=_resume_batches(24, ck), from_checkpoint=ck)
        assert _et_sig(rt, "pane_stats") == want, \
            f"divergence resuming from {ck.ckpt_id}"


def test_resume_parity_keyed_event_time_replicated():
    """Keyed pane groups: snapshots are per-replica and restore shard-true
    under replicated keyed windows."""
    app = spike_detection_keyed()
    par = {"spout": 1, "parser": 2, "device_stats": 2, "sink": 1}
    base = run_app(app, par, batch=64, max_batches=18, seed=2,
                   checkpoint_every=3)
    want = _et_sig(base, "device_stats")
    assert base.panes_fired > 0 and len(base.checkpoints) >= 5
    for ck in base.checkpoints[::2]:
        rt = run_app(spike_detection_keyed(), batch=64, seed=2,
                     max_batches=_resume_batches(18, ck), from_checkpoint=ck)
        assert _et_sig(rt, "device_stats") == want


# ---------------------------------------------------------------------------
# kill-mid-stream property: sweep the kill point over batch indices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,kills", [
    ("threads", range(3, 12)),           # every batch index once
    ("processes", (4, 7, 11)),           # spot checks (forks are pricier)
])
def test_kill_point_sweep_recovery(backend, kills, tmp_path):
    """Stop the run at batch ``k`` (any k, aligned with a barrier or not),
    restore the last checkpoint that *completed and persisted* before the
    kill, and the continuation must match the uninterrupted run."""
    total, every, seed = 12, 3, 11
    app = word_count()
    want = _wc_sig(_run(backend, app, WC_PAR, batch=64, max_batches=total,
                        seed=seed))
    for k in kills:
        d = tmp_path / f"{backend}-{k}"
        _run(backend, word_count(), WC_PAR, batch=64, max_batches=k,
             seed=seed, checkpoint_every=every, checkpoint_dir=str(d))
        ids = list_checkpoints(str(d))
        assert ids == list(range(1, k // every + 1))
        ck = restore_checkpoint(str(d))
        assert ck.ckpt_id == ids[-1]
        rt = _run(backend, word_count(), batch=64, seed=seed,
                  max_batches=_resume_batches(total, ck), from_checkpoint=ck)
        assert _wc_sig(rt) == want, f"kill at batch {k} diverged"


def test_kill_point_sweep_recovery_event_time(tmp_path):
    total, every, seed = 16, 4, 9
    want = _et_sig(run_app(spike_detection_eventtime(), batch=64,
                           max_batches=total, seed=seed), "pane_stats")
    for k in (5, 9, 14):
        d = tmp_path / str(k)
        run_app(spike_detection_eventtime(), batch=64, max_batches=k,
                seed=seed, checkpoint_every=every, checkpoint_dir=str(d))
        ck = restore_checkpoint(str(d))
        rt = run_app(spike_detection_eventtime(), batch=64, seed=seed,
                     max_batches=_resume_batches(total, ck),
                     from_checkpoint=ck)
        assert _et_sig(rt, "pane_stats") == want, f"kill at {k} diverged"


def test_sigkill_worker_recovery(tmp_path, monkeypatch):
    """The real thing on the processes backend: a worker dies by SIGKILL
    mid-stream.  The parent must fail fast, leave zero shared-memory
    orphans, and the on-disk checkpoints must replay to parity."""
    total, every, seed = 12, 3, 4

    def src(batch, seed):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(batch, 2))

    def stage(b, st):
        st["nb"] = st.get("nb", 0) + 1
        kill_at = os.environ.get("BSR_TEST_KILL_AT")
        if kill_at and st["nb"] >= int(kill_at):
            os.kill(os.getpid(), signal.SIGKILL)
        return [b * 2.0]

    def make():
        return (Topology("killable")
                .spout("s", src, exec_ns=100.0)
                .op("f", stage, exec_ns=100.0)
                .sink("k", lambda b, st: st.__setitem__(
                    "seen", st.get("seen", 0) + len(b)) or [],
                    exec_ns=100.0)
                .build())

    def sig(rt):
        return (sum(st.get("seen", 0) for st in rt.states["k"]),
                sum(st.get("nb", 0) for st in rt.states["f"]))

    want = sig(run_app_processes(make(), batch=32, max_batches=total,
                                 seed=seed))
    d = str(tmp_path / "ckpts")
    monkeypatch.setenv("BSR_TEST_KILL_AT", "8")
    with pytest.raises((RuntimeError, TimeoutError), match="died|deadline"):
        run_app_processes(make(), batch=32, max_batches=total, seed=seed,
                          checkpoint_every=every, checkpoint_dir=d,
                          timeout=60.0)
    # kill leaked none of this process's segments (other test processes'
    # rings may be live)
    assert glob.glob(f"/dev/shm/bsr{os.getpid()}x*") == []
    monkeypatch.delenv("BSR_TEST_KILL_AT")
    ck = restore_checkpoint(d)
    assert ck.ckpt_id >= 1                    # a pre-kill round persisted
    rt = run_app_processes(make(), batch=32, seed=seed,
                           max_batches=_resume_batches(total, ck),
                           from_checkpoint=ck)
    assert sig(rt) == want


# ---------------------------------------------------------------------------
# device operators: dispatch windows drain before a snapshot (satellite 3)
# ---------------------------------------------------------------------------

def _device_app(depth):
    def src(batch, seed):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(batch, 4))

    def k_dev(b, st):
        st["nb"] = st.get("nb", 0) + 1
        return [b * 2.0]

    return (Topology("dev")
            .spout("s", src, exec_ns=100.0)
            .op("d", k_dev, exec_ns=300.0, device=True, device_ns=2000.0,
                dispatch_depth=depth)
            .sink("k", lambda b, st: st.__setitem__(
                "seen", st.get("seen", 0) + len(b)) or [], exec_ns=100.0)
            .build())


def test_device_window_drains_before_snapshot():
    """With a deep dispatch window, a barrier must retire every in-flight
    batch before the snapshot: the recorded spout offset covers exactly
    the batches whose results reached the sink — never a batch still in
    flight (the offsets-at-emit-time bug)."""
    batch, total, every = 32, 12, 2
    rt = run_app(_device_app(3), {}, batch=batch, max_batches=total, seed=6,
                 checkpoint_every=every)
    assert len(rt.checkpoints) == total // every
    for ck in rt.checkpoints:
        b = ck.spout_offsets["s#0"]
        assert b == every * ck.ckpt_id
        # the device op dispatched exactly the emitted batches...
        assert ck.states["d#0"]["scratch"]["nb"] == b
        # ...and every one of them was retired through to the sink
        assert ck.states["k#0"]["scratch"]["seen"] == b * batch


@pytest.mark.parametrize("depth", [1, 3])
def test_device_crash_resume_parity(depth, tmp_path):
    """Kill a device run mid-stream (graceful cut between barriers) and
    resume: depth 1 and depth N restore to the same bytes."""
    batch, total, every, kill = 32, 14, 3, 8
    want = run_app(_device_app(depth), {}, batch=batch, max_batches=total,
                   seed=8).states["k"][0]["seen"]
    d = str(tmp_path / "ck")
    run_app(_device_app(depth), {}, batch=batch, max_batches=kill, seed=8,
            checkpoint_every=every, checkpoint_dir=d)
    ck = restore_checkpoint(d)
    rt = run_app(_device_app(depth), batch=batch, seed=8,
                 max_batches=_resume_batches(total, ck), from_checkpoint=ck)
    assert rt.states["k"][0]["seen"] == want


def test_jitted_device_checkpoint_parity_in_clean_subprocess():
    """streaming_inference (jitted predictor, broadcast weights) through
    kill/restore on the processes backend — in a jax-clean child."""
    pytest.importorskip("jax")
    child = (
        "import sys\n"
        "from repro.streaming.apps import streaming_inference\n"
        "from repro.streaming.procexec import run_app_processes\n"
        "def sig(rt):\n"
        "    st = rt.states['sink'][0]\n"
        "    return (st['seen'], st['score'])\n"
        "app = streaming_inference(model_versions=1)\n"
        "base = run_app_processes(app, {}, batch=16, max_batches=12,\n"
        "                         seed=0, checkpoint_every=4,\n"
        "                         dispatch_depth=3)\n"
        "assert len(base.checkpoints) == 3, base.checkpoints\n"
        "for ck in base.checkpoints:\n"
        "    rem = 12 - ck.spout_offsets['spout#0']\n"
        "    rt = run_app_processes(streaming_inference(model_versions=1),\n"
        "                           batch=16, max_batches=rem, seed=0,\n"
        "                           from_checkpoint=ck, dispatch_depth=3)\n"
        "    assert sig(rt) == sig(base), ck.ckpt_id\n"
        "print('OK')\n")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    cp = subprocess.run([sys.executable, "-c", child], capture_output=True,
                        text=True, timeout=600, env=env)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "OK" in cp.stdout


# ---------------------------------------------------------------------------
# event-time pane buffers survive migrate_states (satellite 1)
# ---------------------------------------------------------------------------

def test_migrated_event_time_windows_carry_when_suspended():
    """Suspend an ET run mid-stream (final_watermark=False), migrate its
    states, and continue: the migrated run fires the same pane multiset as
    never having stopped — buffered panes and the watermark frontier ride
    along instead of being dropped (the lossy-replan bug)."""
    total, cut, seed = 24, 10, 5
    app = spike_detection_eventtime()
    base = run_app(spike_detection_eventtime(), batch=64, max_batches=total,
                   seed=seed)
    r1 = run_app(app, batch=64, max_batches=cut, seed=seed,
                 final_watermark=False)
    assert r1.panes_fired < base.panes_fired    # the cut left panes buffered
    seeded = migrate_states(app, r1.states,
                            {n: 1 for n in app.graph.operators})
    r2 = run_app(spike_detection_eventtime(), batch=64,
                 max_batches=total - cut, seed=seed + cut,
                 initial_states=seeded)
    # window counters are window state: migrated totals accumulate r1's
    assert r2.panes_fired == base.panes_fired
    assert r2.late_drops == base.late_drops
    sink = lambda rt: {k: sum(st.get(k, 0) for st in rt.states["sink"])
                       for k in ("seen", "spikes")}
    b, s1, s2 = sink(base), sink(r1), sink(r2)
    assert {k: s1[k] + s2[k] for k in b} == b


def test_migrated_keyed_event_time_windows_carry_across_replan():
    """The same carry across a replica-count change: keyed pane buffers
    reshard by key ownership, so a 1 -> 2 replan mid-stream stays
    pane-multiset-identical."""
    total, cut, seed = 18, 8, 3
    app = spike_detection_keyed()
    base_par = {n: 1 for n in app.graph.operators}
    new_par = dict(base_par, device_stats=2, parser=2)
    base = run_app(spike_detection_keyed(), dict(base_par), batch=64,
                   max_batches=total, seed=seed)
    r1 = run_app(app, dict(base_par), batch=64, max_batches=cut, seed=seed,
                 final_watermark=False)
    seeded = migrate_states(app, r1.states, new_par)
    r2 = run_app(spike_detection_keyed(), new_par, batch=64,
                 max_batches=total - cut, seed=seed + cut,
                 initial_states=seeded)
    assert r2.panes_fired == base.panes_fired
    assert r2.late_drops == base.late_drops
    sink = lambda rt: sum(st.get("seen", 0) for st in rt.states["sink"])
    assert sink(r1) + sink(r2) == sink(base)


# ---------------------------------------------------------------------------
# kernel crash mid-batch releases pooled-buffer leases (satellite 2)
# ---------------------------------------------------------------------------

def test_failed_run_releases_arena_leases():
    """A kernel raising with a non-empty device dispatch window must not
    strand arena buffers: the in-flight batches' leases and the crashing
    batch's own lease all release, returning the arena to baseline."""
    calls = []

    def boom(b, st):
        calls.append(len(b))
        if len(calls) >= 2:
            raise RuntimeError("injected kernel crash")
        return []

    def src(batch, seed):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(batch, 2))

    app = (Topology("crashy")
           .spout("s", src, exec_ns=100.0)
           # halve each batch so jumbos aggregate through the arena —
           # full-batch passthrough would ride the zero-copy, lease-free path
           .op("h", lambda b, st: [b[: len(b) // 2]], exec_ns=100.0)
           .sink("d", boom, exec_ns=100.0, device=True, device_ns=500.0,
                 dispatch_depth=2)
           .build())
    baseline = _Arena.outstanding_total()
    rt = run_app(app, {}, batch=32, max_batches=4, seed=1)
    assert len(calls) == 2                      # crashed on the second jumbo
    assert _Arena.outstanding_total() == baseline
    assert rt.spout_tuples == 4 * 32            # the run itself completed


def test_clean_run_keeps_arena_at_baseline():
    baseline = _Arena.outstanding_total()
    run_app(word_count(), WC_PAR, batch=64, max_batches=6, seed=0,
            checkpoint_every=2)
    assert _Arena.outstanding_total() == baseline


# ---------------------------------------------------------------------------
# persistence + validation
# ---------------------------------------------------------------------------

def test_checkpoint_disk_round_trip(tmp_path):
    d = str(tmp_path)
    rt = run_app(word_count(), WC_PAR, batch=64, max_batches=8, seed=1,
                 checkpoint_every=2, checkpoint_dir=d)
    assert list_checkpoints(d) == [1, 2, 3, 4]
    ck = restore_checkpoint(d)
    assert isinstance(ck, Checkpoint) and ck.ckpt_id == 4
    ck2 = restore_checkpoint(d, ckpt_id=2)
    assert ck2.ckpt_id == 2
    assert ck2.spout_offsets == rt.checkpoints[1].spout_offsets
    # explicit save of an in-memory checkpoint lands loadable
    p = str(tmp_path / "again")
    save_checkpoint(rt.checkpoints[0], p)
    assert restore_checkpoint(p).ckpt_id == 1
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "empty"))


def test_resume_validation_rejects_torn_requests():
    rt = run_app(word_count(), WC_PAR, batch=64, max_batches=4, seed=1,
                 checkpoint_every=2)
    ck = rt.checkpoints[-1]
    with pytest.raises(ValueError, match="seed"):
        run_app(word_count(), max_batches=2, batch=64, seed=2,
                from_checkpoint=ck)
    with pytest.raises(ValueError, match="batch"):
        run_app(word_count(), max_batches=2, batch=32, seed=1,
                from_checkpoint=ck)
    with pytest.raises(ValueError, match="parallelism|replica"):
        run_app(word_count(), dict(WC_PAR, counter=3), max_batches=2,
                batch=64, seed=1, from_checkpoint=ck)
    with pytest.raises(ValueError, match="initial_states|initial_offsets"):
        run_app(word_count(), max_batches=2, batch=64, seed=1,
                from_checkpoint=ck,
                initial_offsets={"spout": 2})
    with pytest.raises(ValueError, match="app"):
        run_app(spike_detection_eventtime(), max_batches=2, batch=64,
                seed=1, from_checkpoint=ck)
    with pytest.raises(ValueError, match="Checkpoint"):
        run_app(word_count(), max_batches=2, batch=64, seed=1,
                from_checkpoint={"not": "a checkpoint"})

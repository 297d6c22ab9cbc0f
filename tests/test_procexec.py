"""Process-parallel backend contract (ISSUE 6).

The parity contract: threads and processes execute the same prepared app
over the same compiled routes — only the transport differs (in-process
queues vs shared-memory SPSC rings) — so under deterministic replay the
outputs are byte-identical: spout/sink counters, merged keyed state, pane
multisets, late drops.  Plus: the ring speaks the executor's queue
protocol, crashes and wedges tear down without orphaning ``/dev/shm``
segments, state migrates across a process-backend replan byte-for-byte,
and plan-faithful grouping realizes the plan's socket map.
"""
import json
import os
import queue
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import server_a, subset
from repro.streaming.api import Job, Topology
from repro.streaming.apps import (linear_road, spike_detection_eventtime,
                                  spike_detection_keyed, streaming_inference,
                                  word_count)
from repro.streaming.procexec import (BACKENDS, ShmRing, get_backend,
                                      host_device_env, plan_placement,
                                      register_backend, register_ring_dtype,
                                      run_app_processes, socket_core_map)
from repro.streaming.runtime import _POISON, _Watermark, run_app
from repro.streaming.state import (KeyedStore, StateSpec, WindowSpec,
                                   merge_keyed, migrate_states)


def _shm_leftovers():
    # only this process's segments: other test processes' rings are live
    return [f for f in os.listdir("/dev/shm")
            if f.startswith(f"bsr{os.getpid()}x")]


def _summary(r):
    return (r.spout_tuples, r.sink_tuples, r.late_drops, r.panes_fired)


def _keyed_bytes(r):
    out = {}
    for op, reps in r.states.items():
        stores = [s.managed for s in reps if isinstance(s.managed, KeyedStore)]
        if stores:
            out[op] = merge_keyed(stores).tobytes()
    return out


def _sink_scratch(r, lg):
    return {op: [{k: v for k, v in st.items() if np.isscalar(v)}
                 for st in r.states[op]] for op in lg.sinks()}


# ---------------------------------------------------------------------------
# ShmRing: the executor queue protocol over one shared segment
# ---------------------------------------------------------------------------

def test_ring_roundtrip_data_watermark_poison():
    ring = ShmRing(capacity=4)
    try:
        arr = np.arange(12.0).reshape(3, 4)
        ring.put((arr, 1.25))
        ring.put(_Watermark("spout#0", 64.0))
        ring.put(_POISON)
        got, t0, lease = ring.get()
        assert got.tobytes() == arr.tobytes() and t0 == 1.25
        assert lease is None        # ring hand-off already owns its copy
        wm = ring.get()
        assert isinstance(wm, _Watermark)
        assert (wm.lane, wm.value) == ("spout#0", 64.0)
        assert ring.get() is _POISON          # sentinel survives by identity
        with pytest.raises(queue.Empty):
            ring.get_nowait()
    finally:
        ring.close()
        ring.unlink()


def _tag_of(ring, slot):
    return ring._buf[16 + slot * ring.slot_bytes]


@pytest.mark.parametrize("arr", [
    np.arange(7, dtype=np.int64),
    np.random.default_rng(0).random((3, 5)).astype(np.float32),
    np.zeros((2, 3, 4), dtype=np.uint16),
    np.array([True, False, True]),
    np.empty((0,), dtype=np.float64),          # empty batch
    np.empty((0, 8), dtype=np.int32),
], ids=["i64", "f32-2d", "u16-3d", "bool", "empty", "empty-2d"])
def test_ring_raw_roundtrip_preserves_bytes_dtype_shape(arr):
    ring = ShmRing(capacity=2, slot_bytes=8192)
    try:
        slot = ring._tail() % ring.capacity
        ring.put((arr, 2.5))
        assert _tag_of(ring, slot) == 0        # raw tag, no pickle
        got, t0, _ = ring.get()
        assert got.dtype == arr.dtype and got.shape == arr.shape
        assert got.tobytes() == np.ascontiguousarray(arr).tobytes()
        assert t0 == 2.5
    finally:
        ring.close()
        ring.unlink()


def test_ring_pickle_fallback_tag_parity():
    """Unregistered dtypes fall back to tagged pickle slots; registering
    them moves the same batch to the raw path — bytes identical either
    way.  ``raw=False`` forces the fallback everywhere (the A/B flag)."""
    sd = np.dtype([("key", "i8"), ("val", "f4")])
    s = np.zeros(5, sd)
    s["key"] = np.arange(5)
    s["val"] = 0.5
    u = np.array(["event", "spïke", ""], dtype="<U8")
    ring = ShmRing(capacity=4, slot_bytes=8192)
    try:
        for a in (s, u):                       # unregistered -> pickle tag
            slot = ring._tail() % ring.capacity
            ring.put((a, 1.0))
            assert _tag_of(ring, slot) == 1
            got, t0, _ = ring.get()
            assert got.dtype == a.dtype and got.tobytes() == a.tobytes()
        did = register_ring_dtype(sd)
        assert register_ring_dtype(sd) == did  # idempotent
        register_ring_dtype("<U8")
        for a in (s, u):                       # registered -> raw tag
            slot = ring._tail() % ring.capacity
            ring.put((a, 1.0))
            assert _tag_of(ring, slot) == 0
            got, t0, _ = ring.get()
            assert got.dtype == a.dtype and got.tobytes() == a.tobytes()
    finally:
        ring.close()
        ring.unlink()
    forced = ShmRing(capacity=2, slot_bytes=8192, raw=False)
    try:
        slot = forced._tail() % forced.capacity
        forced.put((np.arange(4.0), 3.0))      # registered dtype, still pickle
        assert _tag_of(forced, slot) == 1
        got, t0, _ = forced.get()
        assert got.tobytes() == np.arange(4.0).tobytes() and t0 == 3.0
    finally:
        forced.close()
        forced.unlink()


def test_ring_wrap_around_and_copy_counters():
    """Slots reuse cleanly past the wrap point (consumer copies before the
    head advance hands the slot back) and the byte counters account every
    copy on both sides."""
    ring = ShmRing(capacity=3, slot_bytes=4096)
    try:
        for k in range(10):                    # > 3 laps over 3 slots
            a = np.full(16, k, dtype=np.int64)
            ring.put((a, float(k)))
            got, t0, _ = ring.get()
            assert np.array_equal(got, a) and t0 == float(k)
        assert ring.put_slots == ring.get_slots == 10
        assert ring.put_tuples == ring.get_tuples == 160
        assert ring.put_bytes == ring.get_bytes == 10 * 16 * 8
    finally:
        ring.close()
        ring.unlink()


def test_ring_property_roundtrip():
    """Property-test the slot codec over random shapes/dtypes/offsets —
    every batch that fits must round-trip byte-identically, raw or
    fallback alike."""
    hyp = pytest.importorskip("hypothesis")
    hnp = pytest.importorskip("hypothesis.extra.numpy")
    from hypothesis import given, settings, strategies as st

    dtypes = st.sampled_from([np.dtype(s) for s in
                              ("int8", "uint32", "int64", "float32",
                               "float64", "complex64", "<U3")])
    shapes = st.lists(st.integers(0, 7), min_size=1, max_size=3).map(tuple)

    @settings(max_examples=60, deadline=None)
    @given(dt=dtypes, shape=shapes, data=st.data(),
           t0=st.floats(allow_nan=False, allow_infinity=False, width=32))
    def roundtrip(dt, shape, data, t0):
        arr = data.draw(hnp.arrays(dt, shape))
        ring = ShmRing(capacity=2, slot_bytes=1 << 14)
        try:
            ring.put((arr, float(t0)))
            got, got_t0, _ = ring.get()
            assert got.dtype == arr.dtype and got.shape == arr.shape
            assert got.tobytes() == np.ascontiguousarray(arr).tobytes()
            assert got_t0 == float(t0)
        finally:
            ring.close()
            ring.unlink()

    roundtrip()
    assert not _shm_leftovers()


def test_ring_backpressure_full_and_oversize():
    ring = ShmRing(capacity=2, slot_bytes=4096)
    try:
        a = np.zeros(8)
        ring.put((a, 0.0))
        ring.put((a, 0.0))
        t0 = time.perf_counter()
        with pytest.raises(queue.Full):
            ring.put((a, 0.0), timeout=0.05)   # full: bounded wait, then Full
        assert time.perf_counter() - t0 < 2.0
        with pytest.raises(ValueError, match="slot_bytes"):
            ring.put((np.zeros(4096), 0.0))    # never split, always explain
    finally:
        ring.close()
        ring.unlink()


def test_backend_registry():
    assert callable(get_backend("threads"))
    assert get_backend("processes") is run_app_processes
    with pytest.raises(ValueError, match="gpu.*processes.*threads"):
        get_backend("gpu")
    register_backend("test-noop", lambda app, **kw: None)
    try:
        assert get_backend("test-noop")(None) is None
    finally:
        del BACKENDS["test-noop"]


# ---------------------------------------------------------------------------
# The parity contract: threads vs processes, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_app", [word_count, linear_road,
                                      spike_detection_eventtime,
                                      spike_detection_keyed],
                         ids=["wc", "lr", "sd_et", "sd_key"])
def test_backend_parity_benchmark_apps(make_app):
    kw = dict(batch=128, max_batches=5, seed=3)
    rt = run_app(make_app(), **kw)
    rp = run_app_processes(make_app(), **kw)
    assert _summary(rt) == _summary(rp)
    assert _keyed_bytes(rt) == _keyed_bytes(rp)
    lg = make_app().graph
    assert _sink_scratch(rt, lg) == _sink_scratch(rp, lg)
    assert not _shm_leftovers()


def test_ring_format_parity_raw_vs_pickle():
    """The slot encoding is invisible to results: forcing every ring back
    to the pickle fallback (``ring_format="pickle"``) reproduces the raw
    default byte for byte — the invariant behind the serialization A/B."""
    kw = dict(batch=128, max_batches=5, seed=3)
    raw = run_app_processes(word_count(), ring_format="raw", **kw)
    pkl = run_app_processes(word_count(), ring_format="pickle", **kw)
    assert _summary(raw) == _summary(pkl)
    assert _keyed_bytes(raw) == _keyed_bytes(pkl)
    with pytest.raises(ValueError, match="ring_format"):
        run_app_processes(word_count(), ring_format="arrow", **kw)
    assert not _shm_leftovers()


def test_backend_parity_parallel_and_grouped():
    """Parity holds at parallelism > 1 for any worker grouping — solo
    workers (every edge a ring) and two-socket grouping (mixed local
    queues + rings) alike."""
    par = {"splitter": 2, "counter": 2}
    kw = dict(parallelism=par, batch=128, max_batches=5, seed=3)
    rt = run_app(word_count(), **kw)
    rp = run_app_processes(word_count(), **kw)
    groups = {"spout": 0, ("splitter", 0): 0, ("splitter", 1): 1,
              ("counter", 0): 0, ("counter", 1): 1, "sink": 1}
    rg = run_app_processes(word_count(), groups=groups, pin={0: [0], 1: [0]},
                           **kw)
    assert _summary(rt) == _summary(rp) == _summary(rg)
    assert _keyed_bytes(rt) == _keyed_bytes(rp) == _keyed_bytes(rg)
    assert not _shm_leftovers()


def test_pane_multiset_byte_parity_across_backends():
    """Keyed event-time pane *contents* cross the rings byte-identically:
    a recording sink keeps every pane-aggregate row it receives; the
    multiset of row bytes matches the threaded run exactly."""
    def recording_sink(batch, state):
        state.setdefault("rows", []).extend(
            np.ascontiguousarray(r).tobytes() for r in batch)
        return []

    def run(backend):
        app = spike_detection_keyed()
        app.kernels["sink"] = recording_sink
        r = backend(app, batch=128, max_batches=5, seed=3)
        return sorted(r.states["sink"][0]["rows"]), r.panes_fired

    rows_t, panes_t = run(run_app)
    rows_p, panes_p = run(run_app_processes)
    assert panes_t == panes_p > 0
    assert rows_t == rows_p


def test_plan_execute_backend_dispatch_and_placement():
    plan = Job(word_count()).plan(server_a(), optimizer="ff")
    kw = dict(batch=128, batches=5, seed=3, max_threads=6)
    rt = plan.execute(**kw)
    rp = plan.execute(backend="processes", **kw)              # faithful
    rf = plan.execute(backend="processes", faithful=False, **kw)
    for m in (rt, rp, rf):
        assert m.raw.spout_tuples > 0
    assert _summary(rt.raw) == _summary(rp.raw) == _summary(rf.raw)
    assert _keyed_bytes(rt.raw) == _keyed_bytes(rp.raw) == _keyed_bytes(rf.raw)
    with pytest.raises(ValueError, match="unknown execution backend"):
        plan.execute(backend="fpga")
    with pytest.raises(ValueError, match="backend='processes'"):
        plan.execute(env={"X": "1"})          # env is a worker-process knob


def test_plan_placement_groups_follow_socket_map():
    plan = Job(word_count()).plan(server_a(), optimizer="ff")
    par = {op: 1 for op in plan.parallelism}
    groups, pins = plan_placement(plan, par)
    assert set(groups) == {(op, 0) for op in par}
    sockets = set(groups.values())
    assert all(0 <= s < plan.machine.n_sockets for s in sockets)
    # pins partition the host cores over the plan's sockets
    assert set().union(*pins.values()) <= set(os.sched_getaffinity(0))


def test_plan_placement_colocates_device_replicas():
    plan = Job(streaming_inference(model_versions=1)).plan(
        server_a(), optimizer="rlas", compress_ratio=5, bestfit=True,
        max_nodes=5000)
    par = dict(plan.parallelism)
    assert par["predictor"] > 1 and len(set(plan.placement)) > 1
    groups, _ = plan_placement(plan, par)
    assert len({groups[("predictor", j)]
                for j in range(par["predictor"])}) == 1


_DEVICE_GROUP_CHILD = """
import json, os, sys
import numpy as np
from repro.streaming.api import Topology
from repro.streaming.procexec import DeviceGroupError, run_app_processes

def k_device(batch, state):
    import jax.numpy as jnp
    state["pid"] = os.getpid()
    return [jnp.asarray(batch) * 2.0]

def k_sink(batch, state):
    state["pid"] = os.getpid()
    state["jax"] = "jax" in sys.modules
    state["seen"] = state.get("seen", 0) + len(batch)
    return []

app = (Topology("dev")
       .spout("s", lambda b, sd: np.random.default_rng(sd).normal(size=b),
              exec_ns=100.0)
       .op("d", k_device, exec_ns=100.0, device=True, device_ns=100.0)
       .sink("k", k_sink, exec_ns=50.0)
       .build())
r = run_app_processes(app, {"d": 3}, batch=16, max_batches=6, timeout=120.0)
# naming only host operators leaves the device replicas free to share
part = run_app_processes(app, {"d": 2}, batch=16, max_batches=2,
                         groups={"s": 0, "k": 0}, timeout=120.0)
try:
    run_app_processes(app, {"d": 2}, batch=16, max_batches=2,
                      groups={("d", 0): 0, ("d", 1): 1})
    split = "accepted"
except DeviceGroupError as e:
    split = str(e)
print(json.dumps({"dev_pids": [st["pid"] for st in r.states["d"]],
                  "part_pids": [st["pid"] for st in part.states["d"]],
                  "sink_pid": r.states["k"][0]["pid"],
                  "sink_jax": r.states["k"][0]["jax"],
                  "seen": r.states["k"][0]["seen"],
                  "parent_jax": "jax" in sys.modules, "split": split}))
"""


def test_device_replicas_share_one_worker():
    """Every device replica runs in the one worker that imports JAX (the
    chip belongs to one process), also when ``groups=`` names only host
    operators; a split the caller names is refused."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    cp = subprocess.run([sys.executable, "-c", _DEVICE_GROUP_CHILD],
                        capture_output=True, text=True, env=env, timeout=240)
    assert cp.returncode == 0, cp.stderr[-2000:]
    out = json.loads(cp.stdout.strip().splitlines()[-1])
    assert len(set(out["dev_pids"])) == 1 and len(out["dev_pids"]) == 3
    assert out["sink_pid"] != out["dev_pids"][0]
    assert not out["sink_jax"] and not out["parent_jax"]
    assert len(set(out["part_pids"])) == 1 and len(out["part_pids"]) == 2
    assert out["seen"] == 6 * 16
    assert "span worker groups" in out["split"]


# ---------------------------------------------------------------------------
# State across process boundaries: migration round trip
# ---------------------------------------------------------------------------

def test_migration_round_trip_through_process_backend():
    """The WC conservation contract (test_state) with both execution legs
    on the process backend: interrupted + replanned + migrated equals the
    uninterrupted threaded single-replica run, byte for byte."""
    total, cut, seed = 8, 3, 42
    app = word_count()
    ref = run_app(word_count(), {n: 1 for n in app.graph.operators},
                  batch=64, max_batches=total, seed=seed)
    ref_counts = ref.states["counter"][0].managed.table

    job = Job(app)
    par1 = {"spout": 1, "parser": 1, "splitter": 2, "counter": 3, "sink": 1}
    plan1 = job.plan(server_a(), optimizer="ff", parallelism=par1)
    r1 = plan1.execute(batches=cut, batch=64, seed=seed, parallelism=par1,
                       backend="processes").raw

    plan2 = plan1.replan(subset(server_a(), 2))
    par2 = {"spout": 1, "parser": 1, "splitter": 1, "counter": 2, "sink": 1}
    seeded = migrate_states(app, r1.states, par2)
    r2 = plan2.execute(batches=total - cut, batch=64, seed=seed + cut,
                       parallelism=par2, initial_states=seeded,
                       backend="processes").raw

    merged = merge_keyed([st.managed for st in r2.states["counter"]])
    assert merged.tobytes() == ref_counts.tobytes()
    assert r1.spout_tuples + r2.spout_tuples == ref.spout_tuples
    assert not _shm_leftovers()


# ---------------------------------------------------------------------------
# Failure paths: crashes and wedges must not orphan segments
# ---------------------------------------------------------------------------

def _chain_app(kernel):
    return (Topology("chain")
            .spout("s", lambda b, sd: np.random.default_rng(sd)
                   .normal(size=b).astype(np.float64), exec_ns=100.0)
            .op("work", kernel, exec_ns=100.0)
            .sink("sink", lambda b, st: [], exec_ns=50.0)
            .build())


def test_worker_crash_raises_and_cleans_up():
    def exploding(batch, state):
        state["n"] = state.get("n", 0) + 1
        if state["n"] >= 2:
            raise RuntimeError("kaboom in worker")
        return [batch]

    with pytest.raises(RuntimeError, match="kaboom in worker"):
        run_app_processes(_chain_app(exploding), batch=32, max_batches=6,
                          seed=0, timeout=30.0)
    assert not _shm_leftovers()


def test_wedged_worker_times_out_fast_and_cleans_up():
    def wedged(batch, state):
        time.sleep(60.0)
        return [batch]

    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="deadline"):
        run_app_processes(_chain_app(wedged), batch=32, max_batches=4,
                          seed=0, timeout=2.0)
    assert time.perf_counter() - t0 < 20.0    # fail fast, not join_timeout
    assert not _shm_leftovers()


# ---------------------------------------------------------------------------
# Worker environment: pinning, env injection, the JAX host-device variant
# ---------------------------------------------------------------------------

def test_ring_slots_fit_wide_batches():
    """A (1024, 32) float32 jumbo is 128 KiB of rows plus a header: the
    default slot fits two batches of the consumer's declared width."""
    app = (Topology("wide")
           .spout("s", lambda b, sd: np.random.default_rng(sd).normal(
               size=(b, 32)).astype(np.float32), exec_ns=100.0,
               tuple_bytes=128.0)
           .op("work", lambda b, st: [b], exec_ns=100.0, tuple_bytes=128.0)
           .sink("sink", lambda b, st: st.__setitem__(
               "seen", st.get("seen", 0) + len(b)) or [], exec_ns=50.0,
               tuple_bytes=128.0)
           .build())
    r = run_app_processes(app, batch=1024, max_batches=3, seed=0,
                          timeout=60.0)
    assert r.states["sink"][0]["seen"] == 3 * 1024
    assert not _shm_leftovers()


def test_env_and_affinity_reach_the_worker():
    def observer(batch, state):
        if "env" not in state:
            state["env"] = os.environ.get("PROCEXEC_TEST_FLAG", "")
            state["affinity"] = sorted(os.sched_getaffinity(0))
        return [batch]

    host = sorted(os.sched_getaffinity(0))
    groups = {"s": 0, "work": 0, "sink": 0}
    r = run_app_processes(_chain_app(observer), batch=32, max_batches=3,
                          seed=0, groups=groups, pin={0: [host[0]]},
                          env={"PROCEXEC_TEST_FLAG": "on"})
    st = r.states["work"][0]
    assert st["env"] == "on"                   # injected pre-kernel
    assert st["affinity"] == [host[0]]         # sched_setaffinity applied
    assert os.environ.get("PROCEXEC_TEST_FLAG") is None   # parent untouched


def test_host_device_env_composes_xla_flags():
    env = host_device_env(4)
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in env
    # an existing count flag is replaced, other flags preserved
    old = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = \
        "--xla_cpu_enable_fast_math=true " \
        "--xla_force_host_platform_device_count=2"
    try:
        env = host_device_env(8, base={"A": "b"})
        assert env["A"] == "b"
        assert "--xla_cpu_enable_fast_math=true" in env["XLA_FLAGS"]
        assert "device_count=8" in env["XLA_FLAGS"]
        assert "device_count=2" not in env["XLA_FLAGS"]
    finally:
        if old is None:
            del os.environ["XLA_FLAGS"]
        else:
            os.environ["XLA_FLAGS"] = old


def test_socket_core_map_round_robin():
    assert socket_core_map(2, cores=[0, 1, 2, 3, 4]) == \
        {0: [0, 2, 4], 1: [1, 3]}
    # more sockets than cores: empty buckets dropped (those workers float)
    assert socket_core_map(4, cores=[7]) == {0: [7]}


def test_socket_core_map_numa_topology(tmp_path, monkeypatch):
    """With a multi-node sysfs tree, modelled sockets map onto whole NUMA
    nodes (affinity-intersected) instead of round-robining blindly; a
    single-node or absent tree falls back to round-robin."""
    for node, cpulist in [("node0", "0-3,8-9"), ("node1", "4-7"),
                          ("node7x", "ignored")]:     # non-numeric suffix
        d = tmp_path / node
        d.mkdir()
        (d / "cpulist").write_text(cpulist + "\n")
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: {0, 1, 2, 3, 4, 5, 6, 7, 8, 9})
    m = socket_core_map(2, sysfs=str(tmp_path))
    assert m == {0: [0, 1, 2, 3, 8, 9], 1: [4, 5, 6, 7]}
    # more modelled sockets than nodes: wrap around the nodes
    m4 = socket_core_map(4, sysfs=str(tmp_path))
    assert m4[0] == m4[2] == [0, 1, 2, 3, 8, 9]
    assert m4[1] == m4[3] == [4, 5, 6, 7]
    # affinity mask hides node1 entirely -> single visible node -> fallback
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 8})
    assert socket_core_map(2, sysfs=str(tmp_path)) == {0: [0, 8], 1: [1]}
    # absent tree -> plain round-robin over the affinity set
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {3, 5})
    assert socket_core_map(2, sysfs=str(tmp_path / "missing")) == \
        {0: [3], 1: [5]}
    # explicit cores= always bypasses topology
    assert socket_core_map(2, cores=[1, 2, 3], sysfs=str(tmp_path)) == \
        {0: [1, 3], 1: [2]}

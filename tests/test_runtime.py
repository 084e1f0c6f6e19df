"""Persistent runtime: async submit(), run-scoped errors, transfer cache,
the in-order group queue (dispatch ahead, completion in order),
device discovery normalization."""
import threading

import numpy as np
import pytest

from repro.core import (
    DeviceGroup,
    DeviceMask,
    Dynamic,
    EngineCL,
    HGuided,
    Program,
    RunError,
    Static,
    discover,
)
from repro.core.runtime import Runtime
from repro.core.trace import Tracer, set_tracer


def saxpy(offset, x):
    return 2.0 * x + 1.0


def make_prog(n=2048, lws=16, scale=2.0):
    x = (np.arange(n, dtype=np.float32) * scale).copy()
    y = np.zeros(n, np.float32)
    return Program().in_(x).out(y).kernel(saxpy).work_items(n, lws), x, y


# ------------------------------------------------------------- discovery fix
class FakeDevice:
    def __init__(self, platform, id):
        self.platform = platform
        self.id = id


def test_discover_mask_normalized_platforms():
    devs = [FakeDevice("cpu", 0), FakeDevice("gpu", 0), FakeDevice("gpu", 1),
            FakeDevice("tpu", 0)]
    assert [g.name for g in discover(DeviceMask.GPU, devices=devs)] == ["gpu:0", "gpu:1"]
    assert [g.name for g in discover(DeviceMask.CPU, devices=devs)] == ["cpu:0"]
    assert len(discover(DeviceMask.ALL, devices=devs)) == 4
    assert discover(DeviceMask.TPU, devices=[FakeDevice("cpu", 0)]) == []


def test_use_mask_without_a_matching_device_raises():
    """A mask that finds nothing must not fall back to every device."""
    missing = next(m for m in (DeviceMask.TPU, DeviceMask.GPU) if not discover(m))
    with pytest.raises(RuntimeError, match="no device matches"):
        EngineCL().use(missing)


# --------------------------------------------------------------- async submit
def test_concurrent_submit_two_programs():
    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(Dynamic(6))
    p1, x1, y1 = make_prog(scale=1.0)
    p2, x2, y2 = make_prog(scale=3.0)
    h1 = eng.submit(p1)
    h2 = eng.submit(p2)
    assert h1.result() is p1.outputs and h2.result() is p2.outputs
    np.testing.assert_allclose(y1, 2.0 * x1 + 1.0)
    np.testing.assert_allclose(y2, 2.0 * x2 + 1.0)
    assert h1.done() and h2.done()
    assert h1.metrics["n_packages"] > 0 and h2.metrics["n_packages"] > 0


def test_workers_persist_across_runs():
    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(Dynamic(4))
    p, x, y = make_prog()
    eng.program(p).run()
    threads_first = set(eng._runtime.executor._threads)
    for _ in range(3):
        eng.run()
    assert set(eng._runtime.executor._threads) == threads_first
    assert all(t.is_alive() for t in threads_first)
    np.testing.assert_allclose(y, 2.0 * x + 1.0)


def test_result_reraises_kernel_errors():
    def bad(offset, x):
        raise RuntimeError("kaboom")

    x = np.arange(64, dtype=np.float32)
    p = Program().in_(x).out(np.zeros(64, np.float32)).kernel(bad).work_items(64, 8)
    eng = EngineCL().use(DeviceGroup("g"))
    h = eng.submit(p)
    with pytest.raises(RunError, match="kaboom"):
        h.result()
    assert h.has_errors() and h.done()


def test_result_raises_on_validation_failure():
    p = Program().kernel(saxpy)  # no outputs, no gws -> validation error
    eng = EngineCL().use(DeviceGroup("g"))
    h = eng.submit(p)
    with pytest.raises(RunError):
        h.result()


def test_error_scoped_to_its_run_not_concurrent_one():
    """A raising kernel surfaces via has_errors() without corrupting a
    concurrent (queued-in-flight) good run on the same workers."""
    def bad(offset, x):
        raise RuntimeError("boom")

    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(Dynamic(4))
    good, x, y = make_prog()
    h_good = eng.submit(good)
    xb = np.arange(128, dtype=np.float32)
    bad_prog = Program().in_(xb).out(np.zeros(128, np.float32)).kernel(bad).work_items(128, 8)
    eng.program(bad_prog).run()
    assert eng.has_errors()
    assert "boom" in eng.get_errors()[0]
    # The good run, in flight on the same persistent workers, is untouched.
    h_good.result()
    assert not h_good.has_errors()
    np.testing.assert_allclose(y, 2.0 * x + 1.0)


def test_shared_scheduler_object_is_cloned_per_run():
    sched = HGuided(k=2)
    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(sched)
    p1, x1, y1 = make_prog(scale=1.0)
    p2, x2, y2 = make_prog(scale=5.0)
    h1, h2 = eng.submit(p1), eng.submit(p2)
    h1.result(), h2.result()
    np.testing.assert_allclose(y1, 2.0 * x1 + 1.0)
    np.testing.assert_allclose(y2, 2.0 * x2 + 1.0)
    assert h1.scheduler is not sched and h2.scheduler is not h1.scheduler


# ------------------------------------------------------------ transfer cache
def sim_groups():
    """3-group simulated heterogeneous node (GPU:PHI:CPU powers)."""
    return [
        DeviceGroup("gpu", power=4.0, sim_time_per_wi=4e-8),
        DeviceGroup("phi", power=2.0, sim_time_per_wi=8e-8),
        DeviceGroup("cpu", power=1.0, sim_time_per_wi=16e-8),
    ]


def test_iterative_transfer_cache_hits():
    """run_iterative re-transfers only changed buffers: total device_put
    count stays well under iterations x buffers x groups."""
    n, iters = 1536, 6
    state = np.full(n, 2.0 ** iters, np.float32)
    coeff = np.linspace(0.5, 0.5, n).astype(np.float32)  # constant across iters
    out = np.zeros(n, np.float32)

    def step(offset, s, c):
        return s * c

    groups = sim_groups()
    prog = Program().in_(state).in_(coeff).out(out).kernel(step).work_items(n, 16)
    eng = EngineCL().use(*groups).scheduler(Static()).program(prog)
    eng.run_iterative(iters, swap=[(0, 0)])
    assert not eng.has_errors(), eng.get_errors()
    np.testing.assert_allclose(prog._ins[0], 1.0)

    transfers = sum(g.n_transfers for g in groups)
    hits = sum(g.n_cache_hits for g in groups)
    # Static: one package per group per iteration, two input buffers.
    baseline = iters * 2 * len(groups)  # every transfer re-done, no cache
    assert hits > 0
    assert transfers < baseline, (transfers, hits, baseline)
    # The constant coeff buffer is transferred once per group, then hit.
    assert transfers == baseline - hits


def test_cache_invalidation_on_swap_and_external_write():
    n = 256
    x = np.ones(n, np.float32)
    y = np.zeros(n, np.float32)

    def double(offset, a):
        return a * 2.0

    g = DeviceGroup("solo")
    prog = Program().in_(x).out(y).kernel(double).work_items(n, 8)
    eng = EngineCL().use(g).scheduler(Static()).program(prog)
    eng.run()
    np.testing.assert_allclose(y, 2.0)
    first = g.n_transfers
    # Unchanged input -> pure cache hits on rerun.
    eng.run()
    assert g.n_transfers == first and g.n_cache_hits >= 1
    # Swap: the new input (the old output) was just produced by this group,
    # so it hands off device-resident — correct data, NO re-transfer.
    prog.swap_buffers(0, 0)
    hits_before = g.n_cache_hits
    eng.run()
    assert g.n_transfers == first and g.n_cache_hits > hits_before
    np.testing.assert_allclose(prog._outs[0], 4.0)
    # External in-place rewrite + invalidate() -> fresh transfer, fresh data.
    before = g.n_transfers
    prog._ins[0][:] = 10.0
    prog.invalidate()
    eng.run()
    assert g.n_transfers > before
    np.testing.assert_allclose(prog._outs[0], 20.0)


def test_pipeline_sees_fresh_producer_outputs():
    """Linked buffers: p2 reads what p1 just wrote, across repeated pipeline
    executions (write_outputs bumps versions -> no stale hits)."""
    n = 512
    x = np.arange(n, dtype=np.float32)
    y = np.zeros(n, np.float32)
    z = np.zeros(n, np.float32)
    p1 = Program().in_(x).out(y).kernel(lambda o, a: 2.0 * a).work_items(n, 16)
    p2 = Program().in_(y).out(z).kernel(lambda o, a: a + 1.0).work_items(n, 16)
    eng = EngineCL().use(DeviceGroup("a"), DeviceGroup("b")).scheduler(Dynamic(4))
    eng.run_pipeline(p1, p2)
    assert not eng.has_errors(), eng.get_errors()
    np.testing.assert_allclose(z, 2.0 * x + 1.0)
    # Rerun with changed x through the same persistent runtime.
    x *= 3.0
    p1.invalidate(x)
    eng.run_pipeline(p1, p2)
    np.testing.assert_allclose(z, 2.0 * x + 1.0)


# ------------------------------------------------------------ done callbacks
def test_done_callback_fires_once_after_final_state():
    """add_done_callback fires exactly once, after done() is True, for
    success, upstream poisoning, and validation failure alike."""
    eng = EngineCL().use(DeviceGroup("g"))
    fired = []
    ev = threading.Event()

    p, x, y = make_prog()
    h = eng.submit(p)
    h.add_done_callback(lambda hh: (fired.append(hh.done()), ev.set()))
    assert ev.wait(30)
    h.result()
    assert fired == [True]

    # Already-final handle: fires immediately, on the calling thread.
    late = []
    h.add_done_callback(lambda hh: late.append(threading.get_ident()))
    assert late == [threading.get_ident()]
    assert fired == [True]  # original callback did not re-fire

    # Poisoned dependent completes through the same callback path.
    def boom(offset, a):
        raise RuntimeError("upstream dead")

    bad = Program().in_(np.ones(64, np.float32)).out(
        np.zeros(64, np.float32)).kernel(boom).work_items(64, 8)
    good, _, _ = make_prog()
    hb = eng.submit(bad)
    hg = eng.submit(good, after=hb)
    poisoned = threading.Event()
    hg.add_done_callback(lambda hh: poisoned.set())
    assert poisoned.wait(30)
    assert hg.has_errors() and "poisoned" in hg.errors()[0]

    # Validation failure (_fail path: the run never reaches a worker).
    hv = eng.submit(Program().in_(np.ones(8, np.float32)).out(
        np.zeros(8, np.float32)).work_items(8, 1))  # no kernel set
    seen = threading.Event()
    hv.add_done_callback(lambda hh: seen.set())
    assert seen.wait(5)
    with pytest.raises(RunError, match="no kernel"):
        hv.result()


def test_done_callback_exception_does_not_break_worker_or_later_callbacks():
    eng = EngineCL().use(DeviceGroup("g"))
    p, x, y = make_prog()
    got = threading.Event()
    h = eng.submit(p)
    h.add_done_callback(lambda hh: 1 / 0)
    h.add_done_callback(lambda hh: got.set())
    assert got.wait(30)
    h.result()
    # The resident worker survived the raising callback: the engine still runs.
    p2, x2, y2 = make_prog(scale=5.0)
    eng.program(p2).run()
    assert not eng.has_errors(), eng.get_errors()
    np.testing.assert_allclose(y2, 2.0 * x2 + 1.0)


# ------------------------------------------------------ in-order group queue

QN, QT = 64, 0.2  # work-items; simulated device seconds per run


def double_plus(offset, x):
    return x * 2.0 + 1.0


def qprog(cost_fn=None):
    """``state`` ping-pongs between runs (in 0 / out 0), handed off on the
    device; the kernel never donates it."""
    p = Program().in_(np.arange(QN, dtype=np.float32)).out(
        np.zeros(QN, np.float32)).kernel(double_plus, "qchain").work_items(QN, 1)
    p.cost_fn = cost_fn
    return p


def qchain(prog, group, runs, *, serial=False, cbs=None):
    """``runs`` chained runs of ``prog`` pinned to ``group``: all submitted
    at once, or (``serial``) each after the last completed."""
    rt = Runtime([group])
    hs = []
    try:
        for i in range(runs):
            h = rt.submit(prog, Static(), after=hs[-1:] or None,
                          epilogue=lambda: prog.swap_buffers(0, 0),
                          groups=[group])
            if cbs is not None:
                h.add_done_callback(lambda _h, i=i: cbs.append(i))
            hs.append(h)
            if serial:
                h.wait(60)
        for h in hs:
            assert h.wait(60)
    finally:
        rt.shutdown()
    return hs


@pytest.fixture
def traced():
    tr = set_tracer(Tracer(capacity=1 << 14, enabled=True))
    yield tr
    set_tracer(Tracer(enabled=False))


def _spans(tr, name, kernel="qchain"):
    return sorted((e for e in tr.events() if e[3] == "X" and e[4] == name
                   and (e[7] or {}).get("kernel", kernel) == kernel),
                  key=lambda e: e[1])


def test_chained_runs_on_one_group_dispatch_ahead(traced):
    """Runs chained on one group: each after the first is dispatched while
    its predecessor is still on the (simulated) device, and its service
    time starts when the predecessor completes, not at its dispatch."""
    g = DeviceGroup("q", sim_time_per_wi=QT / QN)
    hs = qchain(qprog(), g, 4)
    assert [h.ahead for h in hs] == [False, True, True, True]
    dispatch = _spans(traced, "dispatch")
    assert len(dispatch) == 4
    for prev, h, d in zip(hs, hs[1:], dispatch[1:]):
        assert d[1] < prev.introspector.t_run_end
        t_free = prev.introspector.records[-1].t_end
        assert h.introspector.t_run_start == max(d[1], t_free)
    assert not _spans(traced, "dep_wait")


def test_chain_ahead_equals_serial_chain():
    ahead = qprog()
    serial = qprog()
    hs = qchain(ahead, DeviceGroup("a", sim_time_per_wi=QT / QN / 4), 5)
    hs2 = qchain(serial, DeviceGroup("s"), 5, serial=True)
    assert any(h.ahead for h in hs) and not any(h.ahead for h in hs2)
    for h in hs + hs2:
        h.result()
    np.testing.assert_array_equal(ahead._ins[0], serial._ins[0])
    x = np.arange(QN, dtype=np.float32)
    for _ in range(5):
        x = 2.0 * x + 1.0
    np.testing.assert_array_equal(ahead._ins[0], x)
    # Each run's host output is the one it produced, in its own buffer.
    for h, h2 in zip(hs, hs2):
        np.testing.assert_array_equal(h.outputs[0], h2.outputs[0])


def test_failure_found_at_completion_poisons_the_run_dispatched_ahead():
    """Run N fails only when it completes; run N+1, already dispatched
    behind it, is poisoned and writes nothing back."""
    calls = []

    def cost(off, size):
        calls.append(off)
        if len(calls) == 1:
            raise RuntimeError("device fault")
        return size

    prog = qprog(cost)
    out1 = prog._ins[0]  # the buffer run 2 writes, once the swap ran
    hs = qchain(prog, DeviceGroup("f", sim_time_per_wi=QT / QN), 2)
    assert hs[1].ahead
    with pytest.raises(RunError, match="device fault"):
        hs[0].result()
    with pytest.raises(RunError, match="poisoned"):
        hs[1].result()
    assert hs[1].outputs[0] is out1
    np.testing.assert_array_equal(out1, np.arange(QN, dtype=np.float32))
    assert len(calls) == 1


def test_split_run_waits_for_the_group_queue(traced):
    """A run split over two groups that chains on a run queued on one of
    them is dispatched only after that run completed."""
    a = DeviceGroup("sa", sim_time_per_wi=QT / QN)
    b = DeviceGroup("sb")
    prog = qprog()
    rt = Runtime([a, b])
    try:
        h1 = rt.submit(prog, Static(), groups=[a],
                       epilogue=lambda: prog.swap_buffers(0, 0))
        h2 = rt.submit(prog, Static(), after=[h1], groups=[a, b])
        h2.result(60)
    finally:
        rt.shutdown()
    assert not h2.ahead
    first = min(e[1] for e in _spans(traced, "dispatch")[1:])
    assert first >= h1.introspector.t_run_end
    np.testing.assert_array_equal(prog._outs[0],
                                  4.0 * np.arange(QN, dtype=np.float32) + 3.0)


def test_host_buffer_invalidated_after_its_producer_waits(traced):
    """A run whose input its predecessor writes, but which the host
    invalidated meanwhile, reads it from host only after the predecessor
    completed and wrote it there."""
    g = DeviceGroup("inv", sim_time_per_wi=QT / QN)
    prog = qprog()
    swapped = threading.Event()
    rt = Runtime([g])
    try:
        h1 = rt.submit(prog, Static(), groups=[g], epilogue=lambda: (
            prog.swap_buffers(0, 0), swapped.set()))
        assert swapped.wait(60) and not h1.done()
        prog.invalidate(prog._ins[0])
        h2 = rt.submit(prog, Static(), after=[h1], groups=[g])
        h2.result(60)
    finally:
        rt.shutdown()
    assert not h2.ahead
    assert _spans(traced, "dep_wait")
    np.testing.assert_array_equal(prog._outs[0],
                                  4.0 * np.arange(QN, dtype=np.float32) + 3.0)


def test_callbacks_fire_in_submit_order():
    """Chained and independent runs on one group complete, and fire their
    callbacks, in the order they were submitted."""
    cbs = []
    qchain(qprog(), DeviceGroup("cb", sim_time_per_wi=QT / QN / 4), 5,
           cbs=cbs)
    g = DeviceGroup("cb2", sim_time_per_wi=QT / QN / 4)
    rt = Runtime([g])
    seen = []
    try:
        hs = [rt.submit(qprog(), Static(), groups=[g]) for _ in range(5)]
        for i, h in enumerate(hs):
            h.add_done_callback(lambda _h, i=i: seen.append(i))
        for h in hs:
            h.result(60)
    finally:
        rt.shutdown()
    assert cbs == list(range(5)) and seen == list(range(5))


def test_many_group_queues_under_thread_switching_stress():
    """More group queues than cores, each with a chain of runs and an
    independent run interleaved, under a short switch interval: every chain
    ends at the serial chain's value and every group completes in submit
    order."""
    import os
    import sys

    n_groups, runs = (os.cpu_count() or 4) + 4, 12
    groups = [DeviceGroup(f"stress{i}") for i in range(n_groups)]
    progs = [qprog() for _ in groups]
    order = {g.name: [] for g in groups}
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    rt = Runtime(groups)
    try:
        hs = []
        for k in range(runs):
            for g, p in zip(groups, progs):
                prev = [h for h in hs if h.targets == [g] and h.program is p]
                h = rt.submit(p, Static(), after=prev[-1:] or None, groups=[g],
                              epilogue=lambda p=p: p.swap_buffers(0, 0))
                h.add_done_callback(
                    lambda _h, n=g.name, k=k: order[n].append(2 * k))
                other = rt.submit(qprog(), Static(), groups=[g])
                other.add_done_callback(
                    lambda _h, n=g.name, k=k: order[n].append(2 * k + 1))
                hs += [h, other]
        for h in hs:
            assert h.wait(120)
            h.result()
    finally:
        rt.shutdown()
        sys.setswitchinterval(old)
    x = np.arange(QN, dtype=np.float32)
    for _ in range(runs):
        x = 2.0 * x + 1.0
    for p in progs:
        np.testing.assert_array_equal(p._ins[0], x)
    for g in groups:
        assert order[g.name] == list(range(2 * runs))

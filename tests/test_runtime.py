"""Persistent runtime: async submit(), run-scoped errors, transfer cache,
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

"""Device-resident Program buffers (``Resident``): a run pinned to one
device group keeps them on its device — no host write-back, no transfer
cache entry — while a run split across groups takes the host write-back
path; row reads and writes move only the rows asked for."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import DeviceGroup, Dynamic, Program, Static
from repro.core.program import Resident, copy_rows, fill_rows
from repro.core.runtime import Runtime

N, D, RUNS = 12, 3, 5


def step(offset, x, a):
    """One iteration: the carried state and a per-row summary."""
    rows = offset + jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
    new = x * 0.5 + a + rows
    return new, new.sum(axis=1)


def make_prog(state_in, state_out):
    """``state`` ping-pongs (in 0 / out 0, donated); ``a`` is a pure input
    and the row sums a plain host output."""
    a = np.linspace(-1.0, 1.0, N * D, dtype=np.float32).reshape(N, D)
    prog = (Program().in_(state_in).in_(a).out(state_out)
            .out(np.zeros(N, np.float32)).kernel(step, "resident_step")
            .work_items(N, 1).donate(0))
    return prog


def resident_prog():
    return make_prog(Resident((N, D), np.float32, fill=1.0),
                     Resident((N, D), np.float32, fill=1.0))


def host_prog():
    return make_prog(np.ones((N, D), np.float32), np.zeros((N, D), np.float32))


def chain(prog, groups, scheduler, runs=RUNS):
    """``runs`` dependent runs over ``groups``, swapping the state pair."""
    rt = Runtime(groups)
    try:
        h = None
        for _ in range(runs):
            h = rt.submit(prog, scheduler, after=[h] if h else None,
                          epilogue=lambda: prog.swap_buffers(0, 0))
        h.result(timeout=120)
    finally:
        rt.shutdown()
    return prog


def state_of(prog) -> np.ndarray:
    s = prog._ins[0]
    return s.read_back() if isinstance(s, Resident) else np.array(s)


@pytest.fixture(scope="module")
def reference():
    """What the host write-back path leaves in the state's host buffer."""
    prog = chain(host_prog(), [DeviceGroup("ref")], Static())
    return state_of(prog), np.array(prog._outs[1])


def test_single_group_chain_copies_no_resident_bytes_to_host(reference):
    g = DeviceGroup("one")
    prog = chain(resident_prog(), [g], Static())
    st = g.transfer_stats()
    state_bytes = N * D * 4
    # Only the row sums cross to host, padded to the package's bucket of
    # 16; the state never does, and is kept trimmed to its 12 rows.
    assert st["d2h_bytes"] == RUNS * 16 * 4
    assert st["kept_bytes"] == RUNS * state_bytes
    # Created on the device with its fill: only ``a`` (padded) is uploaded.
    assert st["h2d_bytes"] == 16 * D * 4
    assert prog._ins[0].host is None and prog._ins[0].group is g
    np.testing.assert_array_equal(prog._outs[1], reference[1])


def test_read_back_equals_the_host_write_back_value(reference):
    g = DeviceGroup("rb")
    prog = chain(resident_prog(), [g], Static())
    before = g.d2h_bytes
    np.testing.assert_array_equal(prog._ins[0].read_back(), reference[0])
    assert g.d2h_bytes == before + N * D * 4  # counted when read
    rows = prog._ins[0].read_back([2, 7])
    np.testing.assert_array_equal(rows, reference[0][[2, 7]])
    assert g.d2h_bytes == before + (N + 2) * D * 4


def test_split_over_two_groups_takes_write_back_and_is_bit_identical(
        reference):
    ga, gb = DeviceGroup("split-a"), DeviceGroup("split-b")
    prog = chain(resident_prog(), [ga, gb], Dynamic(4))
    for g in (ga, gb):
        assert g.transfer_stats()["kept_bytes"] == 0
    # Every package wrote its state rows and sums back to host.
    assert ga.d2h_bytes + gb.d2h_bytes >= RUNS * N * (D + 1) * 4
    assert prog._ins[0].host is not None
    np.testing.assert_array_equal(state_of(prog), reference[0])
    np.testing.assert_array_equal(prog._outs[1], reference[1])


def test_single_group_many_packages_keeps_rows_on_device(reference):
    """Several packages of one run on one group (padded to their buckets):
    each keeps its rows of the output on the device."""
    g = DeviceGroup("pkgs")
    prog = chain(resident_prog(), [g], Dynamic(5))
    assert g.transfer_stats()["kept_bytes"] == RUNS * N * D * 4
    np.testing.assert_array_equal(state_of(prog), reference[0])
    np.testing.assert_array_equal(prog._outs[1], reference[1])


def test_one_entry_lru_never_loses_a_resident_value(reference):
    g = DeviceGroup("lru1", transfer_cache_entries=1)
    prog = chain(resident_prog(), [g], Static())
    assert g.transfer_stats()["cached_entries"] <= 1
    np.testing.assert_array_equal(state_of(prog), reference[0])
    np.testing.assert_array_equal(prog._outs[1], reference[1])


def test_pinned_run_after_split_runs_places_the_host_value(reference):
    """A split run leaves the state on host; the next pinned run uploads
    it once and keeps it on the device again."""
    ga, gb = DeviceGroup("back-a"), DeviceGroup("back-b")
    prog = chain(resident_prog(), [ga, gb], Dynamic(3), runs=RUNS - 1)
    chain(prog, [ga], Static(), runs=1)
    assert prog._ins[0].host is None and prog._ins[0].group is ga
    np.testing.assert_array_equal(state_of(prog), reference[0])


def test_copy_and_fill_rows_patch_only_the_rows():
    g = DeviceGroup("rows")
    dst = Resident((4, 2), np.int32, fill=7)
    src = Resident((2, 2), np.int32)
    src.place(g)
    copy_rows([dst], [3, 1], [src], g)  # never written: created on g
    assert dst.group is g and g.n_transfers == 0
    np.testing.assert_array_equal(dst.read_back(),
                                  [[7, 7], [0, 0], [7, 7], [0, 0]])
    copy_rows([dst], [2], [np.array([[5, 6]], np.int32)], g)
    assert g.n_transfers == 1 and g.h2d_bytes == 8  # the one host row
    fill_rows([dst], [0, 3], -1, g)
    np.testing.assert_array_equal(dst.read_back(),
                                  [[-1, -1], [0, 0], [5, 6], [-1, -1]])


def test_host_protocol_refuses_a_device_value():
    g = DeviceGroup("strict")
    r = Resident((2, 2), np.float32)
    r.place(g)
    with pytest.raises(RuntimeError, match="read_back"):
        r[0]
    r.to_host()
    r[0] = 3.0
    np.testing.assert_array_equal(r.read_back([0]), [[3.0, 3.0]])

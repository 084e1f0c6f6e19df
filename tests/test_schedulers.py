"""Scheduler unit + property tests (system invariant: every work-group is
handed out exactly once, regardless of powers/devices/package counts)."""
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Dynamic, HGuided, Static
from repro.core.device import DeviceGroup


def drain(sched, total_groups, lws, devices, order=None):
    """Pull packages round-robin until exhausted; returns [(dev, off, size)]."""
    sched.prepare(total_groups, lws, devices)
    out = []
    active = list(devices)
    i = 0
    while active:
        d = active[i % len(active)]
        pkg = sched.next_package(d)
        if pkg is None:
            active.remove(d)
            continue
        out.append((d.name, pkg[0], pkg[1]))
        sched.observe(d, pkg[1], 0.01)
        i += 1
    return out


def check_partition(pkgs, total_wi):
    covered = np.zeros(total_wi, int)
    for _, off, size in pkgs:
        covered[off : off + size] += 1
    assert (covered == 1).all(), "work-items must be covered exactly once"


@given(
    total_groups=st.integers(1, 500),
    lws=st.sampled_from([1, 16, 64, 255]),
    powers=st.lists(st.floats(0.1, 16.0), min_size=1, max_size=6),
    n_pkgs=st.integers(1, 64),
)
@settings(max_examples=60, deadline=None)
def test_dynamic_partitions_exactly(total_groups, lws, powers, n_pkgs):
    devs = [DeviceGroup(f"d{i}", power=p) for i, p in enumerate(powers)]
    pkgs = drain(Dynamic(n_pkgs), total_groups, lws, devs)
    check_partition(pkgs, total_groups * lws)


@given(
    total_groups=st.integers(1, 500),
    powers=st.lists(st.floats(0.1, 16.0), min_size=1, max_size=6),
    k=st.floats(1.0, 4.0),
    adaptive=st.booleans(),
)
@settings(max_examples=60, deadline=None)
def test_hguided_partitions_exactly(total_groups, powers, k, adaptive):
    devs = [DeviceGroup(f"d{i}", power=p) for i, p in enumerate(powers)]
    pkgs = drain(HGuided(k=k, adaptive=adaptive), total_groups, 8, devs)
    check_partition(pkgs, total_groups * 8)


@given(
    total_groups=st.integers(1, 300),
    powers=st.lists(st.floats(0.1, 8.0), min_size=1, max_size=5),
    reverse=st.booleans(),
)
@settings(max_examples=60, deadline=None)
def test_static_partitions_exactly(total_groups, powers, reverse):
    devs = [DeviceGroup(f"d{i}", power=p) for i, p in enumerate(powers)]
    pkgs = drain(Static(reverse=reverse), total_groups, 4, devs)
    check_partition(pkgs, total_groups * 4)
    assert len(pkgs) <= len(devs)  # static: at most one package per device


def test_static_proportional_shares():
    devs = [DeviceGroup("a", power=3.0), DeviceGroup("b", power=1.0)]
    pkgs = dict((n, s) for n, _, s in drain(Static(), 100, 1, devs))
    assert pkgs["a"] == 75 and pkgs["b"] == 25


def test_static_explicit_props_paper_form():
    # Paper: props for first N-1 devices, remainder to the last.
    devs = [DeviceGroup("cpu"), DeviceGroup("phi"), DeviceGroup("gpu")]
    pkgs = dict((n, s) for n, _, s in drain(Static(props=[0.08, 0.3]), 100, 1, devs))
    assert pkgs["cpu"] == 8 and pkgs["phi"] == 30 and pkgs["gpu"] == 62


def test_hguided_decreasing_packages():
    devs = [DeviceGroup("a", power=1.0)]
    pkgs = drain(HGuided(k=2), 256, 1, devs)
    sizes = [s for _, _, s in pkgs]
    assert sizes == sorted(sizes, reverse=True)
    # paper formula: first package = floor(256 * 1 / (2 * 1 * 1)) = 128
    assert sizes[0] == 128


def test_hguided_min_package_scales_with_power():
    fast = DeviceGroup("fast", power=8.0, min_package_groups=4)
    slow = DeviceGroup("slow", power=1.0, min_package_groups=4)
    sched = HGuided(k=2)
    sched.prepare(1000, 1, [fast, slow])
    f = sched.next_package(fast)
    s = sched.next_package(slow)
    assert f[1] > s[1]


def test_hguided_adaptive_rerates():
    fast = DeviceGroup("fast", power=1.0)  # wrong prior: actually fast
    slow = DeviceGroup("slow", power=1.0)
    sched = HGuided(k=2, adaptive=True)
    sched.prepare(10_000, 1, [fast, slow])
    p1 = sched.next_package(fast)
    sched.observe(fast, p1[1], 0.001)  # very fast
    p2 = sched.next_package(slow)
    sched.observe(slow, p2[1], 1.0)  # very slow
    f2 = sched.next_package(fast)
    s2 = sched.next_package(slow)
    assert f2[1] > s2[1], "adaptive HGuided must give the fast device bigger packages"

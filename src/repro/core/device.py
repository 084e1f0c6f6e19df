"""Tier-2 ``DeviceGroup``: the co-execution unit.

In the paper a Device wraps one OpenCL device and its command queue/thread.
Here a DeviceGroup wraps a set of JAX devices (one chip, a host slice, or a
whole pod sub-mesh) plus scheduling metadata: a relative compute ``power``,
a minimum package size and an optional *specialized kernel* (the paper's
per-device kernel source/binary → a per-group jit variant).

``sim_flops`` emulates heterogeneous compute capacity on the single-CPU CI
container (used by the load-balancing benchmarks): after the real kernel
runs, the group idles to match a device of the given throughput.  Overhead
benchmarks never set it.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.program import Resident, buffer_version
from repro.core.trace import tracer


def jnp_int32(x: int):
    return np.int32(x)


class DeviceGroup:
    def __init__(
        self,
        name: str,
        devices: Optional[Sequence[jax.Device]] = None,
        *,
        power: float = 1.0,
        watts: float = 0.0,
        min_package_groups: int = 1,
        kernel: Optional[Callable] = None,
        sim_time_per_wi: float = 0.0,
        transfer_cache_entries: int = 128,
    ) -> None:
        self.name = name
        self.devices = list(devices) if devices else [jax.devices()[0]]
        self.power = power
        # Rated board power (0 = unrated).  Rate-aware placement divides
        # observed throughput by watts when set, so scheduling optimizes
        # tokens/joule instead of raw tokens/s (Green Computing rating).
        self.watts = watts
        self.min_package_groups = min_package_groups
        self.specialized_kernel = kernel
        self.sim_time_per_wi = sim_time_per_wi
        self._compiled: dict[Any, Callable] = {}
        self._sim_clock = 0.0  # simulated completion time of the last package
        # Device-resident transfer cache: (buffer version, offset, bucket) ->
        # padded device array.  Versions (program.buffer_version) change when
        # a buffer is rewritten/swapped, so hits are always content-correct.
        self._xfer_cache: OrderedDict[tuple, Any] = OrderedDict()
        self._xfer_cache_entries = max(0, transfer_cache_entries)
        self._xfer_lock = threading.Lock()
        # ids of host buffers that were garbage collected: their cached
        # device slices can never be hit again, so they are evicted on the
        # next cache access.  Appended from GC finalizers (which may run
        # while _xfer_lock is held on this very thread), hence a lock-free
        # list + drain-under-lock instead of direct eviction.  _tracked_ids
        # guarantees ONE finalizer per live buffer per group, however many
        # slices/versions of it get cached.
        self._dead_buffers: list = []
        self._tracked_ids: set = set()
        self._placed_args: dict = {}  # id(arg) -> (arg, copy on this device)
        self.n_transfers = 0  # device_put calls for kernel inputs
        self.n_cache_hits = 0
        # Bytes each way, padding included: kernel inputs put on the device,
        # kernel inputs served from the transfer cache or a ``Resident``
        # value instead, outputs copied back to host buffers
        # (``count_d2h``), and ``Resident`` outputs left on the device
        # (``count_kept``).
        self.h2d_bytes = 0
        self.resident_bytes = 0
        self.d2h_bytes = 0
        self.kept_bytes = 0

    @property
    def device(self) -> jax.Device:
        return self.devices[0]

    def compile_kernel(self, program) -> Callable:
        """Per-group jit of the (possibly specialized) kernel."""
        fn = self.specialized_kernel or program._kernel
        # Kernel signature is (offset, *ins, *args): donated input i is
        # jit argument i + 1.
        donate = tuple(1 + i for i in program.donated_ins)
        key = (id(fn), program._kernel_name, donate)
        if key not in self._compiled:
            # Placement follows the device_put inputs, so one jit per group
            # suffices (computation runs where its operands live).
            self._compiled[key] = jax.jit(fn, donate_argnums=donate)
        return self._compiled[key]

    @staticmethod
    def _bucket(size_wi: int, lws: int) -> int:
        """Round a package up to a power-of-two number of work-groups.

        XLA specializes executables on shapes (unlike OpenCL NDRanges), so
        variable package sizes (HGuided!) would recompile per size.  Bucketing
        caps compilations at log2(max_groups) per device; the tail is padded
        and trimmed on write-back.
        """
        groups = -(-size_wi // lws)
        return lws * (1 << max(0, (groups - 1).bit_length()))

    # ------------------------------------------------------- transfer cache
    def _drain_dead(self) -> None:
        """Evict entries of collected buffers (lock held by caller)."""
        if not self._dead_buffers:
            return
        dead = set()
        while self._dead_buffers:  # atomic pops: appends are never lost
            dead.add(self._dead_buffers.pop())
        self._tracked_ids -= dead
        for k in [k for k in self._xfer_cache if k[0] in dead]:
            del self._xfer_cache[k]

    def _cache_get(self, key, *, take: bool = False):
        with self._xfer_lock:
            self._drain_dead()
            if take:
                # Consume the entry: the caller will donate the device array
                # to a kernel (XLA deletes it), so a retained entry would
                # serve a dead buffer on the next probe.
                return self._xfer_cache.pop(key, None)
            v = self._xfer_cache.get(key)
            if v is not None:
                self._xfer_cache.move_to_end(key)
            return v

    def _cache_put(self, key, value, host_buf) -> None:
        if self._xfer_cache_entries <= 0:
            return
        with self._xfer_lock:
            self._drain_dead()
            register = key[0] not in self._tracked_ids
            if register:
                self._tracked_ids.add(key[0])
        if register:
            try:
                weakref.finalize(host_buf, self._dead_buffers.append, key[0])
            except TypeError:  # can't observe its death: don't pin a copy
                with self._xfer_lock:
                    self._tracked_ids.discard(key[0])
                return
        with self._xfer_lock:
            self._xfer_cache[key] = value
            self._xfer_cache.move_to_end(key)
            while len(self._xfer_cache) > self._xfer_cache_entries:
                self._xfer_cache.popitem(last=False)

    def clear_cache(self) -> None:
        with self._xfer_lock:
            self._xfer_cache.clear()

    def transfer_stats(self) -> dict:
        with self._xfer_lock:
            return {
                "transfers": self.n_transfers,
                "cache_hits": self.n_cache_hits,
                "h2d_bytes": self.h2d_bytes,
                "resident_bytes": self.resident_bytes,
                "d2h_bytes": self.d2h_bytes,
                "kept_bytes": self.kept_bytes,
                "cached_entries": len(self._xfer_cache),
            }

    def count_d2h(self, nbytes: int) -> None:
        """Count ``nbytes`` copied from this group to host."""
        with self._xfer_lock:
            self.d2h_bytes += nbytes

    def count_h2d(self, nbytes: int) -> None:
        """Count one transfer of ``nbytes`` from host to this group."""
        with self._xfer_lock:
            self.n_transfers += 1
            self.h2d_bytes += nbytes

    def count_kept(self, nbytes: int) -> None:
        """Count ``nbytes`` of ``Resident`` outputs left on this group."""
        with self._xfer_lock:
            self.kept_bytes += nbytes

    def _input_slice(self, program, host_buf, offset_wi: int, size_wi: int,
                     bucket: int, *, consume: bool = False,
                     keep_resident: bool = False,
                     host_read: Optional[Callable] = None):
        """Device copy of one input's package slice, padded to the bucket.

        Cached per (buffer version, offset, bucket): iterative/serving reruns
        over unchanged buffers skip the host->device transfer entirely.
        ``consume`` (donated inputs): the kernel will delete the device
        array, so a cache hit is *popped* and fresh transfers are never
        retained — each upload/handoff serves exactly one run.

        ``keep_resident`` (a run pinned to this group): a ``Resident`` input
        is read from its device value (:meth:`_resident_slice`).

        ``host_read(host_buf)`` is called before the slice is read from host
        or a cached device copy of it is donated (the runtime waits there
        for a producer that has yet to copy it back to host).

        Returns (device array, bytes put on the device, bytes served from
        the cache or a ``Resident`` value)."""
        lo, hi = program.rows_of(host_buf, offset_wi, size_wi)
        need = int(program.buffer_ratio(host_buf) * bucket) - (hi - lo)
        if keep_resident and isinstance(host_buf, Resident):
            return self._resident_slice(host_buf, lo, hi, need, consume)
        if consume and host_read is not None:
            host_read(host_buf)
        # A buffer that is both input and output of the same Program
        # (in-place update) is uncacheable: under run-scoped write versions a
        # mid-run input slice would be keyed on the run's final version and
        # could shadow the produced output for dependent runs.
        if any(b is host_buf for b in program._outs):
            version = None
        else:
            version = buffer_version(host_buf)
        # Keyed on element bounds (not work-items): a buffer shared between
        # programs of different gws can't alias a wrong slice.  The leading
        # id ties every entry to the buffer whose death evicts it.
        key = (id(host_buf), version, lo, hi, need) if version is not None else None
        if key is not None:
            cached = self._cache_get(key, take=consume)
            if cached is not None:
                with self._xfer_lock:
                    self.n_cache_hits += 1
                    self.resident_bytes += cached.nbytes
                return cached, 0, cached.nbytes
            if need > 0:
                # Handoff probe: a producer run stashed this exact element
                # range unpadded (need=0).  Padding happens device-side —
                # no host re-read, no device_put.  The padded array is a new
                # buffer, so donating it never touches the stashed base.
                base = self._cache_get(key[:4] + (0,))
                if base is not None:
                    dev = jnp.pad(
                        base, [(0, need)] + [(0, 0)] * (base.ndim - 1)
                    )
                    with self._xfer_lock:
                        self.n_cache_hits += 1
                        self.resident_bytes += dev.nbytes
                    if not consume:
                        self._cache_put(key, dev, host_buf)
                    return dev, 0, dev.nbytes
        if host_read is not None:
            host_read(host_buf)
        b = host_buf[lo:hi]
        if need > 0:
            b = np.pad(np.asarray(b), [(0, need)] + [(0, 0)] * (b.ndim - 1))
        dev = jax.device_put(b, self.device)
        with self._xfer_lock:
            self.n_transfers += 1
            self.h2d_bytes += b.nbytes
        if key is not None and not consume:
            self._cache_put(key, dev, host_buf)
        return dev, b.nbytes, 0

    def _resident_slice(self, buf: Resident, lo: int, hi: int, need: int,
                        consume: bool):
        """A ``Resident`` input's rows ``lo:hi`` on this group's device,
        padded by ``need`` rows: the value itself when the package covers it
        whole (taken from ``buf`` when donated: the kernel consumes it), else
        a device-side slice.  No host copy, no transfer-cache entry."""
        up = buf.place(self)
        if lo == 0 and hi == len(buf) and need == 0:
            dev = buf.take() if consume else buf.value
        else:
            dev = buf.value[lo:hi]
            if need > 0:
                dev = jnp.pad(dev, [(0, need)] + [(0, 0)] * (dev.ndim - 1))
        if up:
            return dev, up, 0
        with self._xfer_lock:
            self.resident_bytes += dev.nbytes
        return dev, 0, dev.nbytes

    def stash_output(self, program, host_buf, offset_wi: int, size_wi: int,
                     dev_result, version: Optional[int]) -> None:
        """Device-resident output handoff: seed the transfer cache with a
        slice this group just produced, keyed under the producing run's
        write ``version`` (``RunHandle.version_for_write``).  A dependent
        run that reads the same element range on this group then serves the
        still-on-device result instead of re-reading host memory and paying
        a fresh ``jax.device_put``.  Bucket padding is trimmed device-side
        (pad lanes hold garbage computed from padded inputs); consumers
        re-pad with zeros on their own bucket geometry."""
        if version is None or self._xfer_cache_entries <= 0:
            return
        r = program.buffer_ratio(host_buf)
        lo, hi = int(r * offset_wi), int(r * (offset_wi + size_wi))
        self._cache_put((id(host_buf), version, lo, hi, 0),
                        dev_result[: hi - lo], host_buf)

    def patch_cached(self, program, host_buf, rows, values) -> bool:
        """Patch leading-axis rows of this group's stashed device copy of
        ``host_buf`` in place, *without* a version bump.

        Slot migration rewrites a few rows of a mirror the destination group
        already holds device-resident (the full-range ``stash_output`` entry
        from its last segment).  Re-uploading the whole mirror would be
        O(buffer); this is O(rows).  The caller must have already written the
        same rows into the host mirror, so host and device stay coherent
        under the *unchanged* version token.

        Returns False (caller must ``invalidate`` instead) when no full-range
        stash exists — first segment on this group, entry LRU-evicted, or the
        buffer is uncacheable.  On success, every *other* cached entry for
        this buffer id is evicted (padded variants under the same version
        would otherwise serve stale rows) and exactly one transfer is
        counted for the O(rows) upload."""
        if any(b is host_buf for b in program._outs):
            return False
        version = buffer_version(host_buf)
        if version is None:
            return False
        base_key = (id(host_buf), version, 0, len(host_buf), 0)
        with self._xfer_lock:
            self._drain_dead()
            base = self._xfer_cache.get(base_key)
            if base is None:
                return False
            for k in [k for k in self._xfer_cache
                      if k[0] == id(host_buf) and k != base_key]:
                del self._xfer_cache[k]
        idx = jnp.asarray(np.asarray(rows, np.int32))
        vals = jax.device_put(jnp.asarray(values), self.device)
        patched = base.at[idx].set(vals)
        with self._xfer_lock:
            self.n_transfers += 1
            self.h2d_bytes += idx.nbytes + vals.nbytes
            self._xfer_cache[base_key] = patched
            self._xfer_cache.move_to_end(base_key)
        return True

    def execute_chunk(self, program, offset_wi: int, size_wi: int, *,
                      keep_resident: bool = False,
                      host_read: Optional[Callable] = None):
        """Run one package; returns device arrays (async, not blocked).

        Inputs are padded to the bucket size; callers must trim outputs to
        ``size_wi`` (Program.write_outputs does).  Staging them is the
        ``upload`` span on the group's track, with args ``bytes`` (put on
        the device) and ``resident_bytes`` (served from the transfer
        cache or a ``Resident`` value, read on the device when
        ``keep_resident``: the run is pinned to this group).
        ``jax.device_put`` may return before its copy ends, so the span is
        the host's share of the copy; ``bytes`` is the whole of it.
        ``host_read``: see :meth:`_input_slice`.
        """
        fn = self.compile_kernel(program)
        bucket = self._bucket(size_wi, program.lws)
        donated = set(program.donated_ins)
        ins, h2d, resident = [], 0, 0
        with tracer().span("upload", track=f"group/{self.name}",
                           kernel=program.label) as sp:
            for i, b in enumerate(program._ins):
                dev, up, hit = self._input_slice(program, b, offset_wi,
                                                 size_wi, bucket,
                                                 consume=i in donated,
                                                 keep_resident=keep_resident,
                                                 host_read=host_read)
                ins.append(dev)
                h2d += up
                resident += hit
            sp.set(bytes=h2d, resident_bytes=resident)
        # offset passed as a traced scalar: no recompile per package.
        res = fn(jnp_int32(offset_wi), *ins, *map(self._placed, program._args))
        return res

    def _placed(self, arg):
        """``arg`` with every device array on this group's device.  A jit
        call cannot mix arrays committed to different devices, and an
        uncommitted one would be copied on every call, so an argument that
        lives elsewhere (weights built on device 0 for a member on device
        3) is copied here once and the copy reused while ``arg`` lives."""
        leaves = jax.tree_util.tree_leaves(arg)
        if all(not isinstance(x, jax.Array) or x.devices() == {self.device}
               for x in leaves):
            return arg
        with self._xfer_lock:
            hit = self._placed_args.get(id(arg))
        if hit is not None and hit[0] is arg:
            return hit[1]
        placed = jax.device_put(arg, self.device)
        with self._xfer_lock:
            # Holding ``arg`` keeps its id from being reused by another.
            self._placed_args[id(arg)] = (arg, placed)
        return placed

    def simulate_service_time(self, size_wi: int, elapsed: float,
                              cost_units: Optional[float] = None) -> None:
        """Pad to the service time a device of this speed would need.

        A real device computes packages *serially*, so the simulated clock
        advances from the later of (previous simulated completion, actual
        package start) — otherwise pipelined dispatch would let sleeps
        overlap and produce impossible >S_max speedups.

        ``cost_units`` (defaults to size_wi) lets irregular kernels charge
        content-dependent work (Program.cost_fn)."""
        if self.sim_time_per_wi <= 0:
            return
        target = (cost_units if cost_units is not None else size_wi) * self.sim_time_per_wi
        now = time.perf_counter()
        start = max(self._sim_clock, now - elapsed)
        end = start + target
        if end > now:
            time.sleep(end - now)
            self._sim_clock = end
        else:
            self._sim_clock = now

    def __repr__(self) -> str:
        return f"DeviceGroup({self.name!r}, power={self.power}, n={len(self.devices)})"

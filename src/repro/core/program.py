"""Tier-1 ``Program``: the application-domain unit of EngineCL.

A Program owns input/output buffers, a data-parallel kernel and an
*out pattern* — exactly the paper's abstraction (§4.2).  The kernel is any
JAX function over chunk slices:

    program = Program()
    program.in_(x)                      # host buffers (numpy or jax arrays)
    program.out(y)
    program.out_pattern(1, 255)         # 1 output element per 255 work-items
    program.kernel(fn, "binomial")      # fn(offset, *in_slices) -> out slices

The leading axis of every buffer is the data-parallel axis.  Buffer lengths
relate to the global work size through their own ratio (len / gws), so
buffers of different granularity (e.g. Binomial's 1:255) partition
consistently — the runtime slices work-items, never raw indices.
"""
from __future__ import annotations

import functools
import itertools
import threading
import weakref
from fractions import Fraction
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------- buffer versioning
# The device-resident transfer cache (DeviceGroup) keys cached transfers on a
# *version token*: a process-unique integer assigned per host buffer and
# re-assigned whenever the buffer's contents change through runtime APIs
# (write_outputs, swap_buffers, invalidate).  Tokens come from one global
# counter, so a recycled ``id()`` after garbage collection can never alias a
# live cache entry.  Buffers that don't support weakrefs are uncacheable
# (version None) — correctness never depends on the finalizer firing.

_version_counter = itertools.count(1)
_versions: dict[int, int] = {}
_versions_lock = threading.Lock()


def _drop_version(key: int) -> None:
    # GC callback: may fire on a thread that already holds _versions_lock
    # (any allocation inside the locked regions can trigger collection), so
    # it must not acquire it.  A bare dict.pop is atomic under the GIL, and
    # the worst race outcome is a lost registration — the next lookup just
    # assigns a fresh (never-reused) token, i.e. a cache miss, never a stale
    # hit.
    _versions.pop(key, None)


def buffer_version(buf) -> Optional[int]:
    """Current version token for ``buf`` (None = not cacheable)."""
    key = id(buf)
    with _versions_lock:
        v = _versions.get(key)
        if v is None:
            try:
                weakref.finalize(buf, _drop_version, key)
            except TypeError:
                return None
            v = _versions[key] = next(_version_counter)
        return v


def bump_version(buf) -> None:
    """Invalidate cached transfers of ``buf`` (its contents changed)."""
    key = id(buf)
    with _versions_lock:
        if key in _versions:
            _versions[key] = next(_version_counter)


# ----------------------------------------------------- device-resident buffers
class Resident:
    """A Program buffer whose authoritative value lives on a device group.

    Declared by shape, dtype and fill (``fill`` is what every element holds
    before anything is written), not by a host array: nothing allocates a
    host copy of its full size.  Its value is created on the device the
    first time a run needs it.

    A run pinned to exactly one ``DeviceGroup`` (``RunHandle.on_one_group``)
    reads the value on that group's device and leaves its new output value
    there, at dispatch (``Runtime._hand_off``: a later run on the group may
    take it before this one completes); nothing of it is copied to host,
    and it is never an entry of the evictable transfer cache, so an
    eviction cannot lose it.  A run split across several groups first brings it to host
    (:meth:`to_host`); for that run it is an ordinary host buffer, written
    back and handed off through the transfer cache like any other.

    Host reads go through :meth:`read_back` (only the rows asked for, counted
    in the group's ``d2h_bytes``); row writes through :func:`copy_rows` and
    :func:`fill_rows`, which patch the device value in place."""

    def __init__(self, shape, dtype, fill=0) -> None:
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.fill = fill
        self.value = None  # device array (authoritative) while on a device
        self.group = None  # the DeviceGroup holding ``value``
        self.host = None   # host array while a split run needs one

    def __len__(self) -> int:
        return self.shape[0]

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * self.dtype.itemsize

    # Host-buffer protocol for the write-back path, legal only while on
    # host (``to_host``: a split run); elsewhere use ``read_back``.
    def __getitem__(self, idx):
        return self._host()[idx]

    def __setitem__(self, idx, values) -> None:
        self._host()[idx] = values

    def _host(self) -> np.ndarray:
        if self.host is None:
            raise RuntimeError("Resident buffer is on the device: read it "
                               "with read_back()")
        return self.host

    def place(self, group) -> int:
        """Make ``value`` live on ``group``'s device: created there with the
        fill, uploaded from host (counted on ``group``), or moved from the
        group that held it.  Returns the bytes put on the device from host."""
        up = 0
        if self.host is not None:
            self.value = jax.device_put(self.host, group.device)
            up = self.host.nbytes
            group.count_h2d(up)
            self.host = None
        elif self.value is None:
            self.value = jnp.full(self.shape, self.fill, self.dtype,
                                  device=group.device)
        elif self.group is not group:
            self.value = jax.device_put(self.value, group.device)
        self.group = group
        return up

    def take(self):
        """Hand the device value to a kernel that donates it (the kernel
        consumes the array; its output becomes the next value)."""
        v, self.value = self.value, None
        return v

    def keep(self, group, lo: int, hi: int, result) -> int:
        """Store rows ``lo:hi`` of a run's output, produced on ``group``, as
        the new value (bucket padding trimmed on the device).  Returns the
        bytes kept on the device."""
        part = result if result.shape[0] == hi - lo else result[: hi - lo]
        if lo == 0 and hi == len(self):
            self.value, self.group, self.host = part, group, None
        else:
            self.place(group)
            self.value = self.value.at[lo:hi].set(part)
        return part.nbytes

    def read_back(self, rows=None) -> np.ndarray:
        """Host copy of ``rows`` (all rows when None), counted in the holding
        group's ``d2h_bytes``."""
        sel = slice(None) if rows is None else np.asarray(rows, np.intp)
        if self.host is not None:
            return np.array(self.host[sel])
        if self.value is None:
            n = len(self) if rows is None else len(sel)
            return np.full((n,) + self.shape[1:], self.fill, self.dtype)
        v = self.value if rows is None else self.value[jnp.asarray(sel)]
        out = np.array(v)
        self.group.count_d2h(out.nbytes)
        return out

    def to_host(self) -> np.ndarray:
        """Bring the value to host (read back whole, or the fill when never
        written) for a run split across groups; the host array is then the
        authoritative copy until a pinned run places it again."""
        if self.host is None:
            self.host = self.read_back()
            self.value = None
        return self.host

    def clear(self) -> None:
        """Drop the value (a swapped-out input about to be overwritten)."""
        self.value = self.group = self.host = None
        bump_version(self)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(dsts, srcs, idx):
    return tuple(d.at[idx].set(s.astype(d.dtype)) for d, s in zip(dsts, srcs))


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
def _fill_rows(dsts, idx, value):
    return tuple(d.at[idx].set(value) for d in dsts)


def copy_rows(dsts: Sequence[Resident], rows, srcs: Sequence, group) -> None:
    """Write ``srcs[k]`` (one source row per entry of ``rows``: a
    ``Resident``, a device array or a host array) into rows ``rows`` of
    ``dsts[k]`` on the device, in one jitted, donated scatter for the whole
    set: only the rows move.  A never-written destination is created on
    ``group``; host sources are uploaded (counted on the group); a
    destination on host (a split run's) is written there."""
    idx = np.asarray(rows, np.int32)
    live, vals = [], []
    for d, s in zip(dsts, srcs):
        if isinstance(s, Resident):
            s = s.value if s.value is not None else s.read_back()
        if d.host is not None:
            d.host[idx] = np.asarray(s)
            bump_version(d)
            continue
        d.place(d.group or group)
        if not isinstance(s, jax.Array):
            d.group.count_h2d(np.asarray(s).nbytes)
        live.append(d)
        vals.append(s)
    if live:
        out = _scatter_rows(tuple(d.value for d in live), tuple(vals),
                            jnp.asarray(idx))
        for d, v in zip(live, out):
            d.value = v
            bump_version(d)


def fill_rows(dsts: Sequence[Resident], rows, value, group) -> None:
    """Set rows ``rows`` of every buffer in ``dsts`` to ``value`` on the
    device (one jitted, donated scatter), or on host for a split run's."""
    idx = np.asarray(rows, np.int32)
    live = []
    for d in dsts:
        if d.host is not None:
            d.host[idx] = value
            bump_version(d)
        else:
            d.place(d.group or group)
            live.append(d)
    if live:
        out = _fill_rows(tuple(d.value for d in live), jnp.asarray(idx), value)
        for d, v in zip(live, out):
            d.value = v
            bump_version(d)


class Program:
    def __init__(self) -> None:
        self._ins: list[Any] = []
        self._outs: list[Any] = []
        self._linked: list["Program"] = []
        self._kernel: Optional[Callable] = None
        self._kernel_name: str = "kernel"
        self._args: list[Any] = []
        self._donated_ins: tuple[int, ...] = ()
        self._out_pattern = Fraction(1, 1)  # out elems per work-item
        self.gws: Optional[int] = None
        self.lws: int = 1
        # Optional relative-cost model f(offset_wi, size_wi) -> work units
        # (default: size).  Used only by simulated-heterogeneity DeviceGroups
        # to model irregular kernels (Mandelbrot/Ray) on the CI container.
        self.cost_fn: Optional[Callable[[int, int], float]] = None

    # -- buffers ---------------------------------------------------------
    def in_(self, buf) -> "Program":
        self._ins.append(buf)
        return self

    def out(self, buf) -> "Program":
        self._outs.append(buf if isinstance(buf, Resident) else np.asarray(buf))
        return self

    def out_pattern(self, out_elems: int, work_items: int = 1) -> "Program":
        """``out_elems`` output indices written per ``work_items`` work-items."""
        self._out_pattern = Fraction(out_elems, work_items)
        return self

    # -- kernel ----------------------------------------------------------
    def kernel(self, fn: Callable, name: str = "kernel") -> "Program":
        """fn(offset:int, *in_slices, *args) -> out slice (or tuple of)."""
        self._kernel = fn
        self._kernel_name = name
        return self

    @property
    def label(self) -> str:
        """Human-readable kernel name — what traces and jit-cache keys call
        this Program's work (e.g. ``decode_seg4``, ``prefill_32``)."""
        return self._kernel_name

    # -- dataflow links ---------------------------------------------------
    def reads_from(self, *producers: "Program") -> "Program":
        """Declare upstream producers (the paper's linked buffers, §10).

        Submitting this Program orders it after any in-flight run of the
        named producers, even when the shared-buffer conflict cannot be
        inferred (e.g. the producer swaps in a new buffer mid-flight)."""
        self._linked.extend(producers)
        return self

    @property
    def linked(self) -> tuple:
        return tuple(self._linked)

    @property
    def reads(self) -> tuple:
        """Declared read set: the host buffers this Program's kernel consumes."""
        return tuple(self._ins)

    @property
    def writes(self) -> tuple:
        """Declared write set: the host buffers this Program's kernel produces."""
        return tuple(self._outs)

    def donate(self, *in_indices: int) -> "Program":
        """Donate input buffers (by ``in_`` index) to the kernel.

        The jitted kernel may then alias the donated inputs' device buffers
        to its outputs (XLA buffer donation), so iterative Programs that
        carry large state (a KV cache ping-ponged between segments) update
        it in place on device instead of copying it every run.  Donated
        device inputs are *consumed*: the transfer cache hands them over and
        drops its entry (a retained entry would reference a deleted buffer),
        so each cached upload/handoff of a donated input serves exactly one
        run — the intended pattern is produce-once/consume-once chains like
        ``swap_buffers`` ping-pong, where the next run reads the *new*
        version anyway.  Only worthwhile when input and output shapes/dtypes
        match (XLA pairs them); host buffers are unaffected."""
        idx = sorted(set(int(i) for i in in_indices))
        for i in idx:
            if not 0 <= i < len(self._ins):
                raise IndexError(f"donate index {i} out of range for "
                                 f"{len(self._ins)} inputs")
        self._donated_ins = tuple(idx)
        return self

    @property
    def donated_ins(self) -> tuple:
        return self._donated_ins

    def args(self, *args) -> "Program":
        self._args = list(args)
        return self

    def arg(self, a) -> "Program":
        self._args.append(a)
        return self

    # -- geometry --------------------------------------------------------
    def global_work_items(self, gws: int) -> "Program":
        self.gws = gws
        return self

    def local_work_items(self, lws: int) -> "Program":
        self.lws = lws
        return self

    def work_items(self, gws: int, lws: int = 1) -> "Program":
        self.gws, self.lws = gws, lws
        return self

    # -- runtime-facing helpers (Tier-3) ----------------------------------
    def validate(self) -> list[str]:
        errs = []
        if self._kernel is None:
            errs.append("no kernel set")
        if self.gws is None:
            # Default: gws = leading dim of the first output / out_pattern.
            if self._outs:
                self.gws = int(Fraction(len(self._outs[0]), 1) / self._out_pattern)
            else:
                errs.append("no gws and no output buffer to infer it from")
        if self.gws is not None and self.lws and self.gws % self.lws:
            errs.append(f"gws {self.gws} not a multiple of lws {self.lws}")
        for i, b in enumerate(self._ins + self._outs):
            r = Fraction(len(b)) / self.gws
            if (r * self.lws).denominator != 1:
                errs.append(f"buffer {i}: length {len(b)} not compatible with gws/lws")
        return errs

    def buffer_ratio(self, buf) -> Fraction:
        return Fraction(len(buf), self.gws)

    def rows_of(self, buf, offset_wi: int, size_wi: int) -> tuple:
        """Element bounds ``(lo, hi)`` of ``buf`` for a work-item range."""
        r = self.buffer_ratio(buf)
        return int(r * offset_wi), int(r * (offset_wi + size_wi))

    def slice_inputs(self, offset_wi: int, size_wi: int) -> list:
        """Slice every input buffer for a work-item range."""
        out = []
        for b in self._ins:
            r = self.buffer_ratio(b)
            lo, hi = int(r * offset_wi), int(r * (offset_wi + size_wi))
            out.append(b[lo:hi])
        return out

    def write_outputs(self, offset_wi: int, size_wi: int, results: Sequence,
                      *, bump: bool = True, keep_resident: bool = False,
                      outs: Optional[Sequence] = None) -> int:
        """Write one package's results back to the host output buffers.

        ``bump=True`` (the default, tier-1 semantics) re-versions each buffer
        per call.  The runtime passes ``bump=False`` and assigns ONE fresh
        version per (run, buffer) instead (``RunHandle.version_for_write``),
        so every chunk a run produces shares a single coherent version — the
        precondition for serving still-on-device output slices to dependent
        runs from the transfer cache.

        ``keep_resident`` skips the ``Resident`` outputs: a run pinned to one
        group leaves them on the device (``Resident.keep``).

        ``outs``: the buffers to write (default: the Program's outputs now)
        — the runtime passes the ones the run was dispatched with
        (``RunHandle.outputs``), which an epilogue may since have swapped.

        Returns the bytes copied to host, bucket padding included: the whole
        result crosses before it is trimmed."""
        if not isinstance(results, (tuple, list)):
            results = (results,)
        outs = self._outs if outs is None else outs
        if len(results) != len(outs):
            raise ValueError(
                f"kernel returned {len(results)} outputs, program has {len(outs)}"
            )
        nbytes = 0
        for b, res in zip(outs, results):
            if keep_resident and isinstance(b, Resident):
                continue
            lo, hi = self.rows_of(b, offset_wi, size_wi)
            host = np.asarray(res)
            nbytes += host.nbytes
            b[lo:hi] = host[: hi - lo]  # trim bucket padding
            if bump:
                bump_version(b)  # output changed: stale any cached device copy
        return nbytes

    def swap_buffers(self, i_in: int, i_out: int) -> None:
        """Ping-pong one (input, output) buffer pair between iterations.

        The just-written output becomes the next iteration's input; the old
        input is copied so the kernel keeps a writable, contiguous output.
        The swapped-in buffer's version is NOT bumped: its contents are
        exactly what the producing run wrote (and already re-versioned), so
        still-on-device result slices stay servable from the transfer cache —
        iterative chains hand buffers off device-resident instead of
        re-uploading.  The fresh output copy is a new array the cache has
        never seen; bumping it is a defensive no-op.  A ``Resident`` pair
        swaps objects: the produced value (still on the device) becomes the
        input, and the old input, consumed or stale, is cleared."""
        new_in = self._outs[i_out]
        old_in = self._ins[i_in]
        if isinstance(old_in, Resident):
            old_in.clear()
            new_out = old_in
        else:
            new_out = np.ascontiguousarray(old_in)
        self._ins[i_in], self._outs[i_out] = new_in, new_out
        bump_version(new_out)

    def to_host(self) -> None:
        """Bring every ``Resident`` buffer to host: a run split across
        several groups reads and writes them there."""
        for b in self._ins + self._outs:
            if isinstance(b, Resident):
                b.to_host()

    def invalidate(self, buf=None) -> None:
        """Mark host buffers as externally modified (drops cached transfers).

        Call after mutating an input array in place outside the runtime; with
        no argument every buffer of this Program is invalidated."""
        targets = [buf] if buf is not None else self._ins + self._outs
        for b in targets:
            bump_version(b)

    @property
    def n_work_groups(self) -> int:
        return self.gws // self.lws

    @property
    def outputs(self) -> list:
        return self._outs

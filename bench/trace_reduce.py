"""Profiler trace -> device busy time, idle share, top device operations and
the longest idle gaps, each gap named by the host span that covers it.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``, read with ``jax.profiler.ProfileData``.
Device planes are those whose name starts with ``/device:TPU``; their
``XLA Ops`` line holds one event per operation that ran.  The traced window
is the span between two ``TraceAnnotation`` anchors the harness emits at
known host-clock times, which also line the harness's spans up with the
trace's clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU"
OPS_LINE = "XLA Ops"
# Where each platform's trace puts its operations: (plane prefix, line).
# The CPU entry serves the tests; the benchmark itself runs on the chip.
OPS = {"tpu": (DEVICE_PLANE, OPS_LINE),
       "cpu": ("/host:CPU", "tf_XLAPjRtCpuClient")}
START, END = "bench_window_start", "bench_window_end"
TOP = 10
MIN_GAP_NS = 1000  # shorter idle stretches are op boundaries, not gaps


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_trace(d: str) -> str:
    found = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return found[-1]


def device_ops(pd, plane_prefix: str = DEVICE_PLANE,
               line_name: str = OPS_LINE) -> Dict[str, List[Tuple]]:
    """plane name -> [(start_ns, end_ns, op name)] of every operation."""
    out: Dict[str, List[Tuple]] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name.startswith(line_name):
                out.setdefault(plane.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                    for e in line.events)
    return out


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = bf16[..]
    fusion(..)`` -> ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def annotation(pd, name: str) -> Optional[float]:
    """Start (ns) of the first host event called ``name``."""
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns
    return None


def union(intervals: Iterable[Tuple], lo: float, hi: float) -> List[Tuple]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: List[list] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def gaps(busy: Sequence[Tuple], lo: float, hi: float) -> List[Tuple]:
    out, t = [], lo
    for s, e in busy:
        if s - t >= MIN_GAP_NS:
            out.append((t, s))
        t = max(t, e)
    if hi - t >= MIN_GAP_NS:
        out.append((t, hi))
    return out


def name_gap(g: Tuple, spans: Sequence[Tuple]) -> str:
    """The shortest span (start_ns, end_ns, name) covering the gap's
    middle: what the host was doing while the device waited."""
    mid = (g[0] + g[1]) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no span"


def reduce(pd, lo_ns: float, hi_ns: float, spans: Sequence[Tuple] = (),
           plane_prefix: str = DEVICE_PLANE, line_name: str = OPS_LINE,
           top: int = TOP, names: Optional[Sequence[str]] = None) -> dict:
    """Busy seconds (union of operations, averaged over the device planes,
    and each plane's own under ``busy_s_per_plane``), the window's length,
    the idle share, the ``top`` operations by total time and the ``top``
    longest idle gaps, over [lo_ns, hi_ns).  ``names``: the device planes
    to count (the chips a run used), each whether or not an operation ran
    on it (a chip that ran nothing has no plane in the trace); by default
    every plane with operations."""
    planes = device_ops(pd, plane_prefix, line_name)
    if not planes:
        raise ValueError(f"no {line_name!r} line on a {plane_prefix!r} plane")
    if names is not None:
        if not set(planes) & set(names):
            raise ValueError(f"no operation on any of {list(names)}")
        planes = {n: planes.get(n, []) for n in names}
    window = (hi_ns - lo_ns) / 1e9
    per_plane, per_op, all_gaps = {}, defaultdict(float), []
    for plane, ops in planes.items():
        busy = union(ops, lo_ns, hi_ns)
        per_plane[plane] = sum(e - s for s, e in busy) / 1e9
        for s, e, name in ops:
            d = min(e, hi_ns) - max(s, lo_ns)
            if d > 0:
                per_op[name] += d / 1e9
        all_gaps.extend(gaps(busy, lo_ns, hi_ns))
    busy_s = sum(per_plane.values()) / len(planes)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_share": 1.0 - busy_s / window,
        "busy_s_per_plane": per_plane,
        "device_ops": sorted(([n, t] for n, t in per_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in all_gaps[:top]],
    }


def host_spans(events: Sequence[tuple], to_ns) -> List[Tuple]:
    """The Tracer's complete spans and begin/end pairs as (start_ns,
    end_ns, name) on the trace's clock (``to_ns`` maps its clock)."""
    out, open_ = [], {}
    for _seq, t0, t1, ph, name, track, _aid, _args in events:
        if ph == "X":
            out.append((to_ns(t0), to_ns(t1), name))
        elif ph == "B":
            open_.setdefault((track, name), []).append(t0)
        elif ph == "E" and open_.get((track, name)):
            out.append((to_ns(open_[(track, name)].pop()), to_ns(t0), name))
    return out


def reduce_dir(d: str, anchors: dict, events: Sequence[tuple] = (),
               platform: str = "tpu",
               device_ids: Optional[Sequence[int]] = None) -> dict:
    """Reduce the trace under ``d`` over the window between the two anchor
    annotations, whose host-clock times are ``anchors``; ``events`` are the
    Tracer's events on that host clock.  ``device_ids``: the TPU chips the
    run used, each counted (idle or not) in the busy time and idle share."""
    pd = load(find_trace(d))
    lo, hi = annotation(pd, START), annotation(pd, END)
    if lo is None or hi is None:
        raise ValueError("the trace lacks the window's anchor annotations")
    t_lo = anchors[START]

    def to_ns(t: float) -> float:
        return lo + (t - t_lo) * 1e9

    names = None
    if platform == "tpu" and device_ids is not None:
        names = [f"{DEVICE_PLANE}:{i}" for i in device_ids]
    return reduce(pd, lo, hi, host_spans(events, to_ns), *OPS[platform],
                  names=names)

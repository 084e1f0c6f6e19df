"""The harness: finds a cell's files by name, builds the system under test
from them, drives its traffic, and hands what it saw to the metric readers.

Nothing in here names a cell, a configuration, an architecture or a metric:
``BENCHMARK.json`` names them, ``configs/<file>``, ``traffic/<traffic>.json``,
``archs/<program.bench_arch>.py`` and ``metrics/<metric>.py`` hold them.  A
cell's ``chips`` sets how many server members it runs: one per chip.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from bench import archs

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WARM_WAIT_MS = 200.0  # warm-up waves: long enough to gather a whole wave
POLL_S = 0.002  # closed loop: how often the clients look for completions


def log(msg: str) -> None:
    print(msg, flush=True)


def join_program_threads(before, timeout: float = 60.0) -> None:
    """Wait for the batcher and device-worker threads (named ``enginecl-...``
    by the program) that a closed server started after ``before`` (a
    ``threading.enumerate()`` snapshot) to end, so nothing of it stays
    alive."""
    for t in threading.enumerate():
        if t.name.startswith("enginecl") and t not in before:
            t.join(timeout)


# ----------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    arch: object  # the configuration's archs/<program.bench_arch>.py


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic and metric entries, read from their files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    bench = os.path.join(root, spec["paths"][0])
    with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(workload, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)],
                archs.load(config, os.path.join(bench, "archs")))


def reader(name: str, bench: str = BENCH) -> Callable:
    """``read(ctx)`` of ``<bench>/metrics/<name>.py``."""
    path = os.path.join(bench, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------- the system under test
class CompileLog:
    """Times of every backend compilation (persistent-cache loads too)."""

    def __init__(self) -> None:
        self.times: List[float] = []
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


@dataclasses.dataclass
class Sent:
    """One request as the client saw it (monotonic clock)."""
    prompt: np.ndarray
    gen: int
    bucket: int
    scheduled: float
    sent: float
    in_window: bool
    handle: object = None
    first: Optional[float] = None
    done: Optional[float] = None
    status: str = "missing"  # ok | failed | rejected | missing
    tokens: Optional[np.ndarray] = None
    migrated: bool = False  # a migration moved it from one chip to another

    def settle(self, moved=()) -> None:
        """Copy the handle's outcome, and whether it is among the ``moved``
        handles; drop the handle."""
        from repro.serve import AdmissionError

        h = self.handle
        if h is None:
            return
        self.migrated = h in moved
        if h.done():
            try:
                self.tokens = np.asarray(h.result(0))
                self.status = "ok"
            except AdmissionError:
                self.status = "rejected"
            except Exception:  # noqa: BLE001 — any serving error is a failure
                self.status = "failed"
            self.done = h.t_done
        self.first = h.t_first_token
        self.handle = None


def server_options(srv: dict) -> dict:
    """The ``InferenceServer`` keywords that a traffic file's ``server``
    block sets for several members (``scheduler``, ``migration``,
    ``group_batches``), each policy a fresh object; none where the block
    leaves them out, as a one-member cell's does."""
    from repro.core import HGuided
    from repro.serve.multigroup import RateBalancer

    named = {"scheduler": {"hguided": HGuided},
             "migration": {"rate": RateBalancer}}
    out = {}
    for key, table in named.items():
        if key in srv:
            if srv[key] not in table:
                raise ValueError(f"unknown {key} {srv[key]!r}; known: "
                                 f"{sorted(table)}")
            out[key] = table[srv[key]]()
    if "group_batches" in srv:
        out["group_batches"] = bool(srv["group_batches"])
    return out


class Watched:
    """A migration policy, unchanged, with what it sees written down: the
    requests each member has held (``held``: name -> handles) and those it
    moved (``moved``), so the check can compare moved requests."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.held: dict = {}
        self.moved: set = set()

    @property
    def last_info(self) -> dict:
        return getattr(self.inner, "last_info", {})

    def plan(self, members, weights):
        for nm, m in members.items():
            self.held.setdefault(nm, set()).update(
                r.handle for r in m.slots if r is not None)
        moves, hold = self.inner.plan(members, weights)
        for src, slot, _dst in moves:
            self.moved.add(members[src].slots[slot].handle)
        return moves, hold


def _ring():
    """A migration policy for warm-up: once every member is at a segment
    boundary, each member hands one slot to the next, round the ring, so
    every chip both sends and takes a slot (the row read-back and scatter
    that a balancer's migration runs)."""
    from repro.serve.multigroup import MigrationPolicy

    class Ring(MigrationPolicy):
        def plan(self, members, weights):
            names = list(members)
            if not all(members[nm].at_boundary() for nm in names):
                return [], {nm for nm in names if members[nm].at_boundary()}
            moves = []
            for nm, nxt in zip(names, names[1:] + names[:1]):
                src = members[nm]
                busy = [k for k, r in enumerate(src.slots) if r is not None]
                if busy and members[nxt].can_accept_migration(src, busy[0]):
                    moves.append((nm, busy[0], nxt))
            return moves, set()

    return Ring()


class Session:
    """One cell's model and server pieces, one server member per chip, for
    one seed."""

    def __init__(self, cell: Cell, seed: int, devices,
                 cache_dtype: str = "") -> None:
        import jax

        from repro.core import DeviceGroup
        from repro.models import get_model
        from repro.models.params import abstract
        from repro.serve.batcher import ModelKernels

        from bench import weights as W

        self.cell, self.seed, self.devices = cell, seed, list(devices)
        c, srv = cell.config, cell.traffic["server"]
        self.cfg = cell.arch.program_config(c, cache_dtype)
        self.api = get_model(self.cfg)
        self.params = jax.block_until_ready(
            W.make_params(c, seed, c["torch_dtype"], cell.arch.layout))
        want = jax.tree_util.tree_map(
            lambda s: (s.shape, str(s.dtype)),
            abstract(self.api.param_spec(self.cfg, 1), self.cfg.compute_dtype))
        got = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                     self.params)
        if want != got:
            raise ValueError(f"weights do not match the program's layout:\n"
                             f"{got}\nwant\n{want}")
        # The weights live on the first chip; each other member copies them
        # to its own once (DeviceGroup._placed).
        self.groups = [DeviceGroup(f"chip{i}", [d])
                       for i, d in enumerate(self.devices)]
        self.kernels = ModelKernels(self.cfg, self.api, self.params)
        self.buckets = tuple(srv["buckets"])
        self.slots = int(srv["slots_per_bucket"])  # per member
        self.cap = int(srv["max_new_cap"])
        if srv.get("kv", "contiguous") != "contiguous":
            raise ValueError(f"unknown KV layout {srv['kv']!r}")
        server_options(srv)  # an unknown policy name fails here, not later
        # Several members: warm-up and window share one admission model, so
        # the window's balancer starts from the decode rate that warm-up
        # observed on every chip, as a server that has run for a while
        # does, not from a cold start that favours the first chip to finish
        # a segment.
        self.admission = None
        if len(self.groups) > 1:
            from repro.serve.admission import DeadlineAdmission

            self.admission = DeadlineAdmission()

    def server(self, **kw):
        from repro.serve import InferenceServer

        kw = {**server_options(self.cell.traffic["server"]), **kw}
        if self.admission is not None:
            kw.setdefault("admission", self.admission)
        return InferenceServer(self.cfg, self.api, self.params,
                               groups=self.groups, kernels=self.kernels,
                               buckets=self.buckets,
                               max_batch=self.slots * len(self.groups),
                               max_new_cap=self.cap, **kw)

    def warm_up(self) -> None:
        """Run every program shape the traffic can reach once, on every
        member: for each bucket, a prefill wave of each size 1..slots on each
        member (a fresh group each, gathered by a long batching wait and
        placed evenly; one token, so no segment runs), then one wave that
        decodes a segment.  With several members, one more wave of a request
        each, long enough for two segments, which the ring policy migrates
        at both boundaries: before any segment and after one (a member that
        has run a segment patches its device copy of the moved rows)."""
        from repro.core import Static

        rng = np.random.default_rng([self.seed, 4])
        vocab = self.cell.config["vocab_size"]
        m = len(self.groups)
        kw = {"max_wait_ms": WARM_WAIT_MS}
        if m > 1:
            kw.update(scheduler=Static(), migration=_ring())
        before = set(threading.enumerate())
        with self.server(**kw) as srv:
            waves = [(n, 1) for n in range(1, self.slots + 1)] + [
                (self.slots, 2)] + ([(1, 2 + srv.seg_len)] if m > 1 else [])
            for b in self.buckets:
                for n, gen in waves:
                    t = time.monotonic()
                    hs = [srv.submit(rng.integers(0, vocab, b, dtype=np.int32),
                                     gen) for _ in range(n * m)]
                    for h in hs:
                        h.result(timeout=1200)
                    log(f"warm-up wave: bucket {b}, {n * m} requests, {gen} "
                        f"tokens: {time.monotonic() - t:.3f} s")
            if m > 1:
                log(f"warm-up slot migrations: "
                    f"{srv.stats()['slot_migrations']}")
        join_program_threads(before)
        gc.collect()
        for g in self.groups:
            g.clear_cache()

    def close(self) -> None:
        self.params = self.kernels = self.groups = None
        gc.collect()


def bucket_of(buckets, n: int) -> int:
    return min(b for b in buckets if b >= n)


@dataclasses.dataclass
class Load:
    requests: List[Sent]
    window: tuple          # (t0, t1) monotonic
    window_perf: tuple     # the same instants on the perf_counter clock
    t_end: float           # drain end, monotonic
    stats0: dict
    stats1: dict
    lateness: List[float]
    compiles: int = 0
    spans: list = dataclasses.field(default_factory=list)
    device: Optional[dict] = None


def _stats(srv) -> dict:
    s = srv.stats()
    return {k: s[k] for k in ("segments", "occupancy_mean",
                              "slot_migrations")}


def members_seen(sess: Session, watch: Optional[Watched]) -> str:
    """Each member's requests held so far and the decode rate per bucket
    that its placement weight is drawn from."""
    model = sess.admission.model
    return "; ".join(
        f"{g.name} held {len(watch.held.get(g.name, ()))} requests, rates "
        + ", ".join(f"{b}: {model.rate(b, g.name)}" for b in sess.buckets)
        for g in sess.groups)


class Profiler:
    """One ``jax.profiler`` trace of ``span`` seconds starting at ``at``
    (monotonic), run on a thread of its own; anchors its window with
    ``TraceAnnotation`` events whose perf_counter times it records.
    ``device_ids``: the chips whose busy time the reduction averages."""

    def __init__(self, at: float, span: float, platform: str,
                 device_ids=None) -> None:
        self.at, self.span, self.platform = at, span, platform
        self.device_ids = device_ids
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.anchors: dict = {}
        self.error: Optional[BaseException] = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-profiler")
        self.thread.start()

    def _mark(self, name: str) -> None:
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            pass
        self.anchors[name] = t

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self.at - time.monotonic()))
            jax.profiler.start_trace(self.dir)
            self._mark("bench_window_start")
            time.sleep(self.span)
            self._mark("bench_window_end")
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — reported by reduce()
            self.error = e

    def reduce(self, spans) -> dict:
        from bench import trace_reduce

        self.thread.join()
        try:
            if self.error is not None:
                raise RuntimeError(f"profiler failed: {self.error!r}")
            return trace_reduce.reduce_dir(self.dir, self.anchors, spans,
                                           self.platform, self.device_ids)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def drive(sess: Session, plan, seconds: float, *, trace: bool = False,
          compile_log: Optional[CompileLog] = None) -> Load:
    """Serve ``plan`` on a fresh server: the ramp, then ``seconds`` of
    window, then drain.  Open loop: sends at the plan's times, then waits
    for every request.  Closed loop: the clients stop sending at the
    window's end; queued requests are dropped and boarded ones finish."""
    from repro.core import Tracer, set_tracer, tracer

    traffic = sess.cell.traffic
    ramp = float(traffic["ramp_s"])
    drain_s = float(traffic["drain_s"])
    old_tracer = tracer()
    tr = set_tracer(Tracer(1 << 21)) if trace else None
    sent: List[Sent] = []
    lateness: List[float] = []
    before = set(threading.enumerate())
    opts, watch = {}, None
    mig = server_options(traffic["server"]).get("migration")
    if mig is not None:
        watch = opts["migration"] = Watched(mig)
    moved = watch.moved if watch is not None else ()
    srv = sess.server(**opts)
    try:
        t_start = time.monotonic()
        w0, w1 = t_start + ramp, t_start + ramp + seconds
        prof = None
        if trace:
            span = float(traffic["profile_s"])
            prof = Profiler(w0 + max(0.0, (seconds - span) / 2), span,
                            sess.devices[0].platform,
                            [d.id for d in sess.devices])
        stats0 = window_perf0 = None

        def send(req, scheduled: float) -> Sent:
            now = time.monotonic()
            s = Sent(req.prompt, req.gen,
                     bucket_of(sess.buckets, len(req.prompt)), scheduled, now,
                     w0 <= now < w1)
            s.handle = srv.submit(req.prompt, req.gen)
            lateness.append(now - scheduled)
            sent.append(s)
            return s

        def open_window() -> None:
            nonlocal stats0, window_perf0
            if stats0 is None and time.monotonic() >= w0:
                stats0 = _stats(srv)
                window_perf0 = time.perf_counter()
                if watch is not None:
                    log(f"members at window open: {members_seen(sess, watch)}")

        def wait_until(t: float) -> None:
            while True:
                open_window()
                now = time.monotonic()
                if now >= t:
                    return
                nxt = t if (stats0 is not None or now >= w0) else min(t, w0)
                time.sleep(max(0.0, nxt - now))

        if plan.loop == "open":
            for req in plan.requests:
                wait_until(t_start + req.at)
                send(req, t_start + req.at)
            wait_until(w1)
        else:
            pool = iter(plan.requests)

            def next_request():
                try:
                    return next(pool)
                except StopIteration:
                    raise RuntimeError("the traffic file's pool ran out: "
                                       "raise its 'pool'") from None

            live = [send(next_request(), t_start)
                    for _ in range(plan.clients)]
            while time.monotonic() < w1:
                open_window()
                for i, s in enumerate(live):
                    if s.handle is not None and s.handle.done():
                        done_at = s.handle.t_done
                        s.settle(moved)
                        if time.monotonic() < w1:
                            live[i] = send(next_request(), done_at)
                time.sleep(POLL_S)
        open_window()
        stats1, window_perf1 = _stats(srv), time.perf_counter()
        t_win_end = time.monotonic()
        if watch is not None:
            log(f"members at window close: {members_seen(sess, watch)}")
        if plan.loop == "open":
            for s in sent:
                if s.handle is not None:
                    s.handle.wait(max(0.0, t_win_end + drain_s
                                      - time.monotonic()))
        srv.close(drain=plan.loop == "open", timeout=drain_s)
        srv = None
        join_program_threads(before)
        t_end = time.monotonic()
        for s in sent:
            s.settle(moved)
        load = Load(sent, (w0, w1), (window_perf0, window_perf1), t_end,
                    stats0, stats1, lateness)
        if compile_log is not None:
            load.compiles = compile_log.between(w0, w1)
        if trace:
            load.spans = tr.events()
            load.device = prof.reduce(load.spans)
        return load
    finally:
        if srv is not None:
            srv.close(drain=False, timeout=drain_s)
            join_program_threads(before)
        if trace:
            set_tracer(old_tracer)


# ------------------------------------------------------------- correctness
def sample(load: Load, seed: int, k: int) -> List[Sent]:
    """``k`` finished requests drawn from the seed, the longest always in,
    and up to half of them among those that a migration moved between
    chips (so that a fault in the exchange shows)."""
    done = [s for s in load.requests if s.status == "ok"
            and s.done is not None and s.done >= load.window[0]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (done[i].gen, -i))
    rest = [i for i in range(len(done)) if i != longest]
    order = list(np.random.default_rng([seed, 3]).permutation(rest))
    pick = [longest] + [i for i in order if done[i].migrated][: k // 2]
    pick += [i for i in order if i not in pick][: max(0, k - len(pick))]
    return [done[i] for i in pick]


def compare(cell: Cell, seed: int, chosen: List[Sent],
            control: bool = False) -> dict:
    """Run the reference over each chosen request's padded prompt and
    served tokens; the widest and the mean gap by which a served token's
    reference logit lies below the reference's best (and, with
    ``control``, the same for the float8 pass's first choices at the same
    positions)."""
    srv = cell.traffic["server"]
    t = max(srv["buckets"]) + int(srv["max_new_cap"])
    tokens = np.zeros((len(chosen), t), np.int32)
    targets = np.zeros_like(tokens)
    mask = np.zeros(tokens.shape, bool)
    for i, s in enumerate(chosen):
        n, b = len(s.tokens), s.bucket
        tokens[i, : len(s.prompt)] = s.prompt  # right-padded with 0 to b
        tokens[i, b: b + n - 1] = s.tokens[:-1]
        targets[i, b - 1: b - 1 + n] = s.tokens
        mask[i, b - 1: b - 1 + n] = True
    out = cell.arch.score(cell.config, seed, tokens, targets, control)
    gap = out["gap"][mask]
    res = {"max_gap": float(gap.max()), "mean_gap": float(gap.mean()),
           "served_tokens": int(mask.sum()),
           "first_token_max_gap": float(max(
               out["gap"][i, s.bucket - 1] for i, s in enumerate(chosen)))}
    if control:
        cgap = out["control_gap"][mask]
        res["control_max_gap"] = float(cgap.max())
        res["control_mean_gap"] = float(cgap.mean())
    return res


# ------------------------------------------------------------------ the run
def context(cell: Cell, load: Load, setup_s: float, device_kind: str):
    """What the metric readers see."""
    return Context(
        cell=cell, requests=load.requests, window=load.window,
        window_perf=load.window_perf, t_end=load.t_end, stats0=load.stats0,
        stats1=load.stats1, spans=load.spans, device=load.device,
        setup_s=setup_s, loop=cell.traffic["loop"],
        slots=int(cell.traffic["server"]["slots_per_bucket"]),
        device_kind=device_kind)


@dataclasses.dataclass
class Context:
    cell: Optional[Cell] = None
    requests: list = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)
    window_perf: tuple = (0.0, 0.0)
    t_end: float = 0.0
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    device: Optional[dict] = None
    setup_s: float = 0.0
    loop: str = "open"
    slots: int = 1
    device_kind: str = ""


def read_metrics(entries: List[dict], ctx, bench: str = BENCH) -> dict:
    """Each entry's reader on ``ctx``; a reader that finds nothing to read
    returns None and its metric is left out."""
    out = {}
    for m in entries:
        v = reader(m["name"], bench)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_process: float, *, compile_log: Optional[CompileLog] = None,
        control: str = "", checks_out=sys.stderr) -> dict:
    """One run of a cell on ``devices`` (one server member each; a single
    device is one member): set-up, window, drain, the check; returns the
    result line (a dict)."""
    import jax

    from bench import loadgen

    devices = [devices] if isinstance(devices, jax.Device) else list(devices)
    device = devices[0]
    c, traffic = cell.config, cell.traffic
    t0 = time.monotonic()
    sess = Session(cell, seed, devices,
                   "float8_e4m3fn" if control == "program_fp8_cache" else "")
    t1 = time.monotonic()
    sess.warm_up()
    plan = loadgen.make_plan(traffic, seed, seconds, c["vocab_size"])
    t_ready = time.monotonic()
    log(f"set-up phases: start to weights {t0 - t_process:.3f} s, weights "
        f"{t1 - t0:.3f} s, warm-up {t_ready - t1:.3f} s")
    load = drive(sess, plan, seconds, trace=trace, compile_log=compile_log)
    setup_s = load.window[0] - t_process
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    peak = max(peaks)
    sess.close()
    del sess
    gc.collect()
    freed = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if any(f and f > 1 << 30 for f in freed):
        live = sorted(jax.live_arrays(), key=lambda a: -a.nbytes)[:8]
        log("still on the devices after freeing the program: "
            + ", ".join(f"{a.shape} {a.dtype}" for a in live))

    sent_w = [s for s in load.requests if s.in_window]
    status = {k: sum(s.status == k for s in load.requests)
              for k in ("ok", "failed", "rejected", "missing")}
    late = np.asarray(load.lateness) * 1e3
    log(f"set-up {setup_s:.3f} s (weights, warm-up and ramp; ready after "
        f"{t_ready - t_process:.3f} s)")
    log(f"compilations inside the window: {load.compiles}")
    log(f"slot migrations inside the window: "
        f"{load.stats1['slot_migrations'] - load.stats0['slot_migrations']}"
        f"; requests moved in the run {sum(s.migrated for s in load.requests)}")
    log(f"generator lateness: p50 {np.median(late):.3f} ms, max "
        f"{late.max():.3f} ms over {len(late)} sends")
    log(f"requests: sent {len(load.requests)} ({len(sent_w)} in the window), "
        f"completed {status['ok']}, rejected {status['rejected']}, failed "
        f"{status['failed']}, unfinished {status['missing']}")
    log(f"peak_bytes_in_use {peak} (per device {peaks}); bytes_in_use after "
        f"freeing the program {freed}")

    # The closed loop's close drops queued requests: rejected by us, not by
    # the system.  Everything else that did not finish is a failure.
    closed_loop = traffic["loop"] == "closed"
    failed = sum(s.status in ("failed", "missing")
                 or (s.status == "rejected" and not closed_loop)
                 for s in load.requests)
    chosen = sample(load, seed, int(traffic["check"]["requests"]))
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    ok = failed == 0 and bool(chosen)
    if chosen:
        cmp = compare(cell, seed, chosen, bool(control))
        for name, limit in traffic["check"]["limits"].items():
            v = cmp[("control_" if control == "reference_fp8" else "") + name]
            checks[name] = {"value": v, "limit": limit}
            ok = ok and bool(np.isfinite(v)) and v <= limit
        log(f"check: {cmp['served_tokens']} served tokens of {len(chosen)} "
            f"requests ({sum(s.migrated for s in chosen)} moved between "
            f"chips) against the float32 reference: widest gap "
            f"{cmp['max_gap']!r}, mean gap {cmp['mean_gap']!r} (first tokens "
            f"widest {cmp['first_token_max_gap']!r})"
            + (f"; float8 reference: widest {cmp['control_max_gap']!r}, "
               f"mean {cmp['control_mean_gap']!r}" if control else ""))
    else:
        log("check: no finished request to compare")

    for s in load.requests:
        if s.in_window or (s.done is not None and s.done >= load.window[0]):
            ttft = None if s.first is None else (s.first - s.scheduled) * 1e3
            tpot = (None if s.status != "ok" or s.gen < 2
                    else (s.done - s.first) / (s.gen - 1) * 1e3)
            log(f"request: sent {s.sent - load.window[0]:.3f} s, bucket "
                f"{s.bucket}, prompt {len(s.prompt)}, gen {s.gen}, {s.status}"
                f", ttft {ttft} ms, tpot {tpot} ms")
    ctx = context(cell, load, setup_s, device.device_kind)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak,
           "memory_peak_bytes_per_device": peaks}
    line = {"correct": bool(ok), "attempted": len(sent_w), "failed": failed,
            "metrics": metrics, "device": dev}
    if trace and load.device is not None:
        dev["busy_s"] = load.device["busy_s"]
        dev["window_s"] = load.device["window_s"]
        dev["busy_s_per_device"] = load.device["busy_s_per_plane"]
        line["breakdown"] = {"device_ops": load.device["device_ops"],
                             "idle_gaps": load.device["idle_gaps"]}
    line["checks"] = checks
    for name, chk in checks.items():
        print(f"{name} {chk['value']!r} limit {chk['limit']!r}",
              file=checks_out, flush=True)
    return line

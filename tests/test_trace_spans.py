"""The runtime's and the batcher's spans and byte counters: bytes copied
each way per package, where each run's spans sit on the group and batcher
tracks, the ``TraceAnnotation`` mirror of spans measured in place, and the
disabled tracer's cost (no events, no annotations, counters still kept)."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Dynamic, EngineCL, Program, Static
from repro.core import trace as trace_mod
from repro.core.trace import Tracer, set_tracer, tracer
from repro.models import get_model
from repro.models import params as P
from repro.serve import InferenceServer

PLEN, GEN, SLOTS, SEG = 8, 7, 4, 2
TOK_POS = 2 * SLOTS * np.dtype(np.int32).itemsize  # a segment's tok + pos
# Spans measured in place, mirrored as TraceAnnotations, that a served wave
# emits.  (``dep_wait`` is measured in place too, but runs chained on one
# group are dispatched without waiting: a wave on one group has none.)
ANNOTATED = {"upload", "write_back", "merge", "harvest", "idle",
             "form_group"}


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    yield
    set_tracer(Tracer(enabled=False))


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, api, params


def serve_one_wave(model, group):
    """SLOTS requests boarded as one wave (the batching wait outlasts the
    submits), each decoding GEN tokens: one join, then segments with no
    join between them.  Returns the segment Program's input bytes: the
    token and position buffers plus the cache."""
    cfg, api, params = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, PLEN).astype(np.int32)
               for _ in range(SLOTS)]
    with InferenceServer(cfg, api, params, groups=[group], scheduler=Static(),
                         buckets=(PLEN,), max_batch=SLOTS, seg_len=SEG,
                         max_new_cap=GEN, max_wait_ms=5000.0) as srv:
        for h in [srv.submit(p, GEN) for p in prompts]:
            h.result(timeout=300)
        leaves = srv.kernels.leaf_buffers(SLOTS, srv._max_seq(PLEN),
                                          resident=True)
    return TOK_POS + sum(b.nbytes for b in leaves)


def spans(events, name, track=None):
    return [e for e in events if e[3] == "X" and e[4] == name
            and (track is None or e[5] == track)]


@pytest.fixture(scope="module")
def traced_wave(model):
    set_tracer(Tracer(capacity=1 << 16, enabled=True))
    try:
        cache_bytes = serve_one_wave(model, DeviceGroup("spans"))
        return tracer().events(), cache_bytes
    finally:
        set_tracer(Tracer(enabled=False))


# ------------------------------------------------------------ byte counts
def test_write_back_bytes_are_each_packages_outputs_padding_included():
    """Per package, ``write_back`` ``bytes`` is the summed ``nbytes`` of the
    output arrays the kernel returned (bucket padding included), and the
    group's ``d2h_bytes`` counter is their total."""
    tr = set_tracer(Tracer(enabled=True))
    n = 10
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    y = np.zeros((n, 3), np.float32)
    z = np.zeros((n,), np.int32)

    def kern(offset, a):
        return a * 2.0, a[:, 0].astype(jnp.int32)

    p = Program().in_(x).out(y).out(z).kernel(kern).work_items(n, 1)
    g = DeviceGroup("wb")
    copied = {}
    execute = g.execute_chunk

    def spy(program, off, size, **kw):
        res = execute(program, off, size, **kw)
        copied[off] = sum(r.nbytes for r in res)
        return res

    g.execute_chunk = spy
    eng = EngineCL().use(g).scheduler(Dynamic(2))  # packages of 5
    eng.program(p).run()
    assert not eng.has_errors(), eng.get_errors()
    np.testing.assert_array_equal(y, 2.0 * x)
    wbs = {e[7]["offset"]: e[7]["bytes"] for e in spans(tr.events(),
                                                         "write_back")}
    assert wbs == copied and len(wbs) >= 2
    # A package whose size is not a power of two copies its padding too.
    assert any(b > s * (3 * 4 + 4) for s, b in
               ((e[7]["size"], e[7]["bytes"])
                for e in spans(tr.events(), "write_back")))
    st = g.transfer_stats()
    assert st["d2h_bytes"] == sum(copied.values())
    ups = spans(tr.events(), "upload", "group/wb")
    assert st["h2d_bytes"] == sum(e[7]["bytes"] for e in ups) > 0


def test_first_segment_after_a_join_uploads_every_cache_leaf(traced_wave):
    """After a join the first segment uploads what the join rewrote on
    host — the token and position buffers — and reads every cache leaf on
    the device, where the join wrote the joiners' rows."""
    events, cache_bytes = traced_wave
    segs = [e for e in spans(events, "upload", "group/spans")
            if e[7]["kernel"].startswith("decode_seg")]
    assert len(segs) >= 3
    first = segs[0][7]
    assert first["bytes"] == TOK_POS
    assert first["resident_bytes"] == cache_bytes - TOK_POS


def test_segment_with_no_join_uploads_nothing(traced_wave):
    events, cache_bytes = traced_wave
    segs = [e for e in spans(events, "upload", "group/spans")
            if e[7]["kernel"].startswith("decode_seg")]
    for e in segs[1:]:
        assert e[7]["bytes"] == 0
        assert e[7]["resident_bytes"] == cache_bytes


def test_every_segment_write_back_keeps_the_cache(traced_wave):
    """Each segment's ``write_back`` copies its tokens, token and position
    buffers to host and keeps the whole cache on the device."""
    events, cache_bytes = traced_wave
    segs = spans(events, "segment", "batcher")
    wbs = [e for e in spans(events, "write_back", "group/spans")
           if any(s[1] <= e[1] and e[2] <= s[2] for s in segs)]
    assert len(wbs) == len(segs) >= 3
    for e in wbs:
        assert e[7]["kept_bytes"] == cache_bytes - TOK_POS
        assert e[7]["bytes"] == SLOTS * (SEG + 2) * 4


# --------------------------------------------------------- span placement
def test_queue_wait_precedes_segment_and_run_spans_nest(traced_wave):
    """Each segment run: its ``queue_wait`` (submit → picked up) ends at or
    before the ``segment`` span's start, ``queued_s`` is submit → start, and
    the group track's dispatch, upload, execute and write_back of the run
    lie inside the segment span."""
    events, _ = traced_wave
    segs = sorted(spans(events, "segment", "batcher"), key=lambda e: e[1])
    waits = sorted((e for e in spans(events, "queue_wait", "group/spans")
                    if e[7]["kernel"].startswith("decode_seg")),
                   key=lambda e: e[1])
    assert len(segs) == len(waits) >= 3
    for seg, qw in zip(segs, waits):
        assert qw[2] <= seg[1]
        assert seg[7]["queued_s"] == pytest.approx(seg[1] - qw[1], abs=1e-9)
    runs = segs + spans(events, "prefill_wave", "batcher")
    inner = [e for n in ("dispatch", "upload", "execute", "write_back")
             for e in spans(events, n, "group/spans")]
    assert inner
    for e in inner:
        assert any(r[1] <= e[1] and e[2] <= r[2] for r in runs), e
    in_segments = [e for e in inner
                   if any(s[1] <= e[1] and e[2] <= s[2] for s in segs)]
    assert {e[4] for e in in_segments} == {"dispatch", "upload", "execute",
                                           "write_back"}


def test_batcher_spans_and_gone_instants(traced_wave):
    events, _ = traced_wave
    names = {e[4] for e in events}
    assert {"merge", "harvest", "idle", "queue_wait", "form_group"} <= names
    assert not {"submit", "transfers", "dep_wait"} & names
    merges = spans(events, "merge", "batcher")
    assert [e[7]["joined"] for e in merges] == [SLOTS]


# -------------------------------------------------------- TraceAnnotation
class _Counting:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that records names."""

    names: list = []

    def __init__(self, name, **_):
        _Counting.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_tracer_off_no_events_no_annotations_counters_kept(model,
                                                           monkeypatch):
    monkeypatch.setattr(trace_mod, "TraceAnnotation", _Counting)
    _Counting.names = []
    set_tracer(Tracer(enabled=False))
    g = DeviceGroup("off")
    cache_bytes = serve_one_wave(model, g)
    assert len(tracer()) == 0
    assert _Counting.names == []
    st = g.transfer_stats()
    # The cache stays on the device: what crosses either way is the
    # prompts, tokens and positions, below one copy of the cache.
    assert 0 < st["h2d_bytes"] < cache_bytes
    assert st["resident_bytes"] >= cache_bytes
    assert 0 < st["d2h_bytes"] < cache_bytes
    assert st["kept_bytes"] >= cache_bytes


def test_tracer_on_annotates_every_span_measured_in_place(model,
                                                          monkeypatch):
    monkeypatch.setattr(trace_mod, "TraceAnnotation", _Counting)
    _Counting.names = []
    tr = set_tracer(Tracer(capacity=1 << 16, enabled=True))
    serve_one_wave(model, DeviceGroup("on"))
    assert set(_Counting.names) == ANNOTATED
    # One annotation per span measured in place, none for the others.
    measured = [e[4] for e in tr.events() if e[4] in ANNOTATED]
    assert sorted(_Counting.names) == sorted(measured)


@pytest.mark.parametrize("enabled", [True, False])
def test_profiler_trace_carries_the_spans(model, tmp_path, enabled):
    """A ``jax.profiler`` trace taken while the server runs holds the spans
    measured in place as host events when the Tracer is on, none when off."""
    from jax.profiler import ProfileData

    set_tracer(Tracer(capacity=1 << 16, enabled=enabled))
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve_one_wave(model, DeviceGroup(f"prof-{enabled}"))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events}
    if enabled:
        assert ANNOTATED <= names, ANNOTATED - names
    else:
        assert not ANNOTATED & names

"""Span tracer + streaming telemetry: concurrent well-formedness, ring
wraparound, rolling-quantile math, Prometheus exposition, and the traced
server's bit-identity + internal/external metric consistency."""
import json
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Static
from repro.core.introspector import Introspector, PackageRecord
from repro.core.trace import (
    Tracer,
    phase_totals,
    set_tracer,
    tracer,
    validate_chrome,
)
from repro.models import get_model
from repro.models import params as P
from repro.serve import InferenceServer, Telemetry, make_generate
from repro.serve.telemetry import RollingStat, quantile

PLEN, GEN = 8, 5


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    """Every test leaves the process-wide tracer disabled (instrumentation
    points across the stack read it — leaking an enabled tracer would slow
    and couple unrelated tests)."""
    yield
    set_tracer(Tracer(enabled=False))


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, api, params


# ----------------------------------------------------------------- tracer
def test_concurrent_spans_export_wellformed():
    """Many threads emitting nested sync spans + async request spans at
    once: the exported Chrome JSON passes the schema checker (balanced B/E
    per track, balanced async per id, monotonic timestamps)."""
    tr = Tracer(capacity=1 << 14, enabled=True)

    def client(i: int):
        tr.async_begin("request", i, bucket=8)
        for j in range(20):
            with tr.span("outer", track=f"client/{i}", j=j):
                with tr.span("inner", track=f"client/{i}"):
                    tr.instant("tick", track=f"client/{i}")
            tr.async_instant("step", i, j=j)
        tr.async_end("request", i, status="ok")

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    doc = tr.export()
    assert validate_chrome(doc) == []
    # Round-trips as real JSON.
    doc2 = json.loads(json.dumps(doc))
    assert validate_chrome(doc2) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request", "outer", "inner", "tick", "step"} <= names


def test_ring_wraparound_keeps_export_wellformed():
    """A tiny ring lapped many times over: orphaned ends are dropped and
    dangling begins closed, so the export stays schema-valid and the
    tracer reports what it dropped."""
    tr = Tracer(capacity=64, enabled=True)

    def worker(k: int):
        for j in range(500):
            with tr.span("work", track=f"w/{k}", j=j):
                tr.instant("mid", track=f"w/{k}")
            tr.async_begin("aspan", k * 1000 + j)
            tr.async_end("aspan", k * 1000 + j)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.dropped > 0
    assert len(tr) == 64
    doc = json.loads(json.dumps(tr.export()))
    assert validate_chrome(doc) == []


def test_dangling_begin_closed_at_export():
    tr = Tracer(capacity=256, enabled=True)
    tr.begin("open_forever", track="t")
    tr.instant("later", track="t")
    doc = tr.export()
    assert validate_chrome(doc) == []
    phases = [(e["name"], e["ph"]) for e in doc["traceEvents"]]
    assert ("open_forever", "E") in phases  # synthesized close


def test_disabled_tracer_records_nothing():
    tr = Tracer(capacity=128, enabled=False)
    with tr.span("x"):
        tr.instant("y")
    tr.async_begin("r", 1)
    assert len(tr) == 0


def test_phase_totals_aggregates_known_spans():
    tr = Tracer(capacity=256, enabled=True, clock=lambda: 0.0)
    tr.complete("seg", 0.0, 0.25, track="b")
    tr.complete("seg", 0.0, 0.5, track="b")
    totals = phase_totals(tr.chrome_events())
    assert totals["seg"]["count"] == 2
    assert totals["seg"]["seconds"] == pytest.approx(0.75)


def test_validate_chrome_flags_bad_traces():
    assert validate_chrome({}) != []
    bad = {"traceEvents": [
        {"name": "a", "ph": "E", "ts": 0, "pid": 0, "tid": 1},
    ]}
    assert any("without open B" in e for e in validate_chrome(bad))
    unbalanced = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0, "pid": 0, "tid": 1},
    ]}
    assert any("never ends" in e for e in validate_chrome(unbalanced))


# -------------------------------------------------------------- telemetry
def test_rolling_quantiles_match_numpy_exact():
    """RollingStat's windowed quantiles equal np.percentile (linear
    interpolation) over the same window, for several stream lengths."""
    rng = np.random.default_rng(0)
    for n in (1, 5, 64, 200):
        rs = RollingStat(window=64)
        vals = rng.normal(size=n)
        for v in vals:
            rs.observe(float(v))
        window = vals[-64:]
        snap = rs.snapshot()
        for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
            assert snap[key] == pytest.approx(
                float(np.percentile(window, q)), abs=1e-12), (n, q)
        assert snap["count"] == n
        assert snap["sum"] == pytest.approx(float(vals.sum()))


def test_quantile_helper_edge_cases():
    assert quantile([], 0.5) is None
    assert quantile([3.0], 0.99) == 3.0
    assert quantile([1.0, 2.0], 0.5) == pytest.approx(1.5)


def test_telemetry_counters_gauges_and_nonfinite_guard():
    t = Telemetry(window=8)
    t.count("reqs")
    t.count("reqs", 4)
    t.gauge("pool", 7)
    t.observe("x", float("nan"))  # dropped
    t.observe("x", float("inf"))  # dropped
    t.observe("x", 2.0)
    snap = t.snapshot()
    assert snap["counters"]["reqs"] == 5
    assert snap["gauges"]["pool"] == 7
    assert snap["observations"]["x"]["count"] == 1


def test_prometheus_exposition_parses():
    t = Telemetry(window=32)
    for i in range(10):
        t.observe("ttft_s", 0.01 * (i + 1))
    t.count("requests_completed", 10)
    t.gauge("pool_blocks_in_use", 3)
    text = t.prometheus(prefix="enginecl")
    line_re = re.compile(
        r'^(# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*'
        r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eEinfa]+)$')
    for line in text.strip().split("\n"):
        assert line_re.match(line), line
    assert 'enginecl_ttft_s{quantile="0.5"}' in text
    assert "enginecl_ttft_s_sum" in text
    assert "enginecl_ttft_s_count 10" in text
    assert "enginecl_requests_completed_total 10" in text
    assert "enginecl_pool_blocks_in_use 3" in text


# ----------------------------------------------------- introspector safety
def test_introspector_concurrent_record_and_summary():
    """Workers appending records + counters while another thread reads
    summary()/balance()/per_device(): no exception, and each summary is
    internally consistent (package count matches per-device totals)."""
    intro = Introspector()
    intro.start_run()
    stop = threading.Event()
    errs = []

    def writer(d: str):
        i = 0
        while not stop.is_set():
            intro.record(PackageRecord(d, i, 8, 0.0, 0.1, 0.2))
            intro.record_counters(d, 1, 0)
            i += 1

    def reader():
        try:
            while not stop.is_set():
                s = intro.summary()
                assert s["n_packages"] == sum(
                    d["packages"] for d in s["per_device"].values())
                intro.balance()
                intro.per_device()
                intro.end_run()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(d,))
               for d in ("a", "b")] + [threading.Thread(target=reader)]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errs, errs


def test_introspector_sink_failure_never_breaks_recording():
    def bad_sink(rec):
        raise RuntimeError("observer crashed")

    intro = Introspector(sink=bad_sink)
    intro.start_run()
    intro.record(PackageRecord("a", 0, 8, 0.0, 0.1, 0.2))
    assert intro.summary()["n_packages"] == 1


# ------------------------------------------------------------ traced server
def test_stats_occupancy_mean_guarded_before_any_segment(model):
    cfg, api, params = model
    srv = InferenceServer(cfg, api, params, buckets=(PLEN,), max_batch=2,
                          seg_len=2, max_new_cap=4)
    try:
        s = srv.stats()
        assert s["occupancy_mean"] == 0.0
        assert s["mean_occupancy"] == 0.0  # legacy alias
    finally:
        srv.close()


def test_traced_server_bit_identical_with_full_span_taxonomy(model):
    """Tracing on: served outputs stay bit-identical to one-shot generate,
    the trace carries every lifecycle span (request, admission, boarding,
    merge, harvest, segments, runtime queue_wait/dispatch/upload/execute/
    write_back) for every request, and the server's internal rolling TTFT/ITL quantiles agree with the values
    computed externally from the same handles."""
    cfg, api, params = model
    tr = set_tracer(Tracer(capacity=1 << 15, enabled=True))
    tel = Telemetry(window=256)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, PLEN).astype(np.int32)
               for _ in range(8)]
    with InferenceServer(cfg, api, params, groups=[DeviceGroup("traced")],
                         scheduler=Static(), buckets=(PLEN,), max_batch=4,
                         seg_len=2, max_new_cap=GEN, telemetry=tel) as srv:
        handles = [srv.submit(p, GEN) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
        m = srv.metrics()
    ref = make_generate(cfg, api)
    for p, got in zip(prompts, results):
        want = np.asarray(ref(params, {"tokens": jnp.asarray(p[None])}, GEN))[0]
        np.testing.assert_array_equal(got, want)

    doc = tr.export()
    assert validate_chrome(doc) == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"request", "admission", "board", "first_token", "decode_segment",
            "segment", "queue_wait", "dispatch", "upload", "execute",
            "write_back", "merge", "harvest", "form_group"} <= names, names
    # Every request's async lifecycle is complete: one begin and one end
    # per submitted request, admission verdicts for all.
    per = {}
    for e in evs:
        if e.get("cat") == "request":
            per.setdefault(e["id"], []).append((e["name"], e["ph"]))
    assert len(per) == len(prompts)
    for rid, seq in per.items():
        assert ("request", "b") in seq and ("request", "e") in seq, (rid, seq)
        assert ("admission", "n") in seq, (rid, seq)
        assert ("first_token", "n") in seq, (rid, seq)

    # Internal (rolling telemetry) vs external (handle metrics) quantiles:
    # same values through the same estimator.
    ttft = sorted(h.metrics["ttft"] for h in handles)
    itl = sorted((h.metrics["latency"] - h.metrics["ttft"]) / (GEN - 1)
                 for h in handles)
    obs = m["telemetry"]["observations"]
    for key, ext in (("ttft_s", ttft), ("itl_s", itl)):
        for q, pkey in ((0.5, "p50"), (0.99, "p99")):
            internal, external = obs[key][pkey], quantile(ext, q)
            assert internal == pytest.approx(external, rel=0.05), (
                key, pkey, internal, external)
    assert m["telemetry"]["counters"]["requests_completed"] == len(prompts)


def test_tracing_does_not_change_outputs_vs_untraced(model):
    """The same prompt served traced and untraced produces identical
    bits (observability is passive)."""
    cfg, api, params = model
    p = np.arange(PLEN, dtype=np.int32) % cfg.vocab

    def serve_once():
        with InferenceServer(cfg, api, params, buckets=(PLEN,), max_batch=2,
                             seg_len=2, max_new_cap=GEN) as srv:
            return srv.submit(p, GEN).result(timeout=300)

    set_tracer(Tracer(enabled=False))
    plain = serve_once()
    set_tracer(Tracer(capacity=1 << 12, enabled=True))
    traced = serve_once()
    np.testing.assert_array_equal(plain, traced)
    assert len(tracer()) > 0

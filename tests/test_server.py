"""Continuous-batching inference server: bit-identity to one-shot generate,
multi-client concurrency, mid-stream join/exit, deadline admission, and
device-resident segment chaining (transfer counters)."""
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Dynamic, Static
from repro.models import get_model
from repro.models import params as P
from repro.serve import (
    AdmissionError,
    Buckets,
    DeadlineAdmission,
    DraftSpec,
    InferenceServer,
    PagedSpec,
    ServiceModel,
    edf_key,
    make_generate,
    segments_for,
)
from repro.serve.batcher import BatchGroup, ModelKernels
from repro.serve.paged import PagedBatchGroup, PoolState

PLEN, GEN = 8, 6


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, api, params


@pytest.fixture(scope="module")
def reference(model):
    """Per-request one-shot generate (batch of 1) — the ground truth every
    server result must equal bit-for-bit."""
    cfg, api, params = model
    gen = make_generate(cfg, api)

    def ref(prompt, n):
        toks = gen(params, {"tokens": jnp.asarray(np.asarray(prompt)[None])}, n)
        return np.asarray(toks)[0]

    return ref


@pytest.fixture(scope="module")
def server(model):
    """One shared single-group server (compiling the segment kernel once)."""
    cfg, api, params = model
    srv = InferenceServer(cfg, api, params, groups=[DeviceGroup("shared")],
                          scheduler=Static(), buckets=(PLEN, 2 * PLEN),
                          max_batch=4, seg_len=2, max_new_cap=10,
                          max_wait_ms=10.0)
    yield srv
    srv.close()


def prompts_for(cfg, seed, n, plen=PLEN):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, plen).astype(np.int32) for _ in range(n)]


# ----------------------------------------------------------- acceptance run
def test_poisson_arrivals_bit_identical_with_real_batching(model, reference):
    """32 Poisson-arrival requests through a fresh server: every token
    stream equals its per-request one-shot generate, decode batches
    actually form (mean occupancy > 1), and per-request host→device
    transfers stay O(1) despite multi-segment decode."""
    cfg, api, params = model
    g = DeviceGroup("poisson")
    prompts = prompts_for(cfg, 11, 32)
    gens = [4 + (i % 3) for i in range(32)]  # mixed lengths: staggered exits
    rng = np.random.default_rng(12)
    gaps = rng.exponential(3e-3, 32)
    with InferenceServer(cfg, api, params, groups=[g], scheduler=Static(),
                         buckets=(PLEN,), max_batch=4, seg_len=2,
                         max_new_cap=8, max_wait_ms=5.0) as srv:
        handles = []
        for p, n, gap in zip(prompts, gens, gaps):
            time.sleep(gap)
            handles.append(srv.submit(p, n))
        results = [h.result(timeout=300) for h in handles]
        s = srv.stats()
    for p, n, got in zip(prompts, gens, results):
        np.testing.assert_array_equal(got, reference(p, n))
    assert s["completed"] == 32
    assert s["mean_occupancy"] > 1.0, s
    # Device-resident segment chaining: transfers are paid per prefill wave
    # (prompt upload) and per merge (re-upload of the segment Program's
    # token and position buffers; the cache leaves stay on the device and
    # take the joiners' rows there) — never per decode segment.
    n_ins = 2  # tok, pos
    waves = s["prefill_waves"]
    assert s["segments"] > waves, s  # decode really was multi-segment
    assert g.n_transfers <= waves * (1 + n_ins), (g.transfer_stats(), s)
    # O(1) per request: bounded by join events, not by segment count.
    assert g.n_transfers <= 32 * (1 + n_ins)


# ------------------------------------------------------------- concurrency
def test_multi_client_threads_results_keyed_correctly(model, server, reference):
    """Concurrent client threads, mixed buckets: every handle resolves to
    its own request's reference tokens — no cross-request leakage."""
    cfg, _, _ = model
    n_threads, per_thread = 4, 3
    results = {}
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng(100 + tid)
        for i in range(per_thread):
            plen = PLEN if (tid + i) % 2 == 0 else 2 * PLEN
            p = rng.integers(0, cfg.vocab, plen).astype(np.int32)
            h = server.submit(p, GEN)
            got = h.result(timeout=300)
            with lock:
                results[(tid, i)] = (p, got)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == n_threads * per_thread
    for p, got in results.values():
        np.testing.assert_array_equal(got, reference(p, GEN))


# ------------------------------------------------------- join/exit mid-stream
def test_midstream_join_exit_and_transfer_counters(model, reference):
    """Requests join a group whose decode is already under way (and earlier
    requests exit before later ones finish); tokens stay bit-identical and
    transfers scale with join events, not with decode segments."""
    cfg, api, params = model
    g = DeviceGroup("joiner")
    with InferenceServer(cfg, api, params, groups=[g], scheduler=Static(),
                         buckets=(PLEN,), max_batch=4, seg_len=2,
                         max_new_cap=10, max_wait_ms=1.0) as srv:
        first = prompts_for(cfg, 21, 2)
        h1 = [srv.submit(p, 10) for p in first]  # 5 decode segments each
        # Wait until decode is genuinely mid-stream before the second wave.
        deadline = time.monotonic() + 60
        while srv.stats()["segments"] < 1:
            assert time.monotonic() < deadline, "first segment never finished"
            time.sleep(0.005)
        second = prompts_for(cfg, 22, 2)
        h2 = [srv.submit(p, 3) for p in second]  # exit long before wave 1
        for p, h in zip(first + second, h1 + h2):
            np.testing.assert_array_equal(
                h.result(timeout=300), reference(p, h.max_new_tokens)
            )
        s = srv.stats()
    assert s["midstream_joins"] >= 1, s
    assert s["segments"] > s["prefill_waves"] + 1, s
    # Exact transfer accounting on a single Static group: one prompt upload
    # per prefill wave + one re-upload of the token and position buffers
    # per merge; the cache leaves never cross.
    n_ins = 2
    assert g.n_transfers == s["prefill_waves"] * (1 + n_ins), (
        g.transfer_stats(), s
    )
    # To host: each segment's tokens, token and position buffers, and each
    # joiner's first token — nothing of the cache.  Kept on the device:
    # each segment's whole cache and each joiner's prefill rows.
    slots, seg = 4, 2
    joins = len(first) + len(second)
    st = g.transfer_stats()
    assert st["d2h_bytes"] == (s["segments"] * slots * (seg + 2) * 4
                               + joins * 4), (st, s)
    row = srv.kernels.leaf_buffers(1, srv._max_seq(PLEN), resident=True)
    row_bytes = sum(b.nbytes for b in row)
    assert st["kept_bytes"] == (s["segments"] * slots + joins) * row_bytes, (
        st, s)


@pytest.mark.parametrize("mode", ["plain", "spec", "chunked"])
def test_midstream_join_resident_cache_bit_identical(model, reference, mode):
    """A join into a running batch whose cache lives on the device (one
    group): every stream equals its reference in plain, speculative and
    chunked decode, and what each segment copies to host is bounded by its
    token and position buffers — far below one slot's cache."""
    cfg, api, params = model
    g = DeviceGroup(f"resident-{mode}")
    kw, k = {}, 0
    if mode == "spec":
        kw["draft"], k = DraftSpec(cfg, params, k=2), 2
    if mode == "chunked":
        kw["chunk_len"] = 4
    slots, seg = 4, 2
    with InferenceServer(cfg, api, params, groups=[g], scheduler=Static(),
                         buckets=(PLEN,), max_batch=slots, seg_len=seg,
                         max_new_cap=10, max_wait_ms=1.0, **kw) as srv:
        first = prompts_for(cfg, 23, 2)
        h1 = [srv.submit(p, 10) for p in first]
        deadline = time.monotonic() + 60
        while srv.stats()["segments"] < 1:
            assert time.monotonic() < deadline, "first segment never finished"
            time.sleep(0.005)
        second = prompts_for(cfg, 24, 2)
        h2 = [srv.submit(p, 4) for p in second]
        for p, h in zip(first + second, h1 + h2):
            np.testing.assert_array_equal(
                h.result(timeout=300), reference(p, h.max_new_tokens))
        s = srv.stats()
        row = srv.kernels.leaf_buffers(1, srv._max_seq(PLEN))
    assert s["midstream_joins"] >= 1, s
    st = g.transfer_stats()
    # Per segment: the token buffer (seg_len * (k + 1) per slot) plus at
    # most five one-column buffers (cnt, tok, ptok, pos | pcur, ctok); per
    # joiner: its first token and predecessor.
    per_segment = slots * (seg * (k + 1) + 5) * 4
    assert st["d2h_bytes"] <= s["segments"] * per_segment + 4 * 2 * 4, (st, s)
    assert st["d2h_bytes"] < sum(b.nbytes for b in row), (st, s)
    assert st["kept_bytes"] >= s["segments"] * slots * sum(
        b.nbytes for b in row), (st, s)


def test_coexec_slot_splitting_stays_bit_identical(model, reference):
    """Two device groups + Dynamic scheduler: the slot axis of each segment
    is split across groups (varying splits), results unchanged."""
    cfg, api, params = model
    groups = [DeviceGroup("pod-a"), DeviceGroup("pod-b")]
    prompts = prompts_for(cfg, 31, 6)
    with InferenceServer(cfg, api, params, groups=groups, scheduler=Dynamic(2),
                         buckets=(PLEN,), max_batch=4, seg_len=2,
                         max_new_cap=8, max_wait_ms=5.0) as srv:
        handles = [srv.submit(p, GEN) for p in prompts]
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(timeout=300),
                                          reference(p, GEN))
        assert srv.stats()["completed"] == 6


# ---------------------------------------------------------------- admission
def test_deadline_rejection_and_metrics(model):
    """With a warmed service model, an unmeetable deadline is rejected at
    submit (no queue pollution, handle resolves immediately)."""
    cfg, api, params = model
    sm = ServiceModel()
    sm.observe("prefill", PLEN, 0.050)
    sm.observe("segment", PLEN, 0.050)
    srv = InferenceServer(cfg, api, params, buckets=(PLEN,), seg_len=2,
                          max_new_cap=10,
                          admission=DeadlineAdmission(sm))
    try:
        p = prompts_for(cfg, 41, 1)[0]
        h = srv.submit(p, 9, deadline_s=0.001)  # needs ~4 segments ≈ 250ms
        assert h.done() and h.rejected
        with pytest.raises(AdmissionError, match="deadline"):
            h.result()
        assert h.metrics["latency"] is not None
        assert srv.stats()["rejected"] == 1
        assert srv.stats()["completed"] == 0
    finally:
        srv.close()


def test_deadline_feasible_request_is_served(server, model, reference):
    cfg, _, _ = model
    p = prompts_for(cfg, 42, 1)[0]
    h = server.submit(p, GEN, deadline_s=300.0)
    np.testing.assert_array_equal(h.result(timeout=300), reference(p, GEN))
    assert not h.rejected
    m = h.metrics
    assert m["latency"] >= m["ttft"] >= 0


def test_admission_units():
    sm = ServiceModel(alpha=0.5)
    assert sm.estimate("segment", 8) is None
    sm.observe("segment", 8, 0.1)
    sm.observe("segment", 8, 0.2)
    assert sm.estimate("segment", 8) == pytest.approx(0.15)
    adm = DeadlineAdmission(sm)
    # cold bucket admits; observed bucket forecasts segments*ema
    assert adm.admit(0.0, 1.0, 16, 100)
    assert adm.admit(0.0, None, 8, 10**6)
    assert adm.admit(0.0, 0.5, 8, 3, include_prefill=False)
    assert not adm.admit(0.0, 0.3, 8, 3, include_prefill=False)
    # EDF: deadlines first (earliest first), FIFO among deadline-less
    keys = [edf_key(d, i) for i, d in enumerate([None, 5.0, 1.0, None])]
    order = sorted(range(4), key=lambda i: keys[i])
    assert order == [2, 1, 0, 3]


def test_buckets_and_segments():
    b = Buckets([32, 8, 16])
    assert b.sizes == [8, 16, 32]
    assert b.bucket_for(1) == 8 and b.bucket_for(8) == 8
    assert b.bucket_for(9) == 16 and b.bucket_for(33) is None
    padded = Buckets.pad(np.arange(5, dtype=np.int32), 8, 0)
    assert padded.tolist() == [0, 1, 2, 3, 4, 0, 0, 0]
    assert segments_for(1, 4) == 0  # first token comes from prefill
    assert segments_for(5, 4) == 1
    assert segments_for(6, 4) == 2


# ----------------------------------------------------------- contract edges
def test_padding_contract(server, model, reference):
    """A short prompt is right-padded to its bucket; the server's output is
    one-shot generate on the *padded* prompt (the documented contract)."""
    cfg, _, _ = model
    p = prompts_for(cfg, 51, 1, plen=5)[0]
    h = server.submit(p, GEN)
    got = h.result(timeout=300)
    assert h.metrics["padded_len"] == PLEN
    padded = Buckets.pad(p, PLEN, 0)
    np.testing.assert_array_equal(got, reference(padded, GEN))


def test_single_token_request(server, model, reference):
    """gen=1: the whole answer comes from prefill, no decode segment."""
    cfg, _, _ = model
    p = prompts_for(cfg, 52, 1)[0]
    got = server.submit(p, 1).result(timeout=300)
    assert got.shape == (1,)
    np.testing.assert_array_equal(got, reference(p, 1))


def test_submit_validation(server, model):
    cfg, _, _ = model
    with pytest.raises(ValueError, match="max_new_tokens"):
        server.submit(np.zeros(PLEN, np.int32), 10**6)
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        server.submit(np.zeros(10 * PLEN, np.int32), 2)


def test_closed_server_rejects_submissions(model):
    cfg, api, params = model
    srv = InferenceServer(cfg, api, params, buckets=(PLEN,))
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(np.zeros(PLEN, np.int32), 2)


def test_kernel_path_server_bit_identity(model):
    """kernel_impl=pallas_interpret: the ragged flash-decode Pallas kernel
    runs inside the serving segment scan (and Pallas flash-attention in
    prefill); results stay bit-identical to one-shot generate on the same
    config — the serving equivalence contract extends to the kernel path."""
    import dataclasses

    cfg, api, params = model
    kcfg = dataclasses.replace(cfg, kernel_impl="pallas_interpret")
    gen = make_generate(kcfg, api)
    prompts = prompts_for(kcfg, 71, 3)
    with InferenceServer(kcfg, api, params, groups=[DeviceGroup("kpath")],
                         scheduler=Static(), buckets=(PLEN,), max_batch=2,
                         seg_len=2, max_new_cap=6, max_wait_ms=5.0) as srv:
        handles = [srv.submit(p, 4) for p in prompts]
        results = [h.result(timeout=600) for h in handles]
        assert srv.stats()["completed"] == 3
    for p, got in zip(prompts, results):
        want = np.asarray(gen(params, {"tokens": jnp.asarray(p[None])}, 4))[0]
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------- shared generate helper
def test_make_generate_jit_and_jitless_bit_identical(model):
    """The single shared prefill+chain path (used by the plain launcher,
    the co-exec kernel, and test references) is jit/eager bit-identical —
    the two pre-dedup launcher paths materialized caches differently."""
    cfg, api, params = model
    batch = {"tokens": jnp.asarray(prompts_for(cfg, 61, 3)[0][None])}
    a = make_generate(cfg, api, jit=True)(params, batch, GEN)
    b = make_generate(cfg, api, jit=False)(params, batch, GEN)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _constant_sizes(hlo: str) -> list:
    """Element counts of every constant in a lowered module's text."""
    sizes = []
    for line in hlo.splitlines():
        if "stablehlo.constant" not in line:
            continue
        dims = re.findall(r"tensor<([^>]*)>", line)[-1].split("x")[:-1]
        sizes.append(int(np.prod([int(d) for d in dims])))
    return sizes


@pytest.mark.parametrize("kind", ["decode", "paged", "chunked", "prefill"])
def test_serving_programs_take_weights_as_arguments(model, kind):
    """No serving kernel closes over the weights: jit compiles a closed-over
    array into the program as a constant, which at published widths is the
    whole model in every program.  The weights must arrive as arguments."""
    cfg, api, params = model
    kernels = ModelKernels(cfg, api, params)
    if kind == "prefill":
        fn, ins = kernels.prefill_kernel(16), [np.zeros((2, PLEN), np.int32)]
        args = [kernels.weights]
    else:
        if kind == "paged":
            grp = PagedBatchGroup(kernels, None, None, PLEN, 2, 2, 16,
                                  PagedSpec(block_len=4), PoolState())
        else:
            grp = BatchGroup(kernels, None, None, PLEN, 2, 2, 16,
                             chunk_len=2 if kind == "chunked" else 0)
        fn, ins, args = grp.prog._kernel, grp.prog._ins, grp.prog._args
    assert args == [kernels.weights]
    hlo = jax.jit(fn).lower(np.int32(0), *ins, *args).as_text()
    weights = jax.tree_util.tree_leaves(params)
    main = next(l for l in hlo.splitlines() if "func.func public @main" in l)
    assert main.count("%arg") >= len(weights)
    smallest_matrix = min(w.size for w in weights if w.ndim >= 2)
    assert max(_constant_sizes(hlo)) < smallest_matrix


# ----------------------------------------------------- one segment ahead
def _traced_spans(run):
    from repro.core.trace import Tracer, set_tracer

    tr = set_tracer(Tracer(capacity=1 << 16, enabled=True))
    try:
        run()
        return [e for e in tr.events() if e[3] == "X"]
    finally:
        set_tracer(Tracer(enabled=False))


def test_segment_ahead_tokens_unchanged(model, reference):
    """With a segment queued ahead of the one running: a request that ends
    at ``max_new_cap`` while another still decodes (the segment queued for
    that one decodes its slot past the cache's last row), one that exits in
    the middle of a segment, and joins that drain the boundary first all
    leave every stream bit-identical."""
    cfg, api, params = model
    g = DeviceGroup("ahead")
    cap, out = 24, {}

    def run():
        with InferenceServer(cfg, api, params, groups=[g],
                             scheduler=Static(), buckets=(PLEN,),
                             max_batch=4, seg_len=2, max_new_cap=cap,
                             max_wait_ms=1.0) as srv:
            ps = prompts_for(cfg, 81, 3)
            hs = [srv.submit(ps[0], cap)]
            deadline = time.monotonic() + 60
            while srv.stats()["segments"] < 1:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            hs += [srv.submit(ps[1], cap), srv.submit(ps[2], 4)]
            out["res"] = [(p, h.max_new_tokens, h.result(timeout=300))
                          for p, h in zip(ps, hs)]
            out["hs"], out["stats"] = hs, srv.stats()

    spans = _traced_spans(run)
    for p, n, toks in out["res"]:
        np.testing.assert_array_equal(toks, reference(p, n))
    a, b, _ = out["hs"]
    assert b.t_first_token < a.t_done  # b decoded past a's last segment
    s = out["stats"]
    assert s["midstream_joins"] >= 1, s
    segs = [e for e in spans if e[4] == "segment"]
    assert len(segs) == s["segments"]
    assert any(e[7]["ahead"] == 1 for e in segs)
    assert any(e[4] == "drain" for e in spans)
    # A segment ahead is dispatched while the one before it runs.
    starts = sorted(e[1] for e in spans if e[4] == "dispatch"
                    and e[7]["kernel"].startswith("decode_seg"))
    ends = sorted(e[2] for e in segs)
    assert any(d < end for d, end in zip(starts[1:], ends))


@pytest.mark.parametrize("mode", ["spec", "chunked", "paged"])
def test_spec_chunked_paged_run_one_segment_at_a_time(model, reference,
                                                      mode):
    """Speculative, chunked and paged groups keep one segment in flight:
    each is submitted after the one before it was harvested."""
    cfg, api, params = model
    kw = {"spec": {"draft": DraftSpec(cfg, params, k=2)},
          "chunked": {"chunk_len": 4},
          "paged": {"paged": PagedSpec(block_len=4)}}[mode]
    out = {}

    def run():
        with InferenceServer(cfg, api, params,
                             groups=[DeviceGroup(f"one-{mode}")],
                             scheduler=Static(), buckets=(PLEN,),
                             max_batch=2, seg_len=2, max_new_cap=8,
                             max_wait_ms=1.0, **kw) as srv:
            ps = prompts_for(cfg, 83, 3)
            hs = [srv.submit(p, 7) for p in ps]
            deadline = time.monotonic() + 60
            while not srv._groups:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            out["ahead"] = next(iter(srv._groups.values())).ahead
            out["res"] = [(p, h.result(timeout=300)) for p, h in zip(ps, hs)]

    spans = _traced_spans(run)
    for p, toks in out["res"]:
        np.testing.assert_array_equal(toks, reference(p, 7))
    assert not out["ahead"]
    segs = sorted((e for e in spans if e[4] == "segment"), key=lambda e: e[1])
    waits = sorted((e for e in spans if e[4] == "queue_wait"
                    and "seg" in e[7]["kernel"]), key=lambda e: e[1])
    assert len(segs) == len(waits) >= 2
    assert all(e[7]["ahead"] == 0 for e in segs)
    assert not any(e[4] == "drain" for e in spans)
    for prev, w in zip(segs, waits[1:]):
        assert w[1] >= prev[2]

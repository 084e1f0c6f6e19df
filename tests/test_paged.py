"""Paged KV-cache memory subsystem: bit-identity under block indirection,
block reuse across join/exit, prefix sharing + copy-on-write, pool
exhaustion (defer/reject), allocated-bytes accounting, and rolling-window
configs through the server decode path."""
import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core import DeviceGroup, Dynamic, Program, Runtime, Static
from repro.models import get_model
from repro.models import params as P
from repro.serve import (
    AdmissionError,
    BlockPool,
    InferenceServer,
    PagedSpec,
    PoolAdmission,
    blocks_needed,
    make_generate,
)

PLEN = 8


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, api, params


@pytest.fixture(scope="module")
def reference(model):
    cfg, api, params = model
    gen = make_generate(cfg, api)

    def ref(prompt, n):
        toks = gen(params, {"tokens": jnp.asarray(np.asarray(prompt)[None])}, n)
        return np.asarray(toks)[0]

    return ref


def prompts_for(cfg, seed, n, plen=PLEN):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, plen).astype(np.int32) for _ in range(n)]


def paged_server(cfg, api, params, *, name, block_len=4, n_blocks=0,
                 prefix=True, max_batch=4, seg_len=2, max_new_cap=8,
                 max_wait_ms=5.0, buckets=(PLEN,)):
    return InferenceServer(
        cfg, api, params, groups=[DeviceGroup(name)], scheduler=Static(),
        buckets=buckets, max_batch=max_batch, seg_len=seg_len,
        max_new_cap=max_new_cap, max_wait_ms=max_wait_ms,
        paged=PagedSpec(block_len=block_len, n_blocks=n_blocks,
                        prefix_cache=prefix),
    )


# ------------------------------------------------------------ acceptance run
def test_join_exit_sweep_bit_identical_with_block_reuse(model, reference):
    """Staggered joins/exits with mixed gen lengths through the paged pool:
    every stream equals its one-shot reference regardless of which physical
    blocks back it, and exits really recycle blocks (frees happen, total
    allocations exceed the concurrent peak)."""
    cfg, api, params = model
    prompts = prompts_for(cfg, 11, 16)
    gens = [4 + (i % 3) for i in range(16)]
    rng = np.random.default_rng(12)
    gaps = rng.exponential(3e-3, 16)
    with paged_server(cfg, api, params, name="sweep") as srv:
        handles = []
        for p, n, gap in zip(prompts, gens, gaps):
            time.sleep(gap)
            handles.append(srv.submit(p, n))
        results = [h.result(timeout=300) for h in handles]
        s = srv.stats()
    for p, n, got in zip(prompts, gens, results):
        np.testing.assert_array_equal(got, reference(p, n))
    mem = s["memory"]
    assert s["completed"] == 16
    assert mem["frees"] > 0, mem
    assert mem["allocs"] > mem["blocks_peak"], mem  # blocks were reused
    assert mem["kv_bytes_allocated"] == mem["blocks_peak"] * mem["bytes_per_block"]


def test_pallas_kernel_paged_bit_identity(model):
    """kernel_impl=pallas_interpret + decode_block=block_len: the block-
    table Pallas kernel runs inside the segment scan and stays bit-identical
    to one-shot generate on the same config (equal logical tile
    partitions)."""
    cfg, api, params = model
    kcfg = dataclasses.replace(cfg, kernel_impl="pallas_interpret",
                               decode_block=4)
    gen = make_generate(kcfg, api)
    prompts = prompts_for(kcfg, 71, 3)
    with paged_server(kcfg, api, params, name="kpag", max_batch=2,
                      max_new_cap=6) as srv:
        handles = [srv.submit(p, 4) for p in prompts]
        results = [h.result(timeout=600) for h in handles]
        assert srv.stats()["completed"] == 3
    for p, got in zip(prompts, results):
        want = np.asarray(gen(params, {"tokens": jnp.asarray(p[None])}, 4))[0]
        np.testing.assert_array_equal(got, want)


def test_blocks_past_max_seq_stay_empty_across_reuse(model, reference):
    """A 20-token prompt with 6 new tokens (max_seq 26) in 16-token blocks:
    the prefill row ends inside the tail block, whose rows past it must read
    as empty — also when the block last held another request's early
    positions, which served requests one after another recycle."""
    cfg, api, params = model
    prompts = prompts_for(cfg, 41, 4, plen=20)
    with paged_server(cfg, api, params, name="short", block_len=16,
                      prefix=False, max_batch=1, max_new_cap=6,
                      buckets=(20,)) as srv:
        results = [srv.submit(p, 6).result(timeout=300) for p in prompts]
        mem = srv.stats()["memory"]
    for p, got in zip(prompts, results):
        np.testing.assert_array_equal(got, reference(p, 6))
    assert mem["allocs"] > mem["blocks_peak"], mem  # blocks were reused


# ------------------------------------------------------------- prefix reuse
def test_same_wave_prefix_share_and_cow_divergence(model, reference):
    """Two identical prompts in one wave with a partial tail block
    (bucket < block_len): prefill runs ONCE for the shared blocks, both
    slots share them, and the first divergent append is isolated by
    copy-on-write — each stream still equals its own reference."""
    cfg, api, params = model
    p = prompts_for(cfg, 21, 1)[0]
    with paged_server(cfg, api, params, name="cow", block_len=16,
                      max_wait_ms=50.0) as srv:
        h1 = srv.submit(p, 6)
        h2 = srv.submit(p.copy(), 3)
        r1, r2 = h1.result(timeout=300), h2.result(timeout=300)
        mem = srv.stats()["memory"]
    np.testing.assert_array_equal(r1, reference(p, 6))
    np.testing.assert_array_equal(r2, reference(p, 3))
    assert mem["prefill_rows"] == 1, mem      # one prefill for two requests
    assert mem["prefix_hits"] >= 1, mem
    assert mem["cow"] >= 1, mem               # tail block copied on divergence


def test_cross_wave_prompt_reuse_and_chain_share(model, reference):
    """Prefix cache survives request exit (and group dissolve): a repeated
    whole prompt skips prefill entirely; a prompt sharing only the first
    full block maps its leading table entry to the same physical block."""
    cfg, api, params = model
    p1 = prompts_for(cfg, 31, 1)[0]
    p2 = p1.copy()
    p2[4:] = prompts_for(cfg, 32, 1)[0][4:]
    with paged_server(cfg, api, params, name="pfx", max_wait_ms=2.0) as srv:
        ra = srv.submit(p1, 4).result(timeout=300)
        time.sleep(0.05)  # first group goes idle and dissolves
        hb, hc = srv.submit(p1.copy(), 6), srv.submit(p2, 4)
        rb, rc = hb.result(timeout=300), hc.result(timeout=300)
        mem = srv.stats()["memory"]
    np.testing.assert_array_equal(ra, reference(p1, 4))
    np.testing.assert_array_equal(rb, reference(p1, 6))
    np.testing.assert_array_equal(rc, reference(p2, 4))
    assert mem["prefill_rows_shared"] >= 1, mem  # whole-prompt hit: no prefill
    assert mem["prefix_blocks_shared"] >= 1, mem  # chain hit: shared block
    assert mem["blocks_cached"] > 0, mem


# ---------------------------------------------------------------- admission
def test_pool_exhaustion_defers_then_serves(model, reference):
    """A pool too small for the offered concurrency defers boardings (EDF
    queue intact) until exits free blocks — every request completes
    correctly, no live slot is ever corrupted by overcommit."""
    cfg, api, params = model
    prompts = prompts_for(cfg, 41, 5)
    with paged_server(cfg, api, params, name="exh", n_blocks=10,
                      prefix=False, max_wait_ms=2.0) as srv:
        handles = [srv.submit(p, 6) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
        s = srv.stats()
    for p, got in zip(prompts, results):
        np.testing.assert_array_equal(got, reference(p, 6))
    assert s["completed"] == 5
    assert s["deferred"] >= 1, s


def test_oversize_request_rejected_at_submit(model):
    """A request whose forecast depth exceeds the whole pool can never be
    served: rejected at submit with AdmissionError, queue untouched."""
    cfg, api, params = model
    with paged_server(cfg, api, params, name="rej", n_blocks=5, max_batch=2,
                      max_new_cap=16) as srv:
        h = srv.submit(prompts_for(cfg, 51, 1)[0], 16)
        assert h.done() and h.rejected
        with pytest.raises(AdmissionError, match="blocks"):
            h.result()
        assert srv.stats()["rejected"] == 1


def test_paged_config_validation(model):
    cfg, api, params = model
    # Multi-group paged serving requires per-group pools: slot-splitting a
    # single pool (group_batches=False) names the missing capability.
    with pytest.raises(ValueError, match="per-group block pools"):
        InferenceServer(cfg, api, params, paged=PagedSpec(),
                        groups=[DeviceGroup("a"), DeviceGroup("b")],
                        group_batches=False)
    # An adaptive scheduler + paged pool is legal now (placement follows
    # observed rates); it must construct and shut down cleanly.
    srv = InferenceServer(cfg, api, params, paged=PagedSpec(),
                          scheduler=Dynamic(2), buckets=(PLEN,))
    srv.close()
    kcfg = dataclasses.replace(cfg, kernel_impl="pallas_interpret")
    with pytest.raises(ValueError, match="decode_block"):
        InferenceServer(kcfg, api, params, paged=PagedSpec(block_len=4))


def test_pool_admission_and_blocks_needed_units():
    adm = PoolAdmission()
    assert adm.admit_submit(4, 4) and not adm.admit_submit(5, 4)
    assert adm.admit_board(2, 2.0) and not adm.admit_board(3, 2.0)
    import math

    assert adm.admit_board(10**9, math.inf)  # contiguous: never defers
    # full cache: prompt + every decode-segment position, in blocks
    assert blocks_needed(8, 1, 2, 4) == 2      # prefill only
    assert blocks_needed(8, 6, 2, 4) == 4      # 8 + 3 segments * 2 = 14
    assert blocks_needed(8, 6, 2, 16) == 1
    # rolling window reserves the ring
    assert blocks_needed(8, 6, 2, 4, window=8, max_seq=14) == 2


def test_block_pool_units():
    pool = BlockPool(8, block_len=4, bytes_per_block=100)  # capacity 6
    a = pool.alloc(3)
    assert pool.in_use == 3 and pool.free_count == 3
    pool.incref([a[0]])
    pool.release(a)
    assert pool.in_use == 1  # a[0] still referenced
    pool.release([a[0]])
    assert pool.in_use == 0 and pool.peak_in_use == 3
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(7)
    # prefix registration pins blocks; pressure evicts LRU pins
    b = pool.alloc(2)
    pool.register_prompt(b"p1", b, 7)
    pool.release(b)  # request exits; cache pin keeps them
    assert pool.in_use == 2 and pool.reclaimable() == 2
    assert pool.lookup_prompt(b"p1") is not None
    c = pool.alloc(6)  # forces eviction of the cached pair
    assert len(c) == 6 and pool.lookup_prompt(b"p1") is None
    pool.release(c)


# ----------------------------------------------------------- memory metrics
def test_paged_allocated_bytes_strictly_below_contiguous(model, reference):
    """Equal load, equal geometry, max_new_cap above the replayed gen: the
    contiguous layout allocates every slot at capacity, the pool allocates
    recorded depth — paged KV allocated-bytes strictly below contiguous."""
    cfg, api, params = model
    prompts = prompts_for(cfg, 61, 6)

    def run(paged):
        srv = InferenceServer(
            cfg, api, params, groups=[DeviceGroup("memA" if paged else "memB")],
            scheduler=Static(), buckets=(PLEN,), max_batch=4, seg_len=2,
            max_new_cap=12, max_wait_ms=5.0,
            paged=PagedSpec(block_len=4) if paged else None,
        )
        with srv:
            handles = [srv.submit(p, 6) for p in prompts]
            for p, h in zip(prompts, handles):
                np.testing.assert_array_equal(h.result(timeout=300),
                                              reference(p, 6))
            return srv.stats()["memory"]

    paged = run(True)
    contiguous = run(False)
    assert paged["kv_bytes_allocated"] < contiguous["kv_bytes_allocated"], (
        paged, contiguous
    )
    assert paged["kv_bytes_touched"] > 0 and contiguous["kv_bytes_touched"] > 0


def test_metrics_expose_pool_and_per_run_transfers(model):
    """InferenceServer.metrics reports pool utilization; RunHandle.metrics
    (via the Introspector) reports per-run transfer/cache-hit counters."""
    cfg, api, params = model
    p = prompts_for(cfg, 81, 1)[0]
    with paged_server(cfg, api, params, name="met") as srv:
        srv.submit(p, 4).result(timeout=300)
        m = srv.metrics()
    for key in ("blocks_in_use", "blocks_free", "blocks_peak", "prefix_hits",
                "cow", "kv_bytes_allocated", "kv_bytes_touched"):
        assert key in m["memory"], (key, m["memory"])
    assert m["memory"]["blocks_free"] > 0
    assert "met" in m["groups"] and "transfers" in m["groups"]["met"]

    # Per-run counters straight from the runtime: first run uploads, a
    # rerun on unchanged buffers serves from the device-resident cache.
    g = DeviceGroup("runmet")
    rt = Runtime([g])
    try:
        x = np.arange(64, dtype=np.float32)

        def kern(offset, a):
            return a * np.float32(2.0)

        prog = Program().in_(x).out(np.zeros(64, np.float32))
        prog.kernel(kern).work_items(64, 1)
        h1 = rt.submit(prog, Static())
        h1.result()
        t1 = h1.metrics["transfers"]["runmet"]
        assert t1["transfers"] >= 1
        h2 = rt.submit(prog, Static())
        h2.result()
        t2 = h2.metrics["transfers"]["runmet"]
        assert t2["cache_hits"] >= 1, t2
    finally:
        rt.shutdown()


# ------------------------------------------------------- rolling-window mode
@pytest.mark.parametrize("paged", [False, True],
                         ids=["contiguous", "paged"])
def test_rolling_window_through_server(model, paged):
    """Rolling (sliding-window) caches through the full server decode path:
    window masking × slot reuse × both memory layouts, bit-identical to
    one-shot generate on the same windowed config.  (Previously only
    exercised at the kernel level.)"""
    cfg0, api, params = model
    cfg = dataclasses.replace(cfg0, window=8)
    gen = make_generate(cfg, api)
    prompts = prompts_for(cfg, 91, 5)
    spec = PagedSpec(block_len=4) if paged else None
    with InferenceServer(cfg, api, params, groups=[DeviceGroup(f"win{paged}")],
                         scheduler=Static(), buckets=(PLEN,), max_batch=2,
                         seg_len=2, max_new_cap=8, max_wait_ms=2.0,
                         paged=spec) as srv:
        # two waves of joins so reused slots decode over wrapped rings
        handles = [srv.submit(p, 6) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
        s = srv.stats()
    assert s["completed"] == 5
    for p, got in zip(prompts, results):
        want = np.asarray(gen(params, {"tokens": jnp.asarray(p[None])}, 6))[0]
        np.testing.assert_array_equal(got, want)
    if paged:
        assert s["memory"]["mode"] == "paged"
        # prefix sharing is disabled for rolling caches (in-place ring
        # overwrites would mutate shared blocks)
        assert s["memory"]["blocks_cached"] == 0


def test_rolling_window_paged_pallas_kernel(model):
    """Window masking through the paged Pallas kernel path."""
    cfg0, api, params = model
    cfg = dataclasses.replace(cfg0, window=8, kernel_impl="pallas_interpret",
                              decode_block=4)
    gen = make_generate(cfg, api)
    p = prompts_for(cfg, 95, 1)[0]
    with paged_server(cfg, api, params, name="winpal", max_batch=2,
                      max_new_cap=6) as srv:
        got = srv.submit(p, 5).result(timeout=600)
    want = np.asarray(gen(params, {"tokens": jnp.asarray(p[None])}, 5))[0]
    np.testing.assert_array_equal(got, want)

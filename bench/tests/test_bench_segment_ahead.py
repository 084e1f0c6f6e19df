"""``segment_ahead_share`` (and its ``.chat`` twin) on hand-made spans and
on the spans a served wave records: the share of the window's segments the
runtime dispatched ahead, None where the program put no ``ahead`` arg."""
import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_stats import ctx, read, rec

NAMES = ("segment_ahead_share", "segment_ahead_share.chat")
REQ = rec(0.0, first=5.0, done=15.0, gen=11)


def X(seq, t0, t1, name, **args):
    return (seq, t0, t1, "X", name, "batcher", None, args or None)


@pytest.mark.parametrize("name", NAMES)
def test_hand_computed_share(name):
    spans = [X(0, 101.0, 101.5, "segment", ahead=0, queued_s=0.0),
             X(1, 102.0, 102.5, "segment", ahead=1, queued_s=0.4),
             X(2, 103.0, 103.5, "segment", ahead=1, queued_s=0.4),
             X(3, 104.0, 104.5, "segment", ahead=1, queued_s=0.4),
             X(4, 99.0, 99.5, "segment", ahead=0),  # before the window
             X(5, 111.0, 111.5, "segment", ahead=0),  # after it
             X(6, 105.0, 105.5, "prefill_wave", ahead=0)]
    assert read(name, ctx([REQ], spans=spans)) == pytest.approx(75.0)


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_arg(name):
    assert read(name, ctx([REQ], spans=[])) is None
    bare = [X(0, 101.0, 101.5, "segment", queued_s=0.1)]
    assert read(name, ctx([REQ], spans=bare)) is None


def test_recorded_wave():
    """A traced wave on the CPU: every segment but the first after each
    join is dispatched ahead of the one before it."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.core import DeviceGroup, Static
    from repro.core.trace import Tracer, set_tracer
    from repro.models import get_model
    from repro.models import params as P
    from repro.serve import InferenceServer

    cfg = reduced(get_config("qwen1.5-4b"))
    api = get_model(cfg)
    params = P.materialize(api.param_spec(cfg, 1), jax.random.PRNGKey(0),
                           jnp.float32)
    rng = np.random.default_rng(1)
    tr = set_tracer(Tracer(capacity=1 << 15, enabled=True))
    try:
        t0 = tr.now()
        with InferenceServer(cfg, api, params, groups=[DeviceGroup("rec")],
                             scheduler=Static(), buckets=(8,), max_batch=4,
                             seg_len=2, max_new_cap=12,
                             max_wait_ms=1000.0) as srv:
            hs = [srv.submit(rng.integers(0, cfg.vocab, 8).astype(np.int32),
                             12) for _ in range(4)]
            for h in hs:
                h.result(timeout=300)
        spans = tr.events()
    finally:
        set_tracer(Tracer(enabled=False))
    c = harness.Context(requests=[], window_perf=(t0, tr.now()), spans=spans)
    ahead = [e[7]["ahead"] for e in spans if e[3] == "X"
             and e[4] == "segment"]
    assert ahead[0] == 0 and sum(ahead) >= 3
    for name in NAMES:
        assert read(name, c) == pytest.approx(100.0 * sum(ahead) / len(ahead))

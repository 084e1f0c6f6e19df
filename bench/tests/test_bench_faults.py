"""A run whose timed path is broken underneath comes out not correct: a
token altered where it is produced, in the decode step and in the prefill."""
import io
import time

import jax
import pytest

from bench import harness
from bench.tests.util import tiny_root

SEED = 2**31 + 103


def altered(make):
    """A step factory whose step returns the next token id instead."""
    def factory(cfg, api):
        step = make(cfg, api)

        def broken(*args):
            tok, cache = step(*args)
            return (tok + 1) % cfg.vocab, cache
        return broken
    return factory


@pytest.mark.parametrize("where", ["make_decode_step", "make_prefill_step"])
def test_altered_token_is_not_correct(where, tmp_path, monkeypatch):
    import repro.serve.batcher as batcher

    monkeypatch.setattr(batcher, where, altered(getattr(batcher, where)))
    cell = harness.resolve("tiny.chat", root=tiny_root(str(tmp_path)))
    line = harness.run(cell, SEED, 1.0, False, jax.devices()[0],
                       time.monotonic(), checks_out=io.StringIO())
    assert not line["correct"], line["checks"]
    assert line["checks"]["failed_requests"]["value"] == 0

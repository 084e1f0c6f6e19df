"""The one traffic generator: turns a traffic file's parameters and a seed
into the requests of a run.

The sizes, their order and the arrival times are drawn from the file's
``shape_seed``; the run's ``--seed`` draws the token ids (and the weights).
So every seed gives the same work at the same times, with other tokens: the
dense model's time does not depend on the ids, so runs of different seeds
differ only by the system's own jitter, and two runs of one seed are
identical.

Open loop (``"loop": "open"``): ``rate_rps`` arrivals per second, Poisson, in
a ramp of ``ramp_s`` seconds and then the window; the arrivals of each part
are that part's count of exponential gaps, scaled to its length.
Closed loop (``"loop": "closed"``): ``clients`` each send their next request
when the previous one completes, drawing from a pool of ``pool`` requests.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # int32 token ids, unpadded
    gen: int            # output tokens asked for
    at: Optional[float] = None  # open loop: send time, seconds from ramp start
    in_window: bool = False


@dataclasses.dataclass
class Plan:
    loop: str
    requests: List[Request]  # open: in send order; closed: the pool, in order
    clients: int = 0


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from a length spec: ``lognormal`` (median,
    sigma) or ``uniform``, clipped to [min, max] inclusive."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _arrivals(gaps: np.ndarray, span: float) -> np.ndarray:
    """Arrival times of ``len(gaps) - 1`` requests in ``[0, span)`` from
    gaps scaled to fill the span (uniform order statistics, i.e. Poisson
    conditioned on the count)."""
    return np.cumsum(gaps / gaps.sum() * span)[:-1]


def make_plan(traffic: dict, seed: int, seconds: float, vocab: int) -> Plan:
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    toks = np.random.default_rng([int(seed), 2])
    loop = traffic["loop"]
    if loop == "open":
        rate, ramp = float(traffic["rate_rps"]), float(traffic["ramp_s"])
        parts = [(ramp, 0.0, False), (float(seconds), ramp, True)]
    elif loop == "closed":
        parts = [(None, None, False)]
    else:
        raise ValueError(f"unknown loop {loop!r}")
    reqs: List[Request] = []
    for span, start, in_window in parts:
        n = (int(traffic["pool"]) if span is None
             else max(1, round(rate * span)))
        plen = draw_lengths(traffic["prompt_len"], n, shape)
        gen = draw_lengths(traffic["output_len"], n, shape)
        at = [None] * n
        if span is not None:
            at = start + _arrivals(shape.exponential(1.0, n + 1), span)
        for i in range(n):
            prompt = toks.integers(0, vocab, int(plen[i])).astype(np.int32)
            reqs.append(Request(prompt, int(gen[i]),
                                None if at[i] is None else float(at[i]),
                                in_window))
    return Plan(loop, reqs, int(traffic.get("clients", 0)))

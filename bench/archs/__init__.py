"""Architecture modules: ``archs/<name>.py``, one per model family the
benchmark can run, chosen by a configuration file's ``program.bench_arch``.

A module gives everything of the benchmark that depends on the layers'
equations, each from the configuration dict ``c`` alone:

- ``program_config(c, cache_dtype)``: the program's ``ModelConfig``;
- ``layout(c)``: name -> (shape, kind, std) of every weight leaf, which
  ``weights.py`` draws;
- ``score(c, seed, tokens, targets, control)``: the float32 reference's gaps
  (and the float8 control's), as ``reference.score`` computes them;
- ``prefill_flops(c, prompt_len)`` and ``decode_flops(c, context)``: the
  useful operations that ``metrics/mfu.py`` counts.

A module is loaded by its file's path, so dropping ``archs/<name>.py`` in is
all that a new architecture needs.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from typing import List

ARCHS = os.path.dirname(os.path.abspath(__file__))


def known(d: str = ARCHS) -> List[str]:
    """Names of the architecture modules in ``d``."""
    return sorted(f[:-3] for f in os.listdir(d)
                  if f.endswith(".py") and not f.startswith("_"))


def load(c: dict, d: str = ARCHS):
    """The module that ``c["program"]["bench_arch"]`` names, from ``d``; a
    missing or unknown name is an error that lists the known ones."""
    name = c.get("program", {}).get("bench_arch")
    names = known(d)
    if name not in names:
        raise KeyError(f"the configuration's program.bench_arch is {name!r}; "
                       f"known architecture modules: {names}")
    return _load(os.path.join(d, name + ".py"))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    # One module object per file: its jitted reference functions are cached
    # on it, so a second load must not compile them again.
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

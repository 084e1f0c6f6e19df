"""``segment_ahead_share`` in the cells judged on the token gap: the same
reading, under a name of its own because those cells report
``tpot_p90_ms`` and no ``tokens_per_s``."""
from bench.metrics.segment_ahead_share import read  # noqa: F401

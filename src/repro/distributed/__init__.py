from repro.distributed.sharding import (  # noqa: F401
    batch_axes,
    current_mesh,
    maybe_axis,
    set_current_mesh,
    shard,
)

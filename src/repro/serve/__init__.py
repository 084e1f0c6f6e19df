from repro.serve.admission import (  # noqa: F401
    DeadlineAdmission,
    PoolAdmission,
    ServiceModel,
    SpecGate,
    edf_key,
)
from repro.serve.batcher import (  # noqa: F401
    BatchGroup,
    Buckets,
    ModelKernels,
    chunks_for,
    segments_for,
    spec_segments_for,
)
from repro.serve.multigroup import (  # noqa: F401
    ForceMigrate,
    MigrationPolicy,
    RateBalancer,
    plan_wave,
    proportional_split,
)
from repro.serve.paged import (  # noqa: F401
    BlockPool,
    PagedBatchGroup,
    PagedSpec,
    blocks_needed,
    validate_paged,
)
from repro.serve.server import (  # noqa: F401
    AdmissionError,
    InferenceServer,
    RequestHandle,
    ServeError,
    validate_chunked,
    validate_draft,
)
from repro.serve.http import ObsHTTP  # noqa: F401
from repro.serve.telemetry import (  # noqa: F401
    Ema,
    RollingStat,
    Telemetry,
    parse_exposition,
    quantile,
)
from repro.serve.step import (  # noqa: F401
    DraftSpec,
    cache_batch_axes,
    make_chunk_step,
    make_decode_chain,
    make_decode_step,
    make_draft_verify_step,
    make_generate,
    make_prefill_step,
    make_scored_continuation,
    zeros_cache,
)

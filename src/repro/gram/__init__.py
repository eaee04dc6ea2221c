"""Gram service: streaming, batched, autotuned A^tA serving.

The layer between the fused ATA kernel and the world (DESIGN.md §10):

- ``stream``   — online accumulator: C += chunk^t chunk in packed
                 lower-triangular state, plus a reduce-scatter-sharded
                 variant that never replicates C.
- ``engine``   — ``GramEngine``: slot-based continuous batching of
                 heterogeneous Gram requests, power-of-two shape buckets,
                 one cached executable per bucket.
- ``autotune`` — per-(bucket, dtype, backend) search over
                 mode x levels x blocks, persisted to
                 ``artifacts/autotune/gram_autotune.json`` and consulted
                 by ``kernels/ops.py`` for its defaults.
"""
from . import autotune, engine, stream  # noqa: F401
from .autotune import (  # noqa: F401
    autotune as autotune_bucket, bucket_shape, lookup as autotune_lookup,
    resolve_block_defaults,
)
from . import verify  # noqa: F401
from .engine import (  # noqa: F401
    BucketHealth, EngineShutdown, GramEngine, GramFuture, GramRequest,
    GramServeError, Overloaded, TenantState,
)
from .stream import (  # noqa: F401
    GramStream, init as stream_init, update as stream_update,
    finalize as stream_finalize,
    GramStackStream, stack_init, stack_update, stack_finalize,
    sharded_init, update_sharded,
    distributed_init, distributed_update, distributed_finalize,
    CheckpointedGramStream,
)
from .verify import (  # noqa: F401
    GramVerdict, VerificationError, freivalds_gram, verify_gram,
)

__all__ = [
    "autotune", "engine", "stream", "verify",
    "autotune_bucket", "bucket_shape", "autotune_lookup",
    "resolve_block_defaults",
    "GramEngine", "GramRequest", "GramFuture", "BucketHealth",
    "TenantState", "GramServeError", "Overloaded", "EngineShutdown",
    "GramStream", "stream_init", "stream_update", "stream_finalize",
    "GramStackStream", "stack_init", "stack_update", "stack_finalize",
    "sharded_init", "update_sharded",
    "distributed_init", "distributed_update", "distributed_finalize",
    "CheckpointedGramStream",
    "GramVerdict", "VerificationError", "freivalds_gram", "verify_gram",
]

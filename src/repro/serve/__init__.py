"""Query-time inference over precomputed fast-SPSD factors.

``build_artifact`` (training side) -> ``save_artifact``/``load_or_rebuild``
(warm-boot factor store on ``repro.checkpoint``) -> ``serve_kernel_model``
(one rectangular fused cross-kernel launch per query bucket).  The
continuous-batching production loop lives in ``repro.launch.serve_kernel``;
appended-row maintenance (one thin launch per batch, delta checkpoints,
staleness-triggered re-sketch) lives in ``repro.serve.incremental``.
"""
from repro.serve.artifact import (  # noqa: F401
    TASKS,
    KernelModelArtifact,
    artifact_from_tree,
    artifact_to_tree,
    build_artifact,
    load_artifact,
    load_or_rebuild,
    save_artifact,
)
from repro.serve.engine import (  # noqa: F401
    QueryRequest,
    QueryResult,
    answer_batch,
    dense_krr_oracle,
    dense_oracle,
    krr_reference,
    parity_gap,
    plan_buckets,
    serve_kernel_model,
)
from repro.serve.incremental import (  # noqa: F401
    DeltaRecord,
    GenerationStats,
    IncrementalMaintainer,
    IncrementalState,
    StalenessPolicy,
    append_rows,
    compact,
    gc_superseded_deltas,
    init_state,
    is_delta_step,
    load_artifact_chain,
    load_chain,
    save_delta,
)

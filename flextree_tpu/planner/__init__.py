"""Offline planner: candidate enumeration + TPU cost model + chooser.

The rebuild of the reference's ``cost_model/`` + ``topo_count/`` subsystems.
Unlike the reference (where the planner is a separate binary whose printed
width vector a human pastes into ``FT_TOPO``, SURVEY §1), ours is importable
by the runtime — ``choose_topology(...).topology`` drops straight into
``allreduce(topo=...)`` — while remaining usable offline via
``python -m flextree_tpu.planner``.  A native C++ core (``native/``)
accelerates the enumeration/argmin path, with this package as the
pure-Python fallback and ground truth.
"""

from .cost_model import (
    CostBreakdown,
    DCN_DEFAULT,
    ICI_DEFAULT,
    LinkParams,
    TpuCostParams,
    allreduce_cost,
    bus_bandwidth_GBps,
    ring_cost,
)
from .calibrate import (
    CALIBRATION_SCHEMA,
    MeasuredPoint,
    backend_fingerprint,
    default_params,
    feature_vector,
    fit_cost_params,
    load_calibration,
    plan_cache_key,
    predict_us,
    save_calibration,
    spearman,
)
from .autotune import (
    DEFAULT_CODECS,
    TunedPlan,
    analytic_shortlist,
    autotune_plan,
    invalidate_plan_cache,
)
from .feedback import (
    DriftDetector,
    FeedbackConfig,
    FeedbackController,
    FeedbackRefused,
    ProbePoint,
    ReplanDecision,
    cache_invalidation_predicate,
    extract_residuals,
    fit_from_samples,
)
from .choose import (
    Candidate,
    Plan,
    candidate_topologies,
    choose_bucket_bytes,
    choose_in_place_bytes,
    choose_topology,
    replan_for_survivors,
)
from .factorize import (
    count_ordered_factorizations,
    is_prime,
    ordered_factorizations,
    ordered_factorizations_combinatoric,
    prime_factors,
)
from .shapes import format_shape, parse_shape, shape_taxonomy
from .native import (
    load_native,
    native_available,
    native_choose,
    native_count_shapes,
)

__all__ = [
    "CostBreakdown",
    "LinkParams",
    "TpuCostParams",
    "ICI_DEFAULT",
    "DCN_DEFAULT",
    "allreduce_cost",
    "ring_cost",
    "bus_bandwidth_GBps",
    "MeasuredPoint",
    "feature_vector",
    "fit_cost_params",
    "predict_us",
    "spearman",
    "save_calibration",
    "load_calibration",
    "default_params",
    "backend_fingerprint",
    "plan_cache_key",
    "CALIBRATION_SCHEMA",
    "TunedPlan",
    "analytic_shortlist",
    "autotune_plan",
    "invalidate_plan_cache",
    "DEFAULT_CODECS",
    "DriftDetector",
    "FeedbackConfig",
    "FeedbackController",
    "FeedbackRefused",
    "ProbePoint",
    "ReplanDecision",
    "cache_invalidation_predicate",
    "extract_residuals",
    "fit_from_samples",
    "Candidate",
    "Plan",
    "candidate_topologies",
    "choose_bucket_bytes",
    "choose_in_place_bytes",
    "choose_topology",
    "replan_for_survivors",
    "count_ordered_factorizations",
    "is_prime",
    "ordered_factorizations",
    "ordered_factorizations_combinatoric",
    "prime_factors",
    "format_shape",
    "parse_shape",
    "shape_taxonomy",
    "load_native",
    "native_available",
    "native_choose",
    "native_count_shapes",
]

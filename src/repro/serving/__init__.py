from .planner import (
    LANE_GENERAL,
    LANE_LANDMARK_PAIR,
    LANE_NAMES,
    LANE_ONE_SIDED,
    LANE_TRIVIAL,
    QueryPlan,
    merge_plans,
    plan_from_pairs,
    plan_queries,
)
from .serve_step import (
    make_spg_serve_step,
    serve_spg_batch,
)
from .clock import ManualClock, SystemClock
from .metrics import (
    LatencyHistogram,
    MetricsRegistry,
    merged_latency,
    serve_metrics,
)
from .replicas import ReplicaRouter
from .service import ResultCache, ServingService, round_chunk_to_shards
from .stream import AdmissionPolicy, QoSClass, QueryFuture, StreamingService

__all__ = [
    "AdmissionPolicy",
    "LatencyHistogram",
    "ManualClock",
    "MetricsRegistry",
    "QoSClass",
    "ReplicaRouter",
    "SystemClock",
    "LANE_GENERAL",
    "LANE_LANDMARK_PAIR",
    "LANE_NAMES",
    "LANE_ONE_SIDED",
    "LANE_TRIVIAL",
    "QueryFuture",
    "QueryPlan",
    "ResultCache",
    "ServingService",
    "StreamingService",
    "make_spg_serve_step",
    "merge_plans",
    "merged_latency",
    "plan_from_pairs",
    "plan_queries",
    "round_chunk_to_shards",
    "serve_metrics",
    "serve_spg_batch",
]

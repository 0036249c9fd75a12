"""Serving engines (LM continuous batching + DCNN bucketed plan/execute,
with typed fault/deadline semantics) and the SLO-aware async frontend
(admission control, EDF scheduling, graceful precision degradation)."""
from .admission import AdmissionController, TenantClass
from .config import EngineConfig
from .engine import (DcnnServeEngine, Request, ServeEngine, pow2_buckets,
                     shard_aligned_buckets)
from .errors import (AdmissionRejected, DeadlineExceeded, EngineDegraded,
                     EngineError, PrecisionUnsupported)
from .frontend import AsyncServeFrontend
from .scheduler import EdfScheduler, ServiceModel

__all__ = [
    "EngineConfig", "DcnnServeEngine", "Request", "ServeEngine",
    "pow2_buckets", "shard_aligned_buckets",
    "AsyncServeFrontend", "TenantClass", "AdmissionController",
    "EdfScheduler", "ServiceModel",
    "AdmissionRejected", "DeadlineExceeded", "EngineDegraded", "EngineError",
    "PrecisionUnsupported",
]

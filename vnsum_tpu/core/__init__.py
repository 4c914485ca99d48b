from .config import (
    ApproachName,
    EvalConfig,
    GenerationConfig,
    PipelineConfig,
    approach_defaults,
)
from .faults import (
    FaultInjectingBackend,
    FaultPlan,
    FaultRule,
    RetryingBackend,
    call_with_retries,
)
from .logging import get_logger, setup_run_logging
from .profiling import Tracer, device_profile, host_span
from .results import DocumentRecord, ModelRunRecord, PipelineResults

__all__ = [
    "Tracer",
    "device_profile",
    "host_span",
    "ApproachName",
    "EvalConfig",
    "GenerationConfig",
    "PipelineConfig",
    "approach_defaults",
    "FaultInjectingBackend",
    "FaultPlan",
    "FaultRule",
    "RetryingBackend",
    "call_with_retries",
    "get_logger",
    "setup_run_logging",
    "DocumentRecord",
    "ModelRunRecord",
    "PipelineResults",
]

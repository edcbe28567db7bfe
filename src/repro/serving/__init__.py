"""The consolidated serving API (DESIGN.md §16).

One import surface for everything a serving caller needs: the engine and its
grouped reliability configuration, the request/report protocol types, and the
decode-block helper contract external factories implement. Submodules stay
importable directly (``repro.serving.engine`` etc.) — this package re-exports
the stable names so callers stop reaching into module internals:

    from repro.serving import ServingEngine, ReliabilityConfig, Request

Import order matters here: ``engine`` imports ``scheduler``/``steps``, so the
protocol layers load first (keeps the package safe to import from any entry
point, including ``repro.serving.scheduler`` itself).
"""

from repro.obs import MetricsRegistry, TraceRecorder
from repro.serving.scheduler import (
    ContinuousBatchingScheduler,
    MeshServeReport,
    Request,
    RequestState,
    ServeReport,
    normalize_requests,
    partition_requests,
    serve_stream,
)
from repro.serving.steps import (
    DecodeBlockHelpers,
    HelpersFactory,
    PagedHelpers,
    make_paged_helpers,
    make_prefill_step,
    make_serve_step,
)
from repro.serving.engine import (
    CanaryConfig,
    FaultModelConfig,
    ProtectionConfig,
    RailsConfig,
    ReliabilityConfig,
    ReliabilityConfigError,
    ServingEngine,
)

__all__ = [
    "CanaryConfig",
    "ContinuousBatchingScheduler",
    "DecodeBlockHelpers",
    "FaultModelConfig",
    "HelpersFactory",
    "MeshServeReport",
    "MetricsRegistry",
    "PagedHelpers",
    "ProtectionConfig",
    "RailsConfig",
    "ReliabilityConfig",
    "ReliabilityConfigError",
    "Request",
    "RequestState",
    "ServeReport",
    "ServingEngine",
    "TraceRecorder",
    "make_paged_helpers",
    "make_prefill_step",
    "make_serve_step",
    "normalize_requests",
    "partition_requests",
    "serve_stream",
]

from repro.serving.api import (RagRequest, RagResponse, ReplicaTelemetry,
                               ServerTelemetry, TeleRAGServer,
                               TenantTelemetry, WaveDispatch,
                               summarize_latency)
from repro.serving.engine import (EngineConfig, RequestResult, RoundTelemetry,
                                  TeleRAGEngine)
from repro.serving.chunk_kv import (ChunkKVCache, ChunkKVStats,
                                    ChunkResidency)
from repro.serving.decode import DecodeRunner, supports_paged_decode
from repro.serving.kv_cache import (CacheLease, KVCacheManager, KVPageSlab,
                                    PagedCacheLease)
from repro.serving.policies import (LatencyContext, RetrievalPolicy,
                                    get_policy, policy_names,
                                    register_policy)
from repro.serving.runtime import (DecodeEvent, RequestRecord, RequestState,
                                   RetrievalRuntime, Span, latency_summary)
from repro.serving.sampler import sample
from repro.serving.trace import (PIPELINES, RequestTrace, StageTrace,
                                 calibration_windows, make_trace, make_traces)

__all__ = [
    "RagRequest", "RagResponse", "ReplicaTelemetry", "ServerTelemetry",
    "TeleRAGServer", "TenantTelemetry", "WaveDispatch", "summarize_latency",
    "EngineConfig", "RequestResult", "RoundTelemetry", "TeleRAGEngine",
    "ChunkKVCache", "ChunkKVStats", "ChunkResidency",
    "DecodeRunner", "supports_paged_decode",
    "CacheLease", "KVCacheManager", "KVPageSlab", "PagedCacheLease",
    "LatencyContext", "RetrievalPolicy", "get_policy", "policy_names",
    "register_policy",
    "DecodeEvent", "RequestRecord", "RequestState", "RetrievalRuntime",
    "Span", "latency_summary",
    "sample",
    "PIPELINES", "RequestTrace", "StageTrace", "calibration_windows",
    "make_trace", "make_traces",
]

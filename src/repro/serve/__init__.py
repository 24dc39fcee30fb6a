"""repro.serve subpackage: workload-agnostic continuous batching.

Engine (scheduler) x Workload (StemmerWorkload | TextAnalysisWorkload)
+ DictStore (versioned hot-swappable stemmer dictionaries). ``faults``
supplies the deterministic fault-injection harness
(FaultPlan/FaultInjector) and the structured FailureInfo that
terminally failed requests carry. ``journal`` is the
write-ahead request log behind ``Engine.recover`` (crash-safe warm
restart); ``health`` is the structured event stream plus the
graceful-degradation ladder (DESIGN.md §12). ``spans`` names the
profiler spans the engine and the text front end open.
"""
from repro.serve.dict_store import (DictSnapshotError, DictStore,
                                    DictValidationError, DictVersion,
                                    validate_handle)
from repro.serve.engine import (DrainReport, Engine, EngineUndrained,
                                InflightTile, QueueFull, StemRequest,
                                StemmerWorkload, Workload)
from repro.serve.faults import (DeviceLost, FailureInfo, FaultInjector,
                                FaultPlan, FaultSpec, InjectedFault)
from repro.serve.health import (DegradationPolicy, EngineEvent, EventLog,
                                ServingMode, build_ladder)
from repro.serve.journal import (Journal, JournalError, RecoveryReport,
                                 payload_digest, response_digest)
from repro.serve.text import TextAnalysisWorkload, TextRequest

__all__ = [
    "DegradationPolicy", "DeviceLost", "DictSnapshotError", "DictStore",
    "DictValidationError", "DictVersion", "DrainReport", "Engine",
    "EngineEvent", "EngineUndrained", "EventLog", "FailureInfo",
    "FaultInjector", "FaultPlan", "FaultSpec", "InflightTile",
    "InjectedFault", "Journal", "JournalError", "QueueFull",
    "RecoveryReport", "ServingMode", "StemRequest", "StemmerWorkload",
    "TextAnalysisWorkload", "TextRequest", "Workload", "build_ladder",
    "payload_digest", "response_digest", "validate_handle",
]

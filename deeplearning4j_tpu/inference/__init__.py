"""Production inference engine: continuous micro-batching, slot-based
generative decode scheduling, prefix KV reuse, and SLO metrics.

The pieces compose into the serving stack (`serving/server.py`):
`MicroBatcher` aggregates concurrent `/predict` requests into bucketed
padded batches; `DecodeScheduler` continuously batches generative decode
over the attention KV cache — paged (`kv_pool_mb`: all slots share one
`KVPool` block pool through per-slot block tables, with zero-copy prefix
restore/publish and preempt-and-swap under pool pressure) or contiguous
per-slot stripes with no prefix cache; `MetricsRegistry`
records queue depth, batch occupancy, hit rates, pool occupancy, and
latency percentiles, exported at
`GET /metrics`; the `FlightRecorder` span flight recorder (`trace.py`)
records every request's lifecycle — queued/restore/prefill/decode span
trees plus scheduler instants — exported at `GET /trace` (JSON or
Perfetto-loadable Chrome trace-event format). With ``mesh=N``
(`sharding.py`) the whole decode stack runs tensor-parallel over a
``tp`` device mesh: heads/FFN sharded, KV pool head-sharded (per-device
byte budgets — ``tp×`` the blocks at fixed per-device HBM), block
tables replicated, and the per-token program audited to carry only the
Megatron all-reduces (no resharding collectives on the hot path).
"""
from .batcher import (InferenceFuture, MicroBatcher, QueueFullError,
                      RequestTimeoutError, bucket_for, pow2_buckets)
from .engine import (DecodeHandle, DecodeScheduler, EngineCrashedError,
                     LoadSheddedError, PromptTooLongError)
from .failpoints import (InjectedCrash, InjectedFault, InjectedHang,
                         InjectedOOM)
from .kvpool import KVPool
from .logitproc import (CompiledGrammar, GrammarError, LogitState,
                        StopMatcher, TokenStream, admit_all,
                        compile_json_schema, compile_trie)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry)
from .profiler import SLOMonitor, StepPhaseProfiler, program_costs
from .sharding import (TP_AXIS, collective_counts, decode_mesh,
                       decode_program_hlo, draft_program_hlo,
                       prefill_program_hlo, verify_program_hlo)
from .speculative import ForkGroup, build_shallow_draft
from .supervisor import (AdmissionRejectedError, EngineSupervisor,
                         RetryBudgetExceededError, ShuttingDownError)
from .trace import FlightRecorder, default_recorder, new_request_id

__all__ = ["AdmissionRejectedError", "CompiledGrammar", "Counter",
           "DecodeHandle",
           "DecodeScheduler", "EngineCrashedError", "EngineSupervisor",
           "FlightRecorder", "ForkGroup", "Gauge", "GrammarError",
           "Histogram", "InferenceFuture",
           "InjectedCrash", "InjectedFault", "InjectedHang", "InjectedOOM",
           "KVPool", "LoadSheddedError", "LogitState", "MetricsRegistry",
           "MicroBatcher",
           "PromptTooLongError", "QueueFullError", "RequestTimeoutError",
           "RetryBudgetExceededError", "SLOMonitor", "ShuttingDownError",
           "StepPhaseProfiler", "StopMatcher", "TP_AXIS", "TokenStream",
           "admit_all",
           "bucket_for", "build_shallow_draft", "collective_counts",
           "compile_json_schema", "compile_trie",
           "decode_mesh", "decode_program_hlo", "default_recorder",
           "default_registry", "draft_program_hlo",
           "new_request_id", "pow2_buckets", "prefill_program_hlo",
           "program_costs", "verify_program_hlo"]

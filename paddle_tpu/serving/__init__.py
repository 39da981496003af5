"""paddle_tpu.serving — continuous-batching inference above the executor.

The reference keeps inference hardware saturated with async
executors/DeviceWorkers around AnalysisPredictor (SURVEY §2.8); this
package is that layer rebuilt for the TPU decode path: a paged KV block
arena + page tables with hashed prefix sharing (refcounted blocks, LRU
cached prefixes, copy-on-write isolation) and O(buckets) compiled
shapes (`kv_cache`), an iteration-level scheduler that admits by pages
needed and interleaves suffix prefills with fused chunked decode over a
donated, device-resident pipeline — `decode_chunk` tokens per dispatch,
the next dispatch launched before the previous block is fetched, and
optionally budget-bounded CHUNKED PREFILL (`prefill_chunk`) so a long
prompt never stalls co-batched decode streams
(`scheduler`) — a request-lifecycle engine with bounded admission and
streaming callbacks (`engine`), and request/engine metrics incl. the
dispatch-amortization and block/prefix-cache series (`metrics`).

The engine knows no architecture: a model supplies its prefill, ONE decode
step, optionally a speculative verify pass, a description of its per-layer
cache state and the features it implements through `model.ServingModel`,
named by its config's `serving_model()` (the GPT family,
`models.gpt_decode`; Moonlight-16B-A3B, `models.moonlight`). The fused
loop around the step is the engine's, written once (`decode_loop`: scan,
sampling cadence, finish rule, drafter, named carry), and the sampler's
PRNG every model shares is `sampling`.

Entry points: `inference.create_engine(config, gpt_config)` to serve a
saved GPT model dir, or `ServingEngine(params, cfg)` over an in-memory
parameter pytree of the model that `cfg` names.
"""

from .adapters import (AdapterError, AdapterGeometryError, AdapterPool,
                       AdapterPoolFullError, AdapterReferencedError,
                       UnknownAdapterError, adapter_geometry,
                       make_adapter)
from .engine import (DEFAULT_RETRY_AFTER_S, EngineOverloadError,
                     GenerationRequest, ServingConfig, ServingEngine)
from .faults import FaultPlan, InjectedFault
from .kv_cache import ShapeBuckets, SlotKVCache
from .metrics import EngineMetrics, RequestMetrics
from .model import CacheSpec, ServingModel, serving_model
from .migration import (TICKET_VERSION, MigrationError, MigrationTicket,
                        TicketError)
from .scheduler import (ContinuousBatchingScheduler, SequenceEvent,
                        SwappedSequence)

__all__ = ["ServingEngine", "ServingConfig", "GenerationRequest",
           "EngineOverloadError", "DEFAULT_RETRY_AFTER_S",
           "ShapeBuckets", "SlotKVCache",
           "ContinuousBatchingScheduler", "SequenceEvent",
           "SwappedSequence", "FaultPlan", "InjectedFault",
           "EngineMetrics", "RequestMetrics",
           "MigrationTicket", "MigrationError", "TicketError",
           "TICKET_VERSION",
           "ServingModel", "CacheSpec", "serving_model",
           "AdapterPool", "AdapterError", "UnknownAdapterError",
           "AdapterGeometryError", "AdapterPoolFullError",
           "AdapterReferencedError", "adapter_geometry",
           "make_adapter"]

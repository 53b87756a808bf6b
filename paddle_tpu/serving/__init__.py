"""TPU-native serving engine (ISSUE 9, docs/serving.md).

The production-inference half of the north star: AOT-compiled prefill
(shape-bucketed ladder) and decode (static ``[max_batch]`` slot batch)
executables over a preallocated, donated paged KV cache; continuous / in-flight
batching at token boundaries; int8/bf16 serving weights through the
comm_opt chunk-scaled quantizer; an HTTP front door with admission
control, deadlines, backpressure and graceful drain. Steady state is
ZERO-recompile by construction — ``paddle_recompiles_total`` (PR 4) is
the enforced guardrail.

Quick start::

    from paddle_tpu.models import gpt
    from paddle_tpu import serving

    params = gpt.init_params(jax.random.PRNGKey(0), gpt.GPT_SMALL)
    engine = serving.DecodeEngine(
        params, gpt.GPT_SMALL,
        serving.EngineConfig(max_batch=8, max_seq=256,
                             weight_dtype="int8"))
    engine.warmup()                      # all compiles happen HERE
    sched = serving.Scheduler(engine)
    front = serving.FrontDoor(scheduler=sched, port=8866).start()
"""
from .engine import (  # noqa: F401
    DecodeEngine,
    EngineConfig,
    PromptTooLongError,
    default_bucket_ladder,
)
from .paged_kv import (  # noqa: F401
    CacheFullError,
    PagedKVCache,
    PagePoolFullError,
    PrefixCache,
)
from .sampling import GREEDY, SamplingParams  # noqa: F401
from .spec_decode import SpecDecodeEngine, SpecStats  # noqa: F401
from .quant import (  # noqa: F401
    INT8_LOGIT_TOL,
    INT8_PPL_REL_TOL,
    dequantize_params,
    logit_error_stats,
    quantize_params,
)
from .scheduler import (  # noqa: F401
    QueueFullError,
    Request,
    Scheduler,
    SchedulerConfig,
)
from .server import EngineLoop, FrontDoor, shed_decision  # noqa: F401
from .prefix_store import PrefixStore  # noqa: F401
from .replica import POISONED_EXIT_CODE, ReplicaRole  # noqa: F401
from .gang import (  # noqa: F401
    GangConfig,
    GangFrontDoor,
    ReplicaGang,
)
from .kv_transfer import (  # noqa: F401
    CacheConfigMismatch,
    KVTransferServer,
    adopt_into_engine,
    cache_fingerprint,
    export_slot,
)
from .disagg import (  # noqa: F401
    DisaggRouter,
    LocalReplica,
    SharedPrefixIndex,
)

__all__ = [
    "DecodeEngine", "EngineConfig", "PromptTooLongError",
    "default_bucket_ladder", "CacheFullError",
    "PagedKVCache", "PrefixCache", "PagePoolFullError",
    "SamplingParams", "GREEDY", "SpecDecodeEngine", "SpecStats",
    "quantize_params", "dequantize_params", "logit_error_stats",
    "INT8_LOGIT_TOL", "INT8_PPL_REL_TOL",
    "Scheduler", "SchedulerConfig", "Request", "QueueFullError",
    "FrontDoor", "EngineLoop", "shed_decision",
    "PrefixStore", "POISONED_EXIT_CODE", "ReplicaRole",
    "ReplicaGang", "GangConfig", "GangFrontDoor",
    "CacheConfigMismatch", "KVTransferServer", "cache_fingerprint",
    "export_slot", "adopt_into_engine",
    "DisaggRouter", "LocalReplica", "SharedPrefixIndex",
]

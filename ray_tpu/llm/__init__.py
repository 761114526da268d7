"""ray_tpu.llm — LLM serving and batch inference (reference: python/ray/llm).

The reference wraps vLLM's CUDA engine; on TPU this package IS the engine
(SURVEY §7.3): a continuous-batching scheduler over a paged KV cache with
jitted prefill/decode steps (see _internal/engine.py; the allocator and
prefix index are _internal/paged.py, the pool on the device and the
`paged_*` primitives ray_tpu/ops/paged_attention.py), deployed on
ray_tpu.serve replicas."""

from typing import Any, Dict, Optional

from ray_tpu.llm._internal.batch import (
    Processor,
    ProcessorConfig,
    build_llm_processor,
)
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
from ray_tpu.llm._internal.openai import OpenAIServer, build_openai_app
from ray_tpu.llm._internal.paged import PagedCacheConfig
from ray_tpu.llm._internal.server import GENERATE_TIMEOUT_S, LLMServer
from ray_tpu.llm._internal.tokenizer import (
    ByteBPETokenizer,
    apply_chat_template,
    get_tokenizer,
)
from ray_tpu.ops.paged_attention import (
    paged_attention,
    paged_gather,
    paged_write,
)


def build_llm_deployment(llm_config: Dict[str, Any], *,
                         num_replicas: int = 1,
                         name: Optional[str] = None,
                         num_tpus: float = 0.0):
    """serve Application hosting LLMServer replicas (reference:
    llm/_internal/serve/builders — build_llm_deployments)."""
    from ray_tpu import serve

    dep = serve.deployment(
        LLMServer,
        name=name or f"LLM:{llm_config.get('model', 'model')}",
        num_replicas=num_replicas,
        ray_actor_options={"num_cpus": 1.0, "num_tpus": num_tpus},
        max_ongoing_requests=int(llm_config.get("max_ongoing_requests", 32)),
        request_timeout_s=GENERATE_TIMEOUT_S,
    )
    return dep.bind(llm_config)


__all__ = [
    "ByteBPETokenizer",
    "EngineConfig",
    "LLMEngine",
    "LLMServer",
    "OpenAIServer",
    "apply_chat_template",
    "build_openai_app",
    "get_tokenizer",
    "PagedCacheConfig",
    "Processor",
    "ProcessorConfig",
    "Request",
    "build_llm_deployment",
    "build_llm_processor",
    "paged_attention",
    "paged_gather",
    "paged_write",
]

from ray_tpu._private.usage import record_library_usage as _rec

_rec("llm")
del _rec

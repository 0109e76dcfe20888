"""ray_tpu.llm: TPU-native LLM inference — paged KV cache, continuous
batching, serving (ref: python/ray/llm/ — which delegates to vLLM; here
the engine is native jax/XLA, SURVEY §2.4).

Names resolve on first use (PEP 562): a driver that only builds a
deployment (``build_llm_deployment``) must not import jax — on a TPU host
the chip belongs to the replica process, and the driver stays off it."""

import importlib

_EXPORTS = {
    "KVCache": ".cache", "PageAllocator": ".cache",
    "SequenceTable": ".cache", "init_kv_cache": ".cache",
    "EngineConfig": ".engine", "LLMEngine": ".engine",
    "StepOutput": ".engine",
    "SamplingParams": ".sampling",
    "LLMServer": ".serve", "build_llm_deployment": ".serve",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    value = getattr(importlib.import_module(module, __name__), name)
    globals()[name] = value
    return value

"""Model forward passes (functional JAX, stacked-layer scan).

Families dispatch on ``ModelConfig.architecture``: each module exposes
``init_params / prefill / decode`` with the same paged-cache signature so
the scheduler, prefix cache, KVBM and disaggregation drive any family
uniformly (the role vLLM's model registry plays for the reference's
engines)."""

from dynamo_tpu.engine.config import ModelConfig


def get_module(config: ModelConfig):
    if config.is_hybrid:
        # Layers of stated kinds (ModelConfig.layer_types): the layer-group
        # step programs, on the llama family's building blocks.
        from dynamo_tpu.engine.models import hybrid

        return hybrid
    if config.architecture == "llama":
        from dynamo_tpu.engine.models import llama

        return llama
    if config.architecture == "mla":
        from dynamo_tpu.engine.models import mla

        return mla
    raise ValueError(f"unknown architecture {config.architecture!r}")

"""LM substrate: configs, functional layers, and full-model assembly."""
from .config import (EncoderConfig, ModelConfig, MoEConfig, SSMConfig,
                     SHAPES, SHAPES_BY_NAME, ShapeConfig)
from .transformer import (block_apply, cache_spec_axes, decode_step, encode,
                          forward, init_cache, init_layer, init_model,
                          init_params, init_paged_cache, paged_copy_block,
                          paged_decode_step, paged_prefill_chunk,
                          param_count, prefill)

__all__ = [
    "EncoderConfig", "ModelConfig", "MoEConfig", "SSMConfig", "SHAPES",
    "SHAPES_BY_NAME", "ShapeConfig", "block_apply", "cache_spec_axes",
    "decode_step", "encode", "forward", "init_cache", "init_layer",
    "init_model", "init_params", "init_paged_cache", "paged_copy_block",
    "paged_decode_step", "paged_prefill_chunk", "param_count", "prefill",
]

from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig
from repro.models.model import (
    PREFILL_FAMILIES,
    abstract_params,
    decode_step,
    forward,
    init_cache,
    init_params,
    next_token_loss,
    prefill,
)

__all__ = [
    "INPUT_SHAPES",
    "InputShape",
    "ModelConfig",
    "PREFILL_FAMILIES",
    "abstract_params",
    "decode_step",
    "forward",
    "init_cache",
    "init_params",
    "next_token_loss",
    "prefill",
]

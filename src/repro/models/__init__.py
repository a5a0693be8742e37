from . import attention, layers, lm, moe, params, ssm, xlstm
from .lm import (
    abstract_model,
    cache_template,
    decode_step,
    forward,
    init_model,
    model_param_axes,
    model_template,
    num_params,
    prefill,
)

__all__ = [
    "attention", "layers", "lm", "moe", "params", "ssm", "xlstm",
    "abstract_model", "cache_template", "decode_step", "forward",
    "init_model", "model_param_axes", "model_template",
    "num_params", "prefill",
]

from repro.models import (attention, cnn, layers, mamba2, moe, transformer,
                          vit)
from repro.models.transformer import (decode_step, forward_train, init_params,
                                      init_shapes, logical_axes, make_caches,
                                      param_count, prefill)

__all__ = [
    "attention", "cnn", "layers", "mamba2", "moe", "transformer", "vit",
    "decode_step", "forward_train", "init_params", "init_shapes",
    "logical_axes", "make_caches", "param_count", "prefill",
]

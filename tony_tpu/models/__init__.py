"""Flagship JAX models for the framework's example/benchmark jobs.

The reference shipped user-side example models (tony-examples/: distributed
MNIST for TF/PyTorch/Keras, MXNet linear regression — SURVEY.md §2.2); this
package is their TPU-native counterpart plus the Llama-family transformer
the BASELINE targets (Llama-3-8B pretrain on a TPU pod). Models are pure
pytrees + functions: init(cfg, key) -> params, forward(params, batch) ->
logits, with logical sharding axes declared next to the params.
"""

from tony_tpu.models.generate import generate, generate_text
from tony_tpu.models.llama import (
    LlamaConfig, llama_forward, llama_init, llama_loss, llama_param_axes,
)
from tony_tpu.models.mnist import mnist_forward, mnist_init, mnist_loss
from tony_tpu.models.linear import linreg_forward, linreg_init, linreg_loss
from tony_tpu.models.resnet import (
    ResNetConfig, resnet_forward, resnet_init, resnet_loss,
)
from tony_tpu.models.moe import (
    MoEConfig, moe_forward, moe_init, moe_loss, moe_param_axes,
)
from tony_tpu.models.vit import (
    ViTConfig, vit_forward, vit_init, vit_loss, vit_param_axes,
)

# the models whose layers are of several kinds and whose cache is by layer
# kind: each module has PRESETS, `get_config(name)`, `init(config, key)`
# and what models/generate.py `kind_module` asks of it. Imported when a
# preset is looked for, not with the package.
BY_KIND = ("tony_tpu.models.sala", "tony_tpu.models.lfm2")


def by_kind_preset(name: str):
    """The module of BY_KIND whose PRESETS hold `name`, or None."""
    import importlib
    for module in map(importlib.import_module, BY_KIND):
        if name in module.PRESETS:
            return module
    return None


__all__ = [
    "generate", "generate_text", "BY_KIND", "by_kind_preset",
    "LlamaConfig", "llama_forward", "llama_init", "llama_loss",
    "llama_param_axes", "mnist_forward", "mnist_init", "mnist_loss",
    "linreg_forward", "linreg_init", "linreg_loss",
    "MoEConfig", "moe_forward", "moe_init", "moe_loss", "moe_param_axes",
    "ResNetConfig", "resnet_forward", "resnet_init", "resnet_loss",
    "ViTConfig", "vit_forward", "vit_init", "vit_loss", "vit_param_axes",
]

"""Model zoo built on the layers DSL — parity targets are the reference's
benchmark configs (reference: benchmark/paddle/image/{alexnet,googlenet,
resnet,vgg,smallnet_mnist_cifar}.py) and book tests
(reference: python/paddle/fluid/tests/book/).

Every builder appends ops to the current default program and returns the
logits/cost variables, exactly like user scripts in the reference do.
"""
from .lenet import lenet5  # noqa: F401
from .mlp import mlp  # noqa: F401
from .vgg import vgg16, vgg_cifar  # noqa: F401
from .resnet import resnet, resnet_cifar10, resnet_imagenet  # noqa: F401
from .alexnet import alexnet  # noqa: F401
from .googlenet import googlenet  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerConfig, TransformerLM, transformer_lm, transformer_block,
)
from .ctr import wide_deep, deepfm, synthetic_click_batch  # noqa: F401
from .latent_moe_lm import latent_moe_lm  # noqa: F401
from .window_moe_lm import window_moe_lm  # noqa: F401
from .prerouted_moe_lm import prerouted_moe_lm  # noqa: F401
from .hybrid_ssm_moe_lm import hybrid_ssm_moe_lm  # noqa: F401

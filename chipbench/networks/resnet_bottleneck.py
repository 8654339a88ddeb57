"""ResNet with bottleneck blocks, built from the program's own ``layers.*``
as He et al. and the reference repo's benchmark/paddle/image/resnet.py state
it. A STAND-IN for ``models.resnet_imagenet``, a copy of it with ONE
difference: the ReLU after each residual add is an op of its own, because
``layers.elementwise_add(act="relu")`` drops its activation (PERF.md, Open
questions 0), so ``models.resnet_imagenet`` is not the published network and
the plain reference refuses it (chipbench/tests/test_train_cell.py). A change
inside ``models/resnet.py`` does not reach the cell until that is mended and
a benchmark PR names ``models.resnet_imagenet`` here.

A network module of a training configuration is ``build(layers, img,
config)`` -> the prediction, and ``train_flops_per_row(config, reference)``.
"""
from chipbench import flops


def build(layers, img, config):
    def conv_bn(x, ch_out, k, stride, pad, act="relu"):
        conv = layers.conv2d(x, num_filters=ch_out, filter_size=k,
                             stride=stride, padding=pad, act=None,
                             bias_attr=False)
        return layers.batch_norm(conv, act=act)

    def bottleneck(x, ch_in, width, stride, expansion):
        short = x
        if ch_in != width * expansion or stride != 1:
            short = conv_bn(x, width * expansion, 1, stride, 0, act=None)
        y = conv_bn(x, width, 1, stride, 0)
        y = conv_bn(y, width, 3, 1, 1)
        y = conv_bn(y, width * expansion, 1, 1, 0, act=None)
        return layers.relu(layers.elementwise_add(short, y))

    expansion = config["bottleneck_expansion"]
    x = conv_bn(img, 64, 7, 2, 3)
    x = layers.pool2d(x, pool_size=3, pool_stride=2, pool_padding=1,
                      pool_type="max")
    ch_in = 64
    for i, (count, width) in enumerate(zip(config["stage_blocks"],
                                           config["stage_widths"])):
        for b in range(count):
            x = bottleneck(x, ch_in, width, 2 if (i > 0 and b == 0) else 1,
                           expansion)
            ch_in = width * expansion
    x = layers.pool2d(x, pool_size=7, pool_stride=1, pool_type="avg",
                      global_pooling=True)
    return layers.fc(x, size=config["classes"], act="softmax")


def train_flops_per_row(config, reference):
    """Forward + backward FLOPs of one image, counted from the reference's
    list of the 53 convolution shapes and the dense layer."""
    convs, fc = reference.conv_shapes(config["image"], config["classes"])
    return flops.resnet_train_flops(convs, fc)

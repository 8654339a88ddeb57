"""The FLOP and byte counters against hand-worked values."""
from tiny import harness

from chipbench import flops

ref = harness.load_module("reference", "resnet50.py")


def test_three_conv_shapes_by_hand():
    # stem: 7x7, 3 -> 64, stride 2, pad 3, 224 -> 112
    assert flops.conv_flops(3, 64, 7, 2, 3, 224) == 2 * 49 * 3 * 64 * 112 * 112
    assert flops.conv_flops(3, 64, 7, 2, 3, 224) == 236027904
    # a 3x3 of stage 1: 64 -> 64 at 56x56
    assert flops.conv_flops(64, 64, 3, 1, 1, 56) == 2 * 9 * 64 * 64 * 56 * 56
    assert flops.conv_flops(64, 64, 3, 1, 1, 56) == 231211008
    # the strided 1x1 shortcut into stage 4: 1024 -> 2048, 14 -> 7
    assert flops.conv_flops(1024, 2048, 1, 2, 0, 14) == 2 * 1024 * 2048 * 49


def test_resnet50_total_is_the_published_count():
    net = harness.load_module("networks", "resnet_bottleneck.py")
    cfg = harness.load_json("configs", "resnet50-imagenet.json")
    assert net.train_flops_per_row(cfg, ref) == flops.resnet_train_flops(
        *ref.conv_shapes(224, 1000))
    convs, fc = ref.conv_shapes(224, 1000)
    assert len(convs) == 53 and fc == (2048, 1000)
    fwd = flops.resnet_forward_flops(convs, fc)
    # He et al. table 1: 3.8e9 multiply-adds for the 50-layer net (stride on
    # the 3x3); with the stride on the first 1x1, as here, 4.09e9
    assert 2 * 3.8e9 < fwd < 2 * 4.2e9
    assert flops.resnet_train_flops(convs, fc, batch=2) == 6 * fwd


def test_one_decode_step_by_hand():
    # OPT-1.3B widths: per block 4*2048^2 + 2*2048*8192, 24 blocks, head
    p = flops.transformer_matmul_params(2048, 24, 8192, 50272)
    assert p == 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 2048 * 50272
    assert flops.transformer_token_flops(2048, 24, 8192, 50272) == 2 * p
    # 32 rows with 200 live tokens each, f32 weights and f32 K/V at 819 GB/s
    t = flops.decode_step_floor_s(4 * p, 32 * 200, 24, 2048, 4, 819e9)
    assert abs(t - (4 * p + 6400 * 24 * 2 * 2048 * 4) / 819e9) < 1e-15

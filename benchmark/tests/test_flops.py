"""The operation counters against counts made by hand."""

from flops import i3d_pwc, resnet50


def test_resnet_first_bottleneck_by_hand():
    # layer1.0 at 56 x 56: 1x1 64->64, 3x3 64->64, 1x1 64->256, and the
    # 1x1 64->256 projection on the shortcut
    px = 56 * 56
    by_hand = 2 * px * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert resnet50.bottleneck_flops(56, 64, 64, 1, True) == by_hand


def test_resnet_whole_network_is_the_published_count():
    # 4.09 G multiply-adds per 224 x 224 image (He et al. 2015, torchvision)
    assert abs(resnet50.flops_per_row() / 2 / 1e9 - 4.09) < 0.01


def test_cost_volume_level_by_hand():
    # level 4 of a 256 x 384 grid: 16 x 24 positions, 96 channels
    assert i3d_pwc.level_size(4) == (16, 24)
    assert i3d_pwc.corr_flops(4) == 2 * 81 * 16 * 24 * 96
    assert i3d_pwc.corr_bytes(4) == 4 * 16 * 24 * (96 + 96 + 81)


def test_i3d_tower_is_the_published_count():
    # 107.9 G multiply-adds for 64 x 224 x 224 rgb (Carreira & Zisserman 2017)
    assert abs(i3d_pwc.i3d_flops(3) / 2 / 1e9 - 111.2) < 0.5

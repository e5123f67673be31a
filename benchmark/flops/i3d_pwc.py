"""Operations one ``i3d_pwc_fp32`` row (one 65-frame stack at 256 x 341)
needs, counted from the two architectures' shapes: both I3D towers on 64 x
224 x 224, PWC-Net's pyramid once per frame (65 frames on the 256 x 384 grid
the net resizes to) and its decoders, cost volumes and refiner once per pair
(64 pairs). Convolutions, transposed convolutions and cost volumes only;
warps, resizes, pools and activations are not counted.

Also the cost volume's own operations and bytes per pair and level, for its
roofline: ``2 * 81 * h * w * c`` operations, ``f1`` and ``f2`` read once and
the 81-channel volume written once, in float32.
"""

from math import ceil

from .common import conv_flops

STACK, CROP = 64, 224
GRID = (256, 384)  # 256 x 341 resized up to a multiple of 64

I3D_LAYERS = (
    ("conv", 64, (7, 7, 7), (2, 2, 2)),
    ("pool", (1, 2, 2)),
    ("conv", 64, (1, 1, 1), (1, 1, 1)),
    ("conv", 192, (3, 3, 3), (1, 1, 1)),
    ("pool", (1, 2, 2)),
    ("mixed", (64, 96, 128, 16, 32, 32)),
    ("mixed", (128, 128, 192, 32, 96, 64)),
    ("pool", (2, 2, 2)),
    ("mixed", (192, 96, 208, 16, 48, 64)),
    ("mixed", (160, 112, 224, 24, 64, 64)),
    ("mixed", (128, 128, 256, 24, 64, 64)),
    ("mixed", (112, 144, 288, 32, 64, 64)),
    ("mixed", (256, 160, 320, 32, 128, 128)),
    ("pool", (2, 2, 2)),
    ("mixed", (256, 160, 320, 32, 128, 128)),
    ("mixed", (384, 192, 384, 48, 128, 128)),
)
PYRAMID = (16, 32, 64, 96, 128, 196)
LEVEL_FEAT = {6: 196, 5: 128, 4: 96, 3: 64, 2: 32}
DENSE = (128, 128, 96, 64, 32)
REFINER = (128, 128, 128, 96, 64, 32, 2)


def i3d_flops(cin: int, t: int = STACK, size: int = CROP) -> int:
    shape, total = (t, size, size), 0
    for op, *rest in I3D_LAYERS:
        if op == "conv":
            cout, kernel, stride = rest
            shape = tuple(ceil(n / s) for n, s in zip(shape, stride))
            total += conv_flops(shape, kernel, cin, cout)
            cin = cout
        elif op == "pool":
            shape = tuple(ceil(n / s) for n, s in zip(shape, rest[0]))
        else:
            c0, c1r, c1, c2r, c2, c3 = rest[0]
            total += conv_flops(shape, (1, 1, 1), cin, c0 + c1r + c2r + c3)
            total += conv_flops(shape, (3, 3, 3), c1r, c1)
            total += conv_flops(shape, (3, 3, 3), c2r, c2)
            cin = c0 + c1 + c2 + c3
    return total


def level_size(level: int):
    return GRID[0] >> level, GRID[1] >> level


def pyramid_flops() -> int:
    total, cin = 0, 3
    for level, cout in enumerate(PYRAMID, start=1):
        size = level_size(level)
        total += conv_flops(size, (3, 3), cin, cout) + 2 * conv_flops(size, (3, 3), cout, cout)
        cin = cout
    return total


def corr_flops(level: int) -> int:
    h, w = level_size(level)
    return 2 * 81 * h * w * LEVEL_FEAT[level]


def corr_bytes(level: int) -> int:
    h, w = level_size(level)
    return 4 * h * w * (2 * LEVEL_FEAT[level] + 81)


def decoder_flops() -> int:
    """All five decoders and the refiner, for one pair."""
    total, prev_feat = 0, None
    for level in (6, 5, 4, 3, 2):
        size = level_size(level)
        total += corr_flops(level)
        ch = 81 if level == 6 else 81 + LEVEL_FEAT[level] + 4
        if level < 6:
            # transposed 4x4 stride 2: each output position has 4 live taps
            total += conv_flops(size, (2, 2), 2, 2) + conv_flops(size, (2, 2), prev_feat, 2)
        for cout in DENSE:
            total += conv_flops(size, (3, 3), ch, cout)
            ch += cout
        total += conv_flops(size, (3, 3), ch, 2)
        prev_feat = ch
    ch = prev_feat
    for cout in REFINER:
        total += conv_flops(level_size(2), (3, 3), ch, cout)
        ch = cout
    return total


def flops_per_row() -> int:
    return (i3d_flops(3) + i3d_flops(2) + (STACK + 1) * pyramid_flops()
            + STACK * decoder_flops())

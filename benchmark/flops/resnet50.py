"""Operations one ``resnet50_fp32`` row (one 224 x 224 frame) needs: the
convolutions of ResNet-50 v1.5 counted from the architecture's shapes."""

from .common import conv_flops

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def bottleneck_flops(size_in: int, cin: int, planes: int, stride: int, downsample: bool) -> int:
    """One bottleneck at ``size_in`` x ``size_in`` input (v1.5: the stride
    sits on the 3x3)."""
    size_out = size_in // stride
    total = conv_flops((size_in, size_in), (1, 1), cin, planes)
    total += conv_flops((size_out, size_out), (3, 3), planes, planes)
    total += conv_flops((size_out, size_out), (1, 1), planes, planes * 4)
    if downsample:
        total += conv_flops((size_out, size_out), (1, 1), cin, planes * 4)
    return total


def flops_per_row() -> int:
    total = conv_flops((112, 112), (7, 7), 3, 64)
    size, cin = 56, 64
    for stage, (planes, blocks) in enumerate(STAGES, start=1):
        for b in range(blocks):
            stride = 2 if (stage > 1 and b == 0) else 1
            total += bottleneck_flops(size, cin, planes, stride, b == 0)
            size //= stride
            cin = planes * 4
    return total

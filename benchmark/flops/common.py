"""Counting by hand: a convolution needs 2 x (output positions) x (kernel
taps) x cin x cout operations; pooling, normalisation and activations are
not counted."""

from math import prod


def out_size(n: int, k: int, stride: int, pad_lo: int, pad_hi: int, dilation: int = 1) -> int:
    return (n + pad_lo + pad_hi - dilation * (k - 1) - 1) // stride + 1


def conv_flops(out_positions, kernel, cin: int, cout: int) -> int:
    return 2 * prod(out_positions) * prod(kernel) * cin * cout

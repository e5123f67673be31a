"""The PWC cost-volume kernels' share of their roofline, from the device trace.

Kernel time: the summed self time of the traced slice's operations whose HLO
instruction carries one of the kernels' names (``KERNELS``: the ``name=`` the
program gives its ``pallas_call``s, which the compiler keeps as the
instruction's name, ``%pwc_corr81_tiled.23``); a Mosaic call of any other name
is another kernel's and is not counted. Work: each call's result shape ``[b, h, w, 81]``
and its first operand's channel count name the pyramid level and the pairs it
served; operations (``2 * 81 * h * w * c`` per pair) and bytes (``f1`` and
``f2`` read once, the volume written once, float32) come from
``flops/i3d_pwc.py`` at the level's true size, so padding a kernel adds is not
counted as work. Least time is the larger of operations over the peak and
bytes over the peak bandwidth: bandwidth bounds it at every level (0.6
operations a byte at 32 channels, 2.4 at 196). Nothing to read → nothing
returned.
"""

import re

from flops import i3d_pwc
from peaks import peaks_for

KERNELS = ("pwc_corr81_single", "pwc_corr81_tiled", "pwc_warp_corr81_fused")
INSTRUCTION = re.compile(r"%?([A-Za-z_][\w-]*?)(?:\.\d+)? = ")
SHAPE = re.compile(r"(?:f32|bf16)\[(\d+),(\d+),(\d+),(\d+)\]")


def read(trace, stats, facts):
    peaks = peaks_for(facts["device_kind"], facts["peaks"])
    level_of = {c: lvl for lvl, c in i3d_pwc.LEVEL_FEAT.items()}
    kernel_s = least_s = 0.0
    for name, seconds in trace["op_seconds"].items():
        instruction = INSTRUCTION.match(name)
        if instruction is None or instruction.group(1) not in KERNELS:
            continue
        shapes = [tuple(int(g) for g in m.groups()) for m in SHAPE.finditer(name)]
        volumes = [s for s in shapes if s[3] == 81]
        feats = [s for s in shapes if s[3] in level_of]
        if not volumes or not feats:
            continue
        level, pairs = level_of[feats[0][3]], volumes[0][0]
        calls = trace["op_counts"][name]
        ops = calls * pairs * i3d_pwc.corr_flops(level)
        nbytes = calls * pairs * i3d_pwc.corr_bytes(level)
        kernel_s += seconds
        least_s += max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    if kernel_s <= 0.0:
        return None
    return 100.0 * least_s / kernel_s

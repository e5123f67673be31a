"""Measure the reference computation in torch on this host → BASELINE.json "measured".

The reference publishes no benchmark numbers, so this script anchors
``vs_baseline`` by timing the torch equivalents of the reference's hot paths
(architectures mirrored 1:1 from the reference source in tools/torch_mirrors.py):

- I3D-rgb: one 64-frame 224² clip forward (/root/reference/models/i3d/i3d_net.py:160-274)
- RAFT: one 256² frame-pair, 20 GRU iterations (/root/reference/models/raft/raft_src/raft.py:115-174)
- ResNet-50: 224² frames (/root/reference/models/resnet50/extract_resnet50.py:54)

Numbers are recorded with hardware metadata; on this build host that is torch-CPU
(the reference's CUDA path has no GPU here). Run once.

Usage: python tools/measure_reference.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import torch  # noqa: E402

from tools.torch_mirrors import (  # noqa: E402
    ResNet50,
    i3d_forward,
    i3d_random_state_dict,
    raft_random_state_dict,
    raft_torch_forward,
    random_init_,
)

BASELINE_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BASELINE.json")


def _time(fn, n: int = 1) -> float:
    fn()  # warmup (allocator, thread pool)
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="16-frame i3d clip instead of 64")
    args = ap.parse_args()

    torch.set_grad_enabled(False)
    rng = np.random.default_rng(0)
    results = {}

    # I3D-rgb: clips/sec at the reference geometry (64×224², extract_i3d.py:27,59-63)
    frames = args.quick and 16 or 64
    sd = i3d_random_state_dict("rgb")
    clip = torch.from_numpy(rng.uniform(-1, 1, (1, 3, frames, 224, 224)).astype(np.float32))
    dt = _time(lambda: i3d_forward(sd, clip, features=True))
    results["i3d_rgb_clips_per_sec"] = (frames / 64.0) / dt  # normalize to 64-frame clips

    # RAFT: flow pairs/sec at the I3D-flow context size (256², 20 iterations)
    rsd = raft_random_state_dict()
    im = torch.from_numpy(rng.uniform(0, 255, (1, 3, 256, 256)).astype(np.float32))
    im2 = torch.from_numpy(rng.uniform(0, 255, (1, 3, 256, 256)).astype(np.float32))
    dt = _time(lambda: raft_torch_forward(rsd, im, im2, iters=20))
    results["raft_pairs_per_sec"] = 1.0 / dt
    # a RAFT-flow "clip" in the north-star metric = 64 consecutive pairs
    results["raft_flow_clips_per_sec"] = 1.0 / (dt * 64.0)

    # ResNet-50: frames/sec at 224² (batch 4 amortizes framework overhead)
    model = random_init_(ResNet50()).eval()
    batch = torch.from_numpy(rng.uniform(-2, 2, (4, 3, 224, 224)).astype(np.float32))
    dt = _time(lambda: model(batch, features=True))
    results["resnet50_fps"] = 4.0 / dt

    results = {k: round(v, 6) for k, v in results.items()}
    meta = {
        "hardware": f"torch-{torch.__version__} CPU, {torch.get_num_threads()} thread(s), {platform.processor() or platform.machine()}",
        "note": "reference torch computation timed on the build host (no GPU available); "
        "architectures mirrored from /root/reference (see tools/torch_mirrors.py)",
    }

    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    baseline["measured"] = {**results, **meta}
    with open(BASELINE_PATH, "w") as f:
        json.dump(baseline, f, indent=2)
    print(json.dumps(baseline["measured"], indent=2))


if __name__ == "__main__":
    main()

"""Per-layer activation-diff harness: Flax models vs independent torch mirrors.

SURVEY.md §4's parity plan: convert random reference-named torch weights into
Flax params, run BOTH implementations layer by layer on the same input, and
report the max abs diff per stage — so a topology error (wrong stride, missing
branch, wrong channel split) is localized to the first diverging layer instead
of surfacing as an end-to-end mismatch (or worse, passing because the oracle
shared the bug — see tests/test_mirror_independence.py).

Usage:
    python tools/layer_diff.py            # report for I3D-rgb and RAFT
    python tools/layer_diff.py --model raft --iters 8

Programmatic: ``i3d_layer_diff()`` / ``raft_layer_diff()`` return
``[(stage, max_abs_diff, ref_scale), ...]`` ordered by execution.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# fp32 parity harness: must NOT run on the TPU backend, where fp32 convs default
# to bf16 MXU passes (~2e-3 relative noise that looks like topology divergence).
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402


def _diff(name, torch_nchw, flax_nhwc):
    """Compare a torch NC(T)HW tap with a Flax N(T)HWC tap."""
    t = torch_nchw.numpy()
    t = np.moveaxis(t, 1, -1)  # NCHW→NHWC / NCTHW→NTHWC
    f = np.asarray(flax_nhwc)
    assert t.shape == f.shape, f"{name}: {t.shape} vs {f.shape}"
    return name, float(np.abs(t - f).max()), float(np.abs(t).max())


def i3d_layer_diff(modality="rgb", shape=(1, 16, 64, 64), seed=0, sd=None):
    """Layer-wise diffs through the I3D stem + all Mixed blocks.

    ``sd``: a reference-named torch state dict — pass a REAL pretrained
    checkpoint's dict to verify it end to end (tools/verify_parity.py);
    default None uses the deterministic random mirror weights."""
    import torch

    from tools.torch_mirrors import i3d_forward, i3d_random_state_dict

    from video_features_tpu.models.i3d import I3D
    from video_features_tpu.weights.convert_torch import convert_i3d

    rng = np.random.default_rng(seed)
    c = {"rgb": 3, "flow": 2}[modality]
    b, t, h, w = shape
    x = rng.uniform(-1, 1, (b, t, h, w, c)).astype(np.float32)

    if sd is None:
        sd = i3d_random_state_dict(modality, seed=seed)
    taps_t: dict = {}
    i3d_forward(sd, torch.from_numpy(np.moveaxis(x, -1, 1)), features=True, taps=taps_t)

    params = convert_i3d(sd)
    model = I3D(modality=modality)
    _, state = model.apply(
        {"params": params}, x, features=True, capture_intermediates=True, mutable=["intermediates"]
    )
    inter = state["intermediates"]

    rows = []
    for name, t_out in taps_t.items():
        if name in inter:  # Unit3D / Mixed modules (pools are un-named functions)
            rows.append(_diff(name, t_out, inter[name]["__call__"][0]))
    return rows


def raft_layer_diff(shape=(1, 128, 128), iters=4, seed=0, sd=None):
    # NB: H, W ≥ 128 keeps the coarsest corr-pyramid level ≥ 2×2; at 1×1 the
    # reference's align_corners grid mapping divides by (W−1) = 0 (NaN on both
    # sides — real checkpoints never see inputs that small).
    """Stage-wise diffs: encoders, correlation volume, per-iteration flow.

    ``sd``: optional REAL reference state dict (see tools/verify_parity.py)."""
    import torch

    from tools.torch_mirrors import raft_random_state_dict, raft_torch_forward

    from video_features_tpu.models.raft import raft_forward
    from video_features_tpu.weights.convert_torch import convert_raft

    rng = np.random.default_rng(seed)
    b, h, w = shape
    im1 = rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32)
    im2 = rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32)

    if sd is None:
        sd = raft_random_state_dict(seed=seed)
    taps_t: dict = {}
    raft_torch_forward(sd, torch.from_numpy(np.moveaxis(im1, -1, 1)),
                       torch.from_numpy(np.moveaxis(im2, -1, 1)), iters=iters, taps=taps_t)

    params = convert_raft(sd)
    taps_j: dict = {}
    raft_forward(params, im1, im2, iters=iters, taps=taps_j)

    # every tap follows the same layout rule (torch channel-2nd vs flax channel-last,
    # incl. corr_l0: (BHW, 1, H, W) vs (BHW, H, W, 1))
    return [_diff(name, taps_t[name], taps_j[name]) for name in taps_t]


def _report(title, rows, budget=1e-3):
    print(f"\n== {title} ==")
    print(f"{'stage':<28} {'max|Δ|':>12} {'ref max':>12}")
    worst = 0.0
    for name, d, scale in rows:
        flag = "  <-- DIVERGES" if d > budget * max(scale, 1.0) else ""
        print(f"{name:<28} {d:>12.3e} {scale:>12.3e}{flag}")
        worst = max(worst, d / max(scale, 1e-9))
    print(f"worst relative: {worst:.3e}")
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["i3d", "raft", "all"], default="all")
    ap.add_argument("--iters", type=int, default=4, help="RAFT update iterations")
    args = ap.parse_args()

    if args.model in ("i3d", "all"):
        _report("I3D rgb (random ref-named weights)", i3d_layer_diff("rgb"))
        _report("I3D flow", i3d_layer_diff("flow"))
    if args.model in ("raft", "all"):
        _report(f"RAFT ({args.iters} iters)", raft_layer_diff(iters=args.iters))


if __name__ == "__main__":
    main()

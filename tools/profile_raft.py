"""Stage-level RAFT timing on the live backend: where do the 1.5 s/step go?

Times each stage of ``raft_forward`` (batch 16 × 256², the bench config) as its
own jitted program with unique inputs per call (bench.py's methodology):

- encoders: fnet(x1) + fnet(x2) + cnet(x1)
- pyramid:  all-pairs einsum + 3 avg-pools
- lookup20: 20 chained 4-level 9×9 window lookups (volume impl)
- gru20:    20 scan iterations with the lookup replaced by a fixed corr tensor
- full:     raft_forward volume / on_demand

Run: python tools/profile_raft.py [batch] [side]
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("VFT_ALLOW_RANDOM_WEIGHTS", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from _bench_util import enable_compilation_cache, time_fn  # noqa: E402

enable_compilation_cache()

from video_features_tpu.models import raft as R  # noqa: E402


def main():
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    side = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    h8 = w8 = side // 8
    rng = np.random.default_rng(0)
    params = jax.device_put(R.raft_init_params(0))
    print(f"backend={jax.default_backend()} batch={b} side={side}", flush=True)

    def frames():
        return jnp.asarray(rng.uniform(0, 255, (b, side, side, 3)).astype(np.float32))

    def feats():
        return jnp.asarray(rng.standard_normal((b, h8, w8, 256)).astype(np.float32))

    def small(c):
        return jnp.asarray(rng.standard_normal((b, h8, w8, c)).astype(np.float32))

    # --- encoders ---
    @jax.jit
    def encoders(p, x1, x2):
        f1 = R._encoder(p["fnet"], 2.0 * x1 / 255.0 - 1.0, "instance")
        f2 = R._encoder(p["fnet"], 2.0 * x2 / 255.0 - 1.0, "instance")
        c = R._encoder(p["cnet"], 2.0 * x1 / 255.0 - 1.0, "batch")
        return f1, f2, c

    time_fn("encoders", encoders, lambda: (params, frames(), frames()))

    # --- pyramid build ---
    @jax.jit
    def pyramid(f1, f2):
        return R._build_pyramid(f1, f2)

    time_fn("pyramid", pyramid, lambda: (feats(), feats()))

    # --- 20 lookups (volume: matmul vs gather) ---
    # the drift term consumes EVERY corr channel: a coords+corr[..., :2] probe
    # lets XLA dead-code-eliminate 322 of 324 lookup channels (first profile
    # run under-reported the gather cost 4×)
    def lookup20_impl(impl):
        @jax.jit
        def lookup20(f1, f2, flow0):
            pyr = R._build_pyramid(f1, f2)
            coords0 = R.coords_grid(b, h8, w8)

            def body(coords, _):
                corr = R._lookup(pyr, coords, impl)
                drift = jnp.stack([corr.sum(-1), corr.max(-1)], axis=-1)
                return coords + drift * 1e-4, None

            coords, _ = lax.scan(body, coords0 + flow0, None, length=R.ITERS)
            return coords

        return lookup20

    time_fn("lookup20_mm", lookup20_impl("matmul"),
            lambda: (feats(), feats(), small(2)))
    time_fn("lookup20_ga", lookup20_impl("gather"),
            lambda: (feats(), feats(), small(2)))

    # --- 20 lookups (on-demand) ---
    @jax.jit
    def lookup20_od(f1, f2, flow0):
        pyr = R._build_f2_pyramid(f2)
        coords0 = R.coords_grid(b, h8, w8)

        def body(coords, _):
            corr = R._lookup_on_demand(f1, pyr, coords)
            drift = jnp.stack([corr.sum(-1), corr.max(-1)], axis=-1)
            return coords + drift * 1e-4, None

        coords, _ = lax.scan(body, coords0 + flow0, None, length=R.ITERS)
        return coords

    time_fn("lookup20_od", lookup20_od, lambda: (feats(), feats(), small(2)))

    # --- 20 GRU iterations with fixed corr ---
    n_corr = R.CORR_LEVELS * (2 * R.CORR_RADIUS + 1) ** 2

    @jax.jit
    def gru20(p, corr, net0, inp):
        up = p["update_block"]
        coords0 = R.coords_grid(b, h8, w8)

        def body(carry, _):
            net, coords1 = carry
            flow = coords1 - coords0
            motion = R._motion_encoder(up["encoder"], flow, corr)
            net = R._sep_conv_gru(up["gru"], net, jnp.concatenate([inp, motion], -1))
            delta = R.conv2d(up["flow_head"]["conv2"],
                             R._relu(R.conv2d(up["flow_head"]["conv1"], net, 1, 1)), 1, 1)
            return (net, coords1 + delta), None

        (net, coords1), _ = lax.scan(body, (net0, coords0), None, length=R.ITERS)
        mask = 0.25 * R.conv2d(up["mask.2"], R._relu(R.conv2d(up["mask.0"], net, 1, 1)), 1, 0)
        return R._convex_upsample(coords1 - coords0, mask)

    time_fn("gru20", gru20,
            lambda: (params, small(n_corr), small(R.HIDDEN_DIM), small(R.CONTEXT_DIM)))

    # --- full forward ---
    @jax.jit
    def full(p, x1, x2):
        return R.raft_forward(p, x1, x2)

    time_fn("full_volume", full, lambda: (params, frames(), frames()))

    @jax.jit
    def full_gather(p, x1, x2):
        return R.raft_forward(p, x1, x2, corr_impl="volume_gather")

    time_fn("full_gather", full_gather, lambda: (params, frames(), frames()))

    @jax.jit
    def full_od(p, x1, x2):
        return R.raft_forward(p, x1, x2, corr_impl="on_demand")

    time_fn("full_od", full_od, lambda: (params, frames(), frames()))

    # --- shared-frame forward: b pairs from b+1 frames, fnet once/frame ---
    def frames_plus1():
        return jnp.asarray(
            rng.uniform(0, 255, (b + 1, side, side, 3)).astype(np.float32))

    @jax.jit
    def full_frames(p, fr):
        return R.raft_forward_frames(p, fr)

    time_fn("full_frames", full_frames, lambda: (params, frames_plus1()))

    @jax.jit
    def full_frames_bf16(p, fr):
        return R.raft_forward_frames(p, fr, dtype=jnp.bfloat16)

    time_fn("full_frames_bf16", full_frames_bf16, lambda: (params, frames_plus1()))


if __name__ == "__main__":
    main()

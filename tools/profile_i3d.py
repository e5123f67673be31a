"""Segment-level I3D timing: which part of the headline model eats the step?

Times cumulative prefixes of the I3D spec walk (stem conv → pools/convs →
mixed_3 → mixed_4 → mixed_5 → head) as independent jitted programs on the live
backend; per-segment cost is the difference between adjacent prefixes. Same
unique-inputs methodology as tools/profile_raft.py.

Run: python tools/profile_i3d.py [clips] [stack] [dtype]
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("VFT_ALLOW_RANDOM_WEIGHTS", "1")

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from _bench_util import enable_compilation_cache, time_fn  # noqa: E402

enable_compilation_cache()

from video_features_tpu.models.i3d import (  # noqa: E402
    I3D,
    I3D_STEM,
    Mixed,
    Unit3D,
    i3d_preprocess_rgb,
)
from video_features_tpu.models.layers import max_pool_tf_same  # noqa: E402


class I3DPrefix(nn.Module):
    """First ``n_ops`` entries of the I3D spec walk (random params per prefix)."""

    n_ops: int
    dtype: object = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for op, name, *spec in I3D_STEM[: self.n_ops]:
            if op == "conv":
                feats, kernel, stride = spec
                x = Unit3D(feats, kernel, stride, dtype=self.dtype, name=name)(x)
            elif op == "pool":
                kernel, stride = spec
                x = max_pool_tf_same(x, kernel, stride)
            else:
                x = Mixed(spec[0], dtype=self.dtype, name=name)(x)
        return x


def main():
    clips = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    stack = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        sys.argv[3] if len(sys.argv) > 3 else "float32"
    ]
    rng = np.random.default_rng(0)
    print(f"backend={jax.default_backend()} clips={clips} stack={stack} "
          f"dtype={jnp.dtype(dtype).name}", flush=True)

    def frames():
        return jnp.asarray(
            rng.uniform(-1, 1, (clips, stack, 224, 224, 3)).astype(np.float32))

    segments = [
        ("stem_conv7", 1),
        ("convs+pools", 5),
        ("mixed_3b-3c", 7),
        ("mixed_4a-4f", 13),
        ("mixed_5a-5c", 16),
    ]
    prev_ms, prev_label = 0.0, "input"
    for label, n_ops in segments:
        model = I3DPrefix(n_ops=n_ops, dtype=dtype)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 16, 224, 224, 3)))["params"]
        params = jax.device_put(params)

        def fwd(p, x, model=model):
            return model.apply({"params": p}, x)

        step = jax.jit(fwd)
        ms = time_fn(f"thru_{label}", step, lambda: (params, frames()))
        print(f"{'Δ ' + label:>16}: {(ms - prev_ms) * 1e3:9.2f} ms", flush=True)
        prev_ms = ms

    # full model incl. head, and the real extractor preprocessing
    model = I3D(modality="rgb", dtype=dtype)
    params = jax.device_put(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 224, 224, 3)))["params"])

    def full(p, x):
        return model.apply({"params": p}, x, features=True)

    ms = time_fn("full+head", jax.jit(full), lambda: (params, frames()))
    print(f"{'Δ head':>16}: {(ms - prev_ms) * 1e3:9.2f} ms", flush=True)

    def full_pre(p, u8):
        return model.apply({"params": p}, i3d_preprocess_rgb(u8, dtype), features=True)

    def u8():
        return jnp.asarray(rng.integers(0, 256, (clips, stack, 224, 224, 3),
                                        dtype=np.uint8))

    time_fn("full+preproc", jax.jit(full_pre), lambda: (params, u8()))

    # space-to-depth stem lowering (same params tree)
    model_s2d = I3D(modality="rgb", s2d_stem=True, dtype=dtype)

    def full_s2d(p, x):
        return model_s2d.apply({"params": p}, x, features=True)

    time_fn("full_s2d", jax.jit(full_s2d), lambda: (params, frames()))

    stem = I3DPrefix(n_ops=1, dtype=dtype)
    stem_params = jax.device_put(
        stem.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 224, 224, 3)))["params"])
    from video_features_tpu.models.layers import S2DStemConv

    s2d_conv = S2DStemConv(64, dtype=dtype)
    kernel_tree = {"kernel": stem_params["conv3d_1a_7x7"]["conv3d"]["kernel"]}

    def stem_s2d(p, x):
        return s2d_conv.apply({"params": p}, x)

    time_fn("stem_s2d_conv", jax.jit(stem_s2d), lambda: (kernel_tree, frames()))


if __name__ == "__main__":
    main()

"""Measure the fused warp+corr kernel vs the XLA composition on TPU.

Per-level shapes are PWC's correlation inputs at a 256² frame (the production
two-stream I3D geometry): level ℓ runs at 256/2^ℓ with PYR_CHANNELS[ℓ-1]
features. Each (impl, dtype, level) is timed with bench.py's methodology
(fresh inputs per call, forced host read, sync subtraction); results append
to ``tools/warp_corr_profile.json``.

Run on a TPU; compile failures are caught per-config so one Mosaic
rejection cannot sink the sweep. The numbers in the committed JSON are from
an earlier installation; none has been taken on this one.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("VFT_ALLOW_RANDOM_WEIGHTS", "1")

from tools._bench_util import enable_compilation_cache, time_fn  # noqa: E402


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--levels", default="5,4,3,2",
                    help="comma-separated PWC levels to sweep (subset of 5,4,3,2)")
    ap.add_argument("--forward", action="store_true",
                    help="also run the whole-forward xla/auto/auto_nofused sweep")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    enable_compilation_cache()
    print(f"backend: {jax.default_backend()} {jax.devices()[0]}", flush=True)

    # measure the fused kernel DIRECTLY: the production dispatcher's
    # compile/win allowlist would silently substitute the composition at
    # gated-out shapes, mislabeling composition numbers as kernel data
    from video_features_tpu.ops.pallas_corr import (
        corr81,
        warp_corr81,
        warp_corr81_pallas,
    )
    from video_features_tpu.ops.warp import warp_backward

    rng = np.random.default_rng(0)
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "warp_corr_profile.json")
    device = str(jax.devices()[0])
    import subprocess

    try:
        code_rev = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            text=True).strip()
    except Exception:
        code_rev = "unknown"
    results = {}
    try:  # merge-update: --levels split runs must not clobber each other —
        # but only same-device SAME-CODE results merge (stale pre-change
        # kernel timings presented as current data would silently poison the
        # allowlist calibration)
        with open(out_path) as f:
            prev = json.load(f)
        if prev.get("device") == device and prev.get("code_rev") == code_rev:
            results = prev
    except Exception:
        pass
    results["device"] = device
    results["code_rev"] = code_rev

    def flush():
        with open(out_path + ".tmp", "w") as f:
            json.dump(results, f, indent=2)
        os.replace(out_path + ".tmp", out_path)

    b = 16
    # (level, side, channels) at a 256² input; level 6 has no warp.
    # SMALL levels first: they compile in seconds (the fp32 64² fused kernel
    # takes over a minute), so their data lands first.
    levels_all = {5: (5, 8, 128), 4: (4, 16, 96), 3: (3, 32, 64), 2: (2, 64, 32)}
    try:
        levels = tuple(levels_all[int(v)] for v in args.levels.split(","))
    except (KeyError, ValueError):
        ap.error(f"--levels must be a comma-separated subset of "
                 f"{sorted(levels_all)} (got {args.levels!r})")

    import functools

    for level, side, c in levels:
        for dtype_name, dtype in (("float32", jnp.float32),
                                  ("bfloat16", jnp.bfloat16)):
            def mk(side=side, c=c, dtype=dtype):
                f1 = jnp.asarray(rng.normal(size=(b, side, side, c))
                                 .astype(np.float32)).astype(dtype)
                f2 = jnp.asarray(rng.normal(size=(b, side, side, c))
                                 .astype(np.float32)).astype(dtype)
                fl = jnp.asarray(rng.uniform(-6, 6, (b, side, side, 2))
                                 .astype(np.float32))
                return f1, f2, fl

            # "pallas" times warp_corr81_pallas DIRECTLY (bypassing the
            # production allowlist, which would silently substitute the
            # composition at gated-out shapes); "xla" is the gather-warp +
            # fused-XLA-volume composition; "comp" is the PRODUCTION fallback
            # (gather warp + Pallas corr kernels) — the baseline the fused
            # kernel must beat for the allowlist to admit it
            steps = {
                "xla": jax.jit(functools.partial(warp_corr81, impl="xla")),
                "comp": jax.jit(lambda a, b2, fl2: corr81(
                    a, warp_backward(b2, fl2), "auto")),
                "pallas": jax.jit(warp_corr81_pallas),
            }
            for impl in ("xla", "comp", "pallas"):
                name = f"L{level}_{side}x{side}c{c}_{dtype_name}_{impl}"
                try:
                    sec = time_fn(name, steps[impl], mk, iters=8)
                    results[name] = round(sec * 1e3, 4)  # ms/iter (b=16)
                except Exception as e:  # noqa: BLE001 — per-config barrier
                    results[name] = f"FAILED: {str(e)[:200]}"
                    print(f"{name}: FAILED {str(e)[:160]}", flush=True)
                flush()

            # parity of the compiled fused kernel vs the composition on-device
            try:
                f1, f2, fl = mk()
                ref = np.asarray(
                    jax.jit(lambda a, b2, fl2: warp_corr81(a, b2, fl2, "xla"))
                    (f1, f2, fl), dtype=np.float32)
                out = np.asarray(
                    jax.jit(warp_corr81_pallas)(f1, f2, fl), dtype=np.float32)
                err = float(np.max(np.abs(out - ref)))
                scale = float(np.max(np.abs(ref))) or 1.0
                results[f"L{level}_{dtype_name}_max_abs_err"] = err
                print(f"L{level} {dtype_name} parity: max|Δ|={err:.3e} "
                      f"(max|ref|={scale:.3e})", flush=True)
            except Exception as e:  # noqa: BLE001
                results[f"L{level}_{dtype_name}_max_abs_err"] = f"FAILED: {str(e)[:200]}"
            flush()

    if not args.forward:
        print(json.dumps({k: v for k, v in results.items()
                          if not isinstance(v, str)}), flush=True)
        return

    # whole-forward effect: pwc_forward_frames on a 17-frame 256² stack
    from video_features_tpu.models.pwc import pwc_forward_frames, pwc_init_params

    params = pwc_init_params(seed=0)
    params = jax.device_put(params)
    # The round-5 decision matrix for the PWC floor. `auto` (production
    # default) is the gather warp + Pallas volume composition — the fused
    # kernel is OFF under auto until this sweep proves it, so `auto` IS the
    # round-4 "auto_nofused" baseline. The env-tagged configs flip one
    # lowering each: the fused Pallas warp+corr at its admitted levels
    # (VFT_FUSED_WARP_CORR=1), the one-hot MXU warp at ALL levels
    # (VFT_WARP_IMPL=onehot, ops/warp.bilinear_sample_onehot), and both —
    # onehot covering the levels the Mosaic cliff keeps from the fused
    # kernel. User-exported values of both env vars are saved/restored.
    saved_env = {k: os.environ.get(k)
                 for k in ("VFT_FUSED_WARP_CORR", "VFT_WARP_IMPL")}
    matrix = (
        ("xla", "xla", {}),
        ("auto", "auto", {}),
        ("auto", "auto_fused", {"VFT_FUSED_WARP_CORR": "1"}),
        ("auto", "auto_onehot", {"VFT_WARP_IMPL": "onehot"}),
        ("auto", "auto_onehot_fused", {"VFT_WARP_IMPL": "onehot",
                                       "VFT_FUSED_WARP_CORR": "1"}),
    )
    for dtype_name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        for impl, tag, env in matrix:
            name = f"pwc_frames17_256_{dtype_name}_{tag}"
            # clear BOTH knobs first: a user-exported VFT_WARP_IMPL or
            # VFT_FUSED_WARP_CORR must not leak into configs that don't set
            # it, or the baseline rows get measured with the wrong lowering
            for k in saved_env:
                os.environ.pop(k, None)
            for k, v in env.items():
                os.environ[k] = v
            try:
                step = jax.jit(functools.partial(
                    pwc_forward_frames, corr_impl=impl, dtype=dtype))

                def mk_frames():
                    return (params, jnp.asarray(
                        rng.uniform(0, 255, (17, 256, 256, 3)).astype(np.float32)))

                sec = time_fn(name, step, mk_frames, iters=4)
                results[name] = round(sec * 1e3, 4)  # ms per 16-pair stack
            except Exception as e:  # noqa: BLE001
                results[name] = f"FAILED: {str(e)[:200]}"
                print(f"{name}: FAILED {str(e)[:160]}", flush=True)
            finally:
                for k, v in saved_env.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            flush()

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()

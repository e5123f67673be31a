#!/usr/bin/env python3
"""chip_smoke.py [feature_type] — the quickest proof that the system still
starts on the chip.

One process, one chip (or one host's chips), no network, seeded random weights.
For the default feature type (``i3d``: both streams, PWC flow, fp32, 64-frame
stacks) it runs, in order, each phase printing ``[smoke] <phase>: ok (<s> s)``:

1. ``device``  — what JAX found. Anything but a TPU ends the run at once,
   nonzero, with no result line. Also: the mesh every extractor builds spans
   every local device, and a staged batch has one shard per device.
2. ``kernels`` — every kernel of ``ops/pallas_corr.py`` compiled by Mosaic
   (``interpret=False``) at the PWC level shapes of the sample geometry, fp32
   and bf16, against ``corr81_xla``; then the default PWC step is compiled and
   must contain a Mosaic custom call, so ``--pwc_corr auto`` cannot quietly
   have chosen XLA. (Feature types without the PWC net skip this phase.)
3. ``batch``   — ``main.py --feature_type … --video_paths sample/*.mp4
   --on_extraction save_numpy`` through ``run.main``: exit code 0, no failure
   manifest, every ``.npy`` of the expected shape, float32, finite, rows not
   all equal.
4. ``serve``   — ``main.py --serve --spool_dir …`` through ``run.main``: three
   requests from two tenants dropped into the spool (the third resubmits the
   first), every result record ``done``, SIGTERM drains, exit code 0. This is
   the packed/paged loop, which the batch default never takes.
5. ``anchor``  — one small input through the model on the chip and once more
   on this process's CPU backend with the same params.

The first failure ends the run nonzero; no phase is caught and skipped. Files
land under ``output/chip_smoke/<feature_type>/`` (git-ignored). The last line
of standard output is the result object the driver reads.
"""

from __future__ import annotations

import faulthandler
import functools
import glob
import importlib.metadata
import json
import os
import shutil
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SAMPLES = [os.path.join(REPO, "sample", "v_GGSY1Qvo990.mp4"),
           os.path.join(REPO, "sample", "v_ZNVhz7ctTq0.mp4")]
# PWC pyramid levels 6…2 of the samples' 256×341 frames (a 256×384 /64 grid)
PWC_LEVELS = [(4, 6, 196), (8, 12, 128), (16, 24, 96), (32, 48, 64), (64, 96, 32)]
# the driver allows 1200 s; a wedged device must end as a failure with
# tracebacks, not as a silent kill
WATCHDOG_S = 1150
REQUEST_TIMEOUT_S = 900

# Tolerances, as max |chip − reference| over max |reference|.
#
# Kernels: chip against corr81_xla on the chip. Both accumulate in fp32 on the
# VPU (no MXU pass), so fp32 differs by summation order only; bf16 outputs
# round to 8 mantissa bits (2⁻⁸ ≈ 4e-3).
KERNEL_TOL = {"float32": 1e-5, "bfloat16": 1e-2}
# Anchor: chip against this process's CPU backend. On TPU an fp32 conv or
# matmul runs as single bf16 MXU passes by default (2⁻⁸ per product, averaged
# down over the contraction, compounded over depth); the CPU computes true
# fp32. Measured on the v5e in PR 21 (CHANGES.md) and set about 5× above; a
# wrong kernel, layout or weight tree lands at order 1.
ANCHOR_TOL = {
    "i3d_rgb": 2e-2, "pwc": 1e-1, "resnet50": 2e-2, "r21d_rgb": 2e-2,
    "raft": 1e-1, "vggish": 3e-2,
}
# batch against serve: the same videos through the per-video loop and through
# the paged loop — the same arithmetic in differently shaped programs
PATH_TOL = 2e-2

_phases: dict = {}


def phase(name, fn, *args):
    """Run ``fn`` as a named phase: one ok line with its seconds, or the
    exception ends the run."""
    t0 = time.perf_counter()
    out = fn(*args)
    _phases[name] = round(time.perf_counter() - t0, 1)
    print(f"[smoke] {name}: ok ({_phases[name]} s)", flush=True)
    return out


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.all(np.isfinite(got)), "non-finite values from the chip"
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


# --------------------------------------------------------------------------
# phase 1: device
# --------------------------------------------------------------------------

def check_device(cache_dir):
    import jax
    import jaxlib
    import numpy as np

    from video_features_tpu.parallel.mesh import MeshRunner

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a distribution"
    print(f"[smoke] platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} local={jax.local_device_count()} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"compile_cache={cache_dir}", flush=True)
    print("[smoke] weights: seeded random (VFT_ALLOW_RANDOM_WEIGHTS=1; no "
          "checkpoints and no network on this machine)", flush=True)
    # the mesh an extractor builds with the default --num_devices
    runner = MeshRunner()
    n = jax.local_device_count()
    assert runner.num_devices == n, (runner.num_devices, n)
    staged = runner.put(np.zeros((runner.device_batch(1), 8, 8, 3), np.uint8))
    shard_devices = {s.device for s in staged.addressable_shards}
    assert len(staged.addressable_shards) == n and len(shard_devices) == n, \
        (len(staged.addressable_shards), len(shard_devices), n)
    print(f"[smoke] mesh spans {n} device(s); a staged batch of "
          f"{staged.shape[0]} has {n} shard(s), one per device", flush=True)


# --------------------------------------------------------------------------
# phase 2: kernels
# --------------------------------------------------------------------------

def check_kernels(pwc_step):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from video_features_tpu.ops import pallas_corr as pc

    rng = np.random.default_rng(0)
    ran = 0
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        isz = jnp.dtype(dtype).itemsize
        for (h, w, c) in PWC_LEVELS:
            f1 = jnp.asarray(rng.standard_normal((2, h, w, c)), dtype)
            f2 = jnp.asarray(rng.standard_normal((2, h, w, c)), dtype)
            want = jax.jit(pc.corr81_xla)(f1, f2)
            for kernel, admitted, run in (
                ("single", pc._pallas_supported(h, w, c, isz), pc.corr81_pallas),
                ("tiled", pc._pallas_tiled_supported(h, w, c, isz),
                 pc.corr81_pallas_tiled),
            ):
                if not admitted:
                    print(f"[smoke]   {kernel:6s} {name:8s} {h}x{w}x{c}: "
                          "excluded by its gate", flush=True)
                    continue
                err = rel_err(run(f1, f2), want)
                print(f"[smoke]   {kernel:6s} {name:8s} {h}x{w}x{c}: "
                      f"rel err {err:.2e}", flush=True)
                assert err <= KERNEL_TOL[name], (kernel, name, (h, w, c), err)
                ran += 1
    assert ran, "no kernel ran"
    # what `auto` selects per level for the default (fp32) flow net
    for (h, w, c) in PWC_LEVELS:
        choice = pc.corr81_lowering((16, h, w, c), jnp.float32, jnp.float32, "auto")
        print(f"[smoke]   auto float32 {h}x{w}x{c}: {choice}", flush=True)
        assert choice.startswith("pallas"), (h, w, c, choice)
    # … and the compiled default step must really hold the kernels
    forward, params, stack = pwc_step
    compiled = jax.jit(functools.partial(forward, "auto")).lower(params, stack).compile()
    n_calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"[smoke]   compiled default PWC step holds {n_calls} Mosaic "
          "custom call(s)", flush=True)
    assert n_calls >= 1, "--pwc_corr auto compiled without a Mosaic kernel"


def pwc_default_step():
    """The flow net as the default I3D step runs it on one 16-pair chunk:
    ``--pwc_corr auto``, fp32 — jitted, with its params and 17 real frames."""
    import jax
    import numpy as np

    from video_features_tpu.io.video import open_video
    from video_features_tpu.models.pwc import pwc_forward_frames, pwc_init_params
    from video_features_tpu.ops.image import pil_edge_resize

    _meta, frames = open_video(SAMPLES[0], transform=lambda f: pil_edge_resize(f, 256))
    stack = []
    for rgb, _pos in frames:
        stack.append(rgb)
        if len(stack) == 17:
            break
    stack = np.stack(stack)  # (17, 256, 341, 3) uint8
    params = pwc_init_params(seed=0)

    def forward(impl, p, fr):
        return pwc_forward_frames(p, fr, corr_impl=impl)

    return forward, params, stack


# --------------------------------------------------------------------------
# phases 3 and 4: the entry points
# --------------------------------------------------------------------------

def expected_outputs(feature_type, video):
    """{suffix: check(array)} for one input's saved features."""
    import numpy as np

    def rows(width, n=None):
        def check(a):
            assert a.ndim == 2 and a.shape[1] == width, a.shape
            if n is not None:
                assert a.shape[0] == n, (a.shape, n)
            assert a.shape[0] >= 2, a.shape
            assert not np.all(a == a[0]), "every row equal"
        return check

    def flow(a):  # (pairs, 2, H, W), the reference's layout
        assert a.ndim == 4 and a.shape[1] == 2 and a.shape[0] >= 2, a.shape
        assert not np.all(a == a[0]), "every flow field equal"

    if feature_type == "i3d":
        # 355 and 420 frames in 64-frame stacks at stride 64
        stacks = {SAMPLES[0]: 5, SAMPLES[1]: 6}[video]
        return {"rgb": rows(1024, stacks), "flow": rows(1024, stacks)}
    return {"resnet50": {"resnet50": rows(2048)},
            "r21d_rgb": {"r21d_rgb": rows(512)},
            "raft": {"raft": flow}, "pwc": {"pwc": flow},
            "vggish": {"vggish": rows(128)}}[feature_type]


def check_outputs(feature_type, out_dir, videos):
    import numpy as np

    from video_features_tpu.reliability import failed_manifest_path

    found = {}
    for video in videos:
        stem = os.path.splitext(os.path.basename(video))[0]
        for suffix, check in expected_outputs(feature_type, video).items():
            path = os.path.join(out_dir, feature_type, f"{stem}_{suffix}.npy")
            a = np.load(path)
            assert a.dtype == np.float32, (path, a.dtype)
            assert np.all(np.isfinite(a)), f"{path}: non-finite values"
            check(a)
            found[f"{stem}_{suffix}"] = a
            print(f"[smoke]   {os.path.relpath(path, REPO)}: {a.shape} "
                  f"{a.dtype} |max| {np.abs(a).max():.3g}", flush=True)
    manifest = failed_manifest_path(os.path.join(out_dir, feature_type))
    assert not os.path.exists(manifest), f"a failure manifest exists: {manifest}"
    return found


def cli_argv(feature_type, out_dir):
    return ["--feature_type", feature_type, "--on_extraction", "save_numpy",
            "--output_path", os.path.join(out_dir, "out"),
            "--tmp_path", os.path.join(out_dir, "tmp")]


def run_batch(feature_type, root, videos):
    from video_features_tpu import run

    out = os.path.join(root, "batch")
    rc = run.main(cli_argv(feature_type, out) + ["--video_paths", *videos])
    assert rc == 0, f"run.main returned {rc}"
    return check_outputs(feature_type, os.path.join(out, "out"), videos)


def drop_request(spool, request_id, payload):
    tmp = os.path.join(spool, f".{request_id}.tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(spool, f"{request_id}.json"))


def feed_requests(spool, videos, failure):
    """The tenants: drop two requests, wait, resubmit the first, wait, then
    send the daemon SIGTERM. Whatever goes wrong is kept for the main thread,
    and the drain is sent regardless so the main thread returns."""
    results = os.path.join(spool, "results")

    def wait_for(ids):
        deadline = time.monotonic() + REQUEST_TIMEOUT_S
        paths = [os.path.join(results, f"{i}.result.json") for i in ids]
        while not all(os.path.exists(p) for p in paths):
            if time.monotonic() > deadline:
                raise TimeoutError(f"no result record for {ids} after "
                                   f"{REQUEST_TIMEOUT_S} s")
            time.sleep(0.2)

    try:
        drop_request(spool, "req_alice", {"tenant": "alice", "videos": videos[:1]})
        drop_request(spool, "req_bob", {"tenant": "bob", "videos": videos[1:]})
        wait_for(["req_alice", "req_bob"])
        drop_request(spool, "req_alice_again",
                     {"tenant": "alice", "videos": videos[:1]})
        wait_for(["req_alice_again"])
    except BaseException as e:  # noqa: BLE001 — re-raised by the main thread
        failure.append(e)
    finally:
        os.kill(os.getpid(), signal.SIGTERM)


def run_serve(feature_type, root, videos, batch):
    from video_features_tpu import run

    out = os.path.join(root, "serve")
    spool = os.path.join(out, "spool")
    os.makedirs(spool)
    failure: list = []
    feeder = threading.Thread(target=feed_requests, args=(spool, videos, failure),
                              name="smoke-tenants", daemon=True)
    handlers = {s: signal.getsignal(s)
                for s in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)}
    feeder.start()
    try:
        rc = run.main(cli_argv(feature_type, out) + ["--serve", "--spool_dir", spool])
    finally:
        for s, h in handlers.items():
            signal.signal(s, h)
    feeder.join(timeout=10)
    assert not feeder.is_alive(), "the tenant thread did not finish"
    if failure:
        raise failure[0]
    assert rc == 0, f"the daemon returned {rc}"
    records = sorted(glob.glob(os.path.join(spool, "results", "*.result.json")))
    assert len(records) == 3, records
    for path in records:
        with open(path) as f:
            record = json.load(f)
        assert record["state"] == "done", record
        assert len(record["done"]) == 1 and not record.get("failed"), record
        print(f"[smoke]   {os.path.relpath(path, REPO)}: {record['state']} "
              f"(tenant {record['tenant']})", flush=True)
    served = check_outputs(feature_type, os.path.join(out, "out"), videos)
    # the same videos through both loops must give the same features
    assert batch.keys() == served.keys(), (sorted(batch), sorted(served))
    worst = max(rel_err(served[k], batch[k]) for k in batch)
    print(f"[smoke]   batch vs serve: worst rel diff {worst:.2e}", flush=True)
    assert worst <= PATH_TOL, worst


# --------------------------------------------------------------------------
# phase 5: numeric anchor
# --------------------------------------------------------------------------

def anchors(feature_type, pwc_step):
    """[(name, chip_fn, cpu_fn, args)] — small inputs through ``models/``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from video_features_tpu.weights.store import random_params_like

    rng = np.random.default_rng(0)

    def flax_anchor(name, model, dummy, x, apply):
        init = lambda r, d: model.init(r, d, features=False)  # noqa: E731
        params = random_params_like(init, jax.random.PRNGKey(0), dummy)["params"]
        fn = jax.jit(lambda p, a: apply(model, p, a))
        return (name, fn, fn, (params, x))

    def pwc_anchor():
        forward, params, stack = pwc_step
        return ("pwc", jax.jit(functools.partial(forward, "auto")),
                jax.jit(functools.partial(forward, "xla")), (params, stack))

    if feature_type == "i3d":
        from video_features_tpu.models.i3d import I3D

        clip = rng.uniform(-1, 1, (1, 64, 224, 224, 3)).astype(np.float32)
        return [flax_anchor("i3d_rgb", I3D(modality="rgb"),
                            jnp.zeros((1, 16, 224, 224, 3)), clip,
                            lambda m, p, a: m.apply({"params": p}, a, features=True)),
                pwc_anchor()]
    if feature_type == "pwc":
        return [pwc_anchor()]
    if feature_type == "resnet50":
        from video_features_tpu.models.resnet import ResNet50, preprocess_frames

        frames = rng.integers(0, 256, (4, 224, 224, 3), dtype=np.uint8)
        return [flax_anchor(
            "resnet50", ResNet50(), jnp.zeros((1, 224, 224, 3)), frames,
            lambda m, p, a: m.apply({"params": p}, preprocess_frames(a), features=True))]
    if feature_type == "r21d_rgb":
        from video_features_tpu.models.r21d import R2Plus1D18

        clip = rng.uniform(-1, 1, (1, 16, 112, 112, 3)).astype(np.float32)
        return [flax_anchor(
            "r21d_rgb", R2Plus1D18(), jnp.zeros((1, 4, 112, 112, 3)), clip,
            lambda m, p, a: m.apply({"params": p}, a, features=True))]
    if feature_type == "raft":
        from video_features_tpu.models.raft import raft_forward_frames, raft_init_params

        # Two refinement iterations, not the model's twenty: every part runs
        # (encoders, correlation pyramid, a lookup at fractional coordinates,
        # GRU, convex upsampling), but on random weights the recurrence is an
        # amplifier — each iteration adds ~38 px of flow, and chip-vs-CPU
        # grows 2.0e-2 → 2.9e-2 → 8.0e-2 → 1.6e-1 over 1, 2, 4, 20
        # iterations (5.5e-2 at 20 under Precision.HIGHEST; v5e, PR 21)
        _forward, _params, stack = pwc_step  # the same real frames
        fn = jax.jit(lambda p, fr: raft_forward_frames(p, fr, iters=2,
                                                       corr_impl="auto"))
        return [("raft", fn, fn, (raft_init_params(seed=0), stack[:3, :, :336]))]
    if feature_type == "vggish":
        from video_features_tpu.models.vggish import VGGish, vggish_init_params

        examples = rng.standard_normal((4, 96, 64)).astype(np.float32)
        fn = jax.jit(lambda p, a: VGGish().apply({"params": p}, a))
        return [("vggish", fn, fn, (vggish_init_params(seed=0), examples))]
    raise ValueError(feature_type)


def check_anchor(feature_type, pwc_step):
    import jax

    cpu = jax.devices("cpu")[0]
    chip = jax.devices()[0].platform  # main() has established this is the TPU
    for name, chip_fn, cpu_fn, args in anchors(feature_type, pwc_step):
        got = chip_fn(*args)
        assert {d.platform for d in got.devices()} == {chip}, got.devices()
        want = cpu_fn(*jax.device_put(args, cpu))
        assert {d.platform for d in want.devices()} == {"cpu"}, want.devices()
        err = rel_err(got, want)
        print(f"[smoke]   {name}: chip vs CPU rel err {err:.2e} over "
              f"{got.shape} (tolerance {ANCHOR_TOL[name]:.0e})", flush=True)
        assert err <= ANCHOR_TOL[name], (name, err)


# --------------------------------------------------------------------------

def synthetic_wavs(root):
    """Two seeded 16 kHz mono .wav files (there is no ffmpeg to pull audio out
    of the sample videos): tones under noise, 12 s and 9 s."""
    import wave

    import numpy as np

    paths = []
    for i, seconds in enumerate((12, 9)):
        rng = np.random.default_rng(i)
        t = np.arange(16000 * seconds) / 16000.0
        pcm = (0.3 * np.sin(2 * np.pi * (220 * (i + 1)) * t)
               + 0.2 * np.sin(2 * np.pi * 1730 * t * (1 + 0.1 * t))
               + 0.05 * rng.standard_normal(t.shape))
        path = os.path.join(root, f"tone{i}.wav")
        with wave.open(path, "wb") as w:
            w.setnchannels(1)
            w.setsampwidth(2)
            w.setframerate(16000)
            w.writeframes((np.clip(pcm, -1, 1) * 32767).astype("<i2").tobytes())
        paths.append(path)
    return paths


def main(argv) -> int:
    feature_type = argv[1] if len(argv) > 1 else "i3d"
    # the anchor needs this process's CPU backend beside the chip; the first
    # platform named stays the default
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    os.environ["VFT_ALLOW_RANDOM_WEIGHTS"] = "1"
    t0 = time.perf_counter()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform={dev.platform!r} "
              f"({dev.device_kind!r} x{len(jax.devices())})", file=sys.stderr)
        return 1
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    from video_features_tpu.config import FEATURE_TYPES
    from video_features_tpu.parallel.mesh import enable_compilation_cache

    if feature_type not in FEATURE_TYPES:
        print(f"chip_smoke: unknown feature type {feature_type!r}; one of "
              f"{FEATURE_TYPES}", file=sys.stderr)
        return 2
    cache_dir = enable_compilation_cache()
    root = os.path.join(REPO, "output", "chip_smoke", feature_type)
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)

    phase("device", check_device, cache_dir)
    pwc_step = pwc_default_step() if feature_type in ("i3d", "pwc", "raft") else None
    if feature_type in ("i3d", "pwc"):
        phase("kernels", check_kernels, pwc_step)
    videos = synthetic_wavs(root) if feature_type == "vggish" else SAMPLES
    batch = phase(f"batch {feature_type}", run_batch, feature_type, root, videos)
    phase(f"serve {feature_type}", run_serve, feature_type, root, videos, batch)
    phase("numeric anchor", check_anchor, feature_type, pwc_step)

    faulthandler.cancel_dump_traceback_later()
    wall = round(time.perf_counter() - t0, 1)
    print("[smoke] summary: " + json.dumps(
        {"feature_type": feature_type, "wall_s": wall, "phases": _phases}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

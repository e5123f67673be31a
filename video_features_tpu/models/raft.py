"""RAFT optical flow in JAX (NHWC, functional, scan-tied update loop).

Behavioral spec — ``/root/reference/models/raft/raft_src/``:
- Input pair normalized ``2·(x/255) − 1`` (``raft.py:118-119``); images pre-padded to
  /8 multiples by the extractor (replicate, sintel split — ``raft.py:27-44``).
- ``fnet`` (instance norm) → 256-d features at 1/8 res for both frames;
  ``cnet`` (eval batch norm) → 128 tanh hidden + 128 relu context (``raft.py:127-143``).
- All-pairs correlation ``⟨f1, f2⟩/√256`` pooled into a 4-level pyramid
  (``corr.py:12-27,52-60``); each iteration gathers a 9×9 bilinear window per level
  at the current flow (``corr.py:29-50``) — torch's channel order (the reference
  swaps dx/dy when building the delta grid, ``corr.py:37-43``) is reproduced exactly
  because the update-block weights were trained against it.
- 20 iterations of motion encoder + separable ConvGRU + flow head
  (``update.py:37-139``, ``raft.py:151-168``) — here one ``lax.scan`` body.
- Convex upsampling ×8 with a learned 9-tap softmax mask (``raft.py:100-111``),
  computed ONCE after the loop (the reference recomputes it every iteration and
  discards all but the last in test mode — identical output, 20× less upsample work).

Weight-tied loops are why this model is functional over a param pytree instead of a
linen module: ``lax.scan`` over pure functions keeps the compiled HLO one body long.
Param tree names mirror the torch checkpoint (minus the ``module.`` prefix) so
conversion is mechanical (:func:`video_features_tpu.weights.convert_torch.convert_raft`).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.nnf import avg_pool2d, batch_norm_eval, conv2d, instance_norm
from ..ops.warp import coords_grid, equalize_chunks

HIDDEN_DIM = 128
CONTEXT_DIM = 128
CORR_LEVELS = 4
CORR_RADIUS = 4
ITERS = 20  # reference inference default (raft.py:115)

# HBM budget for the materialized all-pairs pyramid; past it, corr_impl
# "auto" switches to the on-demand path (the alt_cuda_corr equivalent).
# ~4 GiB leaves room for the one-hot selectors, activations, and double
# buffering on a 16 GiB chip.
_VOLUME_HBM_BUDGET = 4 * 1024**3


def resolve_corr_impl(corr_impl: str, n_pairs: int, h: int, w: int,
                      dtype=jnp.float32, n_devices: int = 1) -> str:
    """Resolve ``auto`` per frame geometry: the reference-default materialized
    volume while it fits, the O(H·W·D) on-demand GATHER path beyond
    (``--raft_corr on_demand_matmul`` asks for the MXU volume remat — its
    FLOPs scale with frame area, the gather's with the fixed window; see the
    big-frame comment below). In fp32 the paths agree to reduction-order ulps
    (~3e-3 px through 20 iterations, a CPU comparison of an earlier round); under
    ``dtype=bfloat16`` the volume path stores a bf16 pyramid while the remat
    rounds the einsum inputs — the same one-bf16-rounding drift class,
    bounded in tests/test_flow_bf16.py.

    The pyramid holds ``n_pairs · (h/8·w/8)² · Σ4⁻ˡ`` correlation values
    (corr.py:12-27 geometry); e.g. 16 pairs at 1080p → ~89 GB fp32, several
    times HBM — exactly the case the reference's alt_cuda_corr serves.

    ``n_devices``: mesh size of the surrounding sharded step. Inside a jit the
    traced ``n_pairs`` is the GLOBAL pair count but each device materializes
    only its ``n_pairs / n_devices`` shard of the pyramid, so the budget
    (``_VOLUME_HBM_BUDGET`` bytes, per device) is compared against the
    per-device share — without it a mesh-sharded step near the boundary would
    needlessly take the ~40× slower on-demand path.
    """
    if corr_impl != "auto":
        return corr_impl
    q = (h // 8) * (w // 8)
    itemsize = 2 if dtype == jnp.bfloat16 else 4
    per_device_pairs = max(1, -(-n_pairs // max(n_devices, 1)))
    vol_bytes = per_device_pairs * q * q * itemsize * (1 + 1 / 4 + 1 / 16 + 1 / 64)
    if vol_bytes <= _VOLUME_HBM_BUDGET:
        return "volume"
    # past the budget, the GATHER formulation is the default (ADVICE r5
    # revert): the matmul remat's contraction FLOPs per query scale with the
    # level's hi·wi (quadratic in frame area) while the gather's scale with
    # the fixed 10×10 window, so the 3.2-3.6× win measured at 64×64 on CPU
    # can invert by ~300× more remat work at 1080p — exactly the regime auto
    # selects this path. Flip to matmul only on a committed 1080p TPU
    # measurement (ROADMAP S5); ``on_demand_matmul`` asks for it per run.
    return "on_demand"

# (name, cin, cout, kernel, stride, pad) for plain convs; residual layers described
# structurally in _encoder below.
ENCODER_DIMS = (64, 64, 96, 128)  # stem, layer1, layer2, layer3


def _relu(x):
    return jnp.maximum(x, 0)


def _norm(p: dict, x: jnp.ndarray, kind: str, name: str) -> jnp.ndarray:
    if kind == "instance":
        return instance_norm(x)
    if kind == "batch":
        return batch_norm_eval(p[name], x)
    return x


def _residual_block(p: dict, x: jnp.ndarray, kind: str, stride: int) -> jnp.ndarray:
    y = _relu(_norm(p, conv2d(p["conv1"], x, stride, 1), kind, "norm1"))
    y = _relu(_norm(p, conv2d(p["conv2"], y, 1, 1), kind, "norm2"))
    if stride != 1:
        x = _norm(p, conv2d(p["downsample.0"], x, stride, 0), kind, "norm3")
    return _relu(x + y)


def _encoder(p: dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    """BasicEncoder (extractor.py:118-192): 7×7/2 stem + 3 residual stages + 1×1."""
    x = _relu(_norm(p, conv2d(p["conv1"], x, 2, 3), kind, "norm1"))
    for stage, stride in (("layer1", 1), ("layer2", 2), ("layer3", 2)):
        x = _residual_block(p[f"{stage}.0"], x, kind, stride)
        x = _residual_block(p[f"{stage}.1"], x, kind, 1)
    return conv2d(p["conv2"], x, 1, 0)


def _build_pyramid(f1: jnp.ndarray, f2: jnp.ndarray,
                   dtype=jnp.float32) -> Tuple[jnp.ndarray, ...]:
    """All-pairs correlation volume pooled over target resolution (corr.py:12-27).

    ``dtype=bfloat16`` stores the (H·W)² volume in bf16 — half the HBM for the
    framework's largest tensor and half the lookup read traffic; the einsum
    still accumulates in fp32 before the cast.
    """
    b, h, w, d = f1.shape
    corr = jnp.einsum("bijc,bklc->bijkl", f1.astype(jnp.float32), f2.astype(jnp.float32))
    corr = (corr / math.sqrt(d)).astype(dtype)
    corr = corr.reshape(b * h * w, h, w, 1)
    pyramid = [corr]
    for _ in range(CORR_LEVELS - 1):
        corr = avg_pool2d(corr, 2, 2)  # fp32 accumulation, cast back inside
        pyramid.append(corr)
    return tuple(pyramid)


def _int_window(c: jnp.ndarray):
    """Integer tap indices and bilinear fractions for a 10×10 window.

    ``c``: (..., 2) level-scaled window centers. Returns ``(ix, iy, fx, fy)``
    with taps (..., 10) covering offsets −4…+5 (all 81 corners of the 9×9
    window share these integer taps) and fractions (...,).
    """
    cf = jnp.floor(c)
    off = jnp.arange(-CORR_RADIUS, CORR_RADIUS + 2, dtype=jnp.int32)  # (10,)
    ix = cf[..., 0].astype(jnp.int32)[..., None] + off
    iy = cf[..., 1].astype(jnp.int32)[..., None] + off
    return ix, iy, c[..., 0] - cf[..., 0], c[..., 1] - cf[..., 1]


def _tap_index_mask(ix: jnp.ndarray, iy: jnp.ndarray, hi: int, wi: int):
    """Clipped per-image flat indices and in-bounds mask for a (10y, 10x) patch.

    ``idx`` (..., 10y, 10x) indexes a row-major (hi·wi) plane; ``mask`` zeroes
    out-of-bounds taps after the clipped gather — the reference's zero-padding
    semantics (grid_sample padding_mode='zeros', per corner tap). Per-image
    offsets stay bounded by hi·wi (a global arange(n)·hi·wi base would overflow
    int32 for large frames × batch).
    """
    idx = (jnp.clip(iy, 0, hi - 1)[..., :, None] * wi
           + jnp.clip(ix, 0, wi - 1)[..., None, :])
    mask = (((iy >= 0) & (iy <= hi - 1))[..., :, None]
            & ((ix >= 0) & (ix <= wi - 1))[..., None, :])
    return idx, mask


def _combine_window(patch: jnp.ndarray, fx: jnp.ndarray, fy: jnp.ndarray) -> jnp.ndarray:
    """(..., 10y, 10x) integer patch → (..., 81) bilinear window values.

    Four shifted elementwise combinations (identical arithmetic to per-point
    bilinear sampling: 4 products + 3 adds per value), flattened x-major —
    channel k = i·9 + j samples (δ_i in x, δ_j in y), the reference's
    delta-grid axis swap (corr.py:37-43) that the update-block weights were
    trained against.
    """
    fx = fx.astype(patch.dtype)[..., None, None]  # keep bf16 paths bf16 (a
    fy = fy.astype(patch.dtype)[..., None, None]  # fp32 fraction would promote)
    v = (
        (1 - fy) * (1 - fx) * patch[..., :-1, :-1]
        + (1 - fy) * fx * patch[..., :-1, 1:]
        + fy * (1 - fx) * patch[..., 1:, :-1]
        + fy * fx * patch[..., 1:, 1:]
    )  # (..., 9y, 9x)
    sw = jnp.swapaxes(v, -1, -2)  # x-major
    return sw.reshape(sw.shape[:-2] + ((2 * CORR_RADIUS + 1) ** 2,))


def _lookup(pyramid, coords: jnp.ndarray, impl: str = "matmul") -> jnp.ndarray:
    """9×9 bilinear window per level around the current correspondence,
    flattened i-major (δx-major) into 81 channels per level.

    TPU formulation: every window point shares the query's fractional offset
    (the 81 deltas are integers), so the whole window is ONE 10×10 integer
    patch per query, and the 81 bilinear values are four shifted elementwise
    combinations of that patch — identical arithmetic to per-point bilinear
    sampling (4 products + 3 adds per value).

    The patch extraction itself has two lowerings:
    - ``matmul`` (default): two one-hot batched matmuls — rows then columns —
      so the data-dependent 2-D slice runs on the MXU instead of the scalar
      gather unit. Out-of-bounds taps fall out as all-zero one-hot rows, which
      IS the reference's zero-padding semantics (grid_sample
      padding_mode='zeros', per corner tap). Measured on TPU v5e at batch
      16 × 256² (an earlier installation's stage profile; not measured on
      this one): 20 lookups 1370 ms → 63 ms; full
      20-iteration forward 1551 ms → 100 ms (15.5×).
    - ``gather``: one ``take_along_axis`` patch gather per level (the exact
      arithmetic reference path; also the faster lowering on CPU).
    """
    b, h, w, _ = coords.shape
    r = CORR_RADIUS
    n = b * h * w
    win = 2 * r + 2  # 10 taps per axis
    out = []
    for i, corr in enumerate(pyramid):
        hi, wi = corr.shape[1], corr.shape[2]
        if hi == 0 or wi == 0:
            # tiny inputs can pool a pyramid level away entirely; every tap is
            # out of bounds → zeros (the per-corner mask semantics)
            out.append(jnp.zeros((b, h, w, (2 * r + 1) ** 2), corr.dtype))
            continue
        ix, iy, fx, fy = _int_window((coords / 2**i).reshape(n, 2))
        if impl == "matmul":
            # one-hot row/column selectors; comparisons against the level's
            # iota leave out-of-bounds taps as all-zero rows — exactly the
            # zero-padding semantics (grid_sample padding_mode='zeros')
            sy = (iy[:, :, None] == jnp.arange(hi, dtype=jnp.int32)[None, None, :])
            sx = (ix[:, :, None] == jnp.arange(wi, dtype=jnp.int32)[None, None, :])
            # fp32 volume: HIGHEST — selection against 0/1 has one nonzero
            # product per output, so the lowering is bit-identical to the
            # gather path even when surrounding convs run default precision.
            # bf16 volume (flow_dtype bf16): default precision — a one-hot
            # selection has no accumulation error at ANY precision, only the
            # value rounding the bf16 volume already paid, and the MXU runs
            # single-pass instead of the 6-pass fp32 sequence (the lookup is
            # 70% of the fp32 step: 77.7 of 111 ms at b16·256², an earlier
            # installation's stage profile).
            prec = (lax.Precision.HIGHEST if corr.dtype == jnp.float32
                    else lax.Precision.DEFAULT)
            rows = jnp.einsum("npi,nij->npj", sy.astype(corr.dtype),
                              corr.reshape(n, hi, wi), precision=prec)
            patch = jnp.einsum("npj,nqj->npq", rows, sx.astype(corr.dtype),
                               precision=prec)
        elif impl == "gather":
            idx, mask = _tap_index_mask(ix, iy, hi, wi)
            patch = jnp.take_along_axis(corr.reshape(n, hi * wi),
                                        idx.reshape(n, win * win), axis=1)
            patch = patch.reshape(n, win, win)  # ONE gather per level
            patch = patch * mask.astype(patch.dtype)
        else:
            raise ValueError(f"lookup impl must be matmul|gather, got {impl!r}")
        out.append(_combine_window(patch, fx, fy).reshape(b, h, w, -1))
    return jnp.concatenate(out, axis=-1)  # (B, H, W, 4·81)


def _build_f2_pyramid(f2: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Pooled TARGET features for on-demand correlation.

    The TPU-native equivalent of the reference's optional ``alt_cuda_corr``
    extension (corr.py:63-91): instead of materializing the (H·W)² volume,
    exploit linearity — avg-pooling the volume over target coordinates equals
    correlating against avg-pooled f2, and bilinear lookup is linear too, so
    ``sample(pool(corr))(x, p) == ⟨f1(x), sample(pool(f2))(p)⟩``. Memory drops
    from O((H·W)²) to O(H·W·D); FLOPs drop too once H·W > 81·levels·iters.
    """
    pyr = [f2]
    for _ in range(CORR_LEVELS - 1):
        pyr.append(avg_pool2d(pyr[-1], 2, 2))
    return tuple(pyr)


def _lookup_on_demand(f1: jnp.ndarray, f2_pyramid, coords: jnp.ndarray,
                      impl: str = "gather",
                      chunk_budget: int = 16_000_000,
                      dtype=jnp.float32) -> jnp.ndarray:
    """Correlation window computed on the fly from pooled-f2 features — the
    memory-bounded path (O(H·W·D), no persistent (H·W)² volume).

    ``impl='gather'``: bilinear interpolation commutes with the channel dot
    product, so instead of sampling f2 at 81 fractional points (324 corner
    gathers of D-vectors per query), gather ONE 10×10 integer patch of f2
    vectors per query per level, contract with f1 on the MXU, and form the 81
    bilinear values as four shifted combinations of the (10, 10) correlation
    patch — ~3× fewer gathered bytes and one gather per level. Numerics
    identical to the fractional-point formulation up to fp reduction order
    (the bilinear weights multiply the same products).

    ``impl='matmul'``: zero gathers — rematerialize the chunk's slice of the
    correlation volume each call (``einsum('bnc,bijc->bnij')``, pure MXU) and
    select the 10×10 window with the volume path's one-hot matmuls
    (models/raft.py one-hot trick, 15.5× there). The volume slice does not
    persist: O(chunk·hᵢ·wᵢ) live bytes, bounded by ``chunk_budget`` elements
    per batch element via ``lax.scan`` over query chunks. Against the gather
    impl this trades ITERS× recomputed volume FLOPs (MXU-cheap) for zero
    scalar-unit gather traffic (the measured 40× cliff); against ``volume``
    it trades the same FLOPs for the O((H·W)²) HBM the big-frame regime
    doesn't have. Reference anchor: ``alt_cuda_corr``
    (/root/reference/models/raft/corr.py:63-91) recomputes per-iteration too.

    ``dtype=bfloat16`` (matmul impl only): the vol einsum's INPUTS are cast
    bf16 (fp32 accumulation via preferred_element_type) — halves the remat's
    HBM reads and runs single-pass on the MXU instead of the fp32 3-pass
    default. Same drift class as the volume path's bf16 pyramid storage
    (that path rounds the correlation values AFTER the product; this rounds
    the features BEFORE — both one bf16 rounding of the lookup input,
    bounded in tests/test_flow_bf16.py). The gather impl stays fp32: its
    cost is the gather, not the contraction.
    """
    if impl not in ("gather", "matmul"):
        raise ValueError(
            f"on-demand lookup impl must be gather|matmul, got {impl!r}")
    b, h, w, d = f1.shape
    r = CORR_RADIUS
    win = 2 * r + 2  # 10 taps per axis
    scale = 1.0 / math.sqrt(d)
    f1 = f1.astype(jnp.float32)
    n = h * w
    out = []
    for i, f2i in enumerate(f2_pyramid):
        hi, wi = f2i.shape[1], f2i.shape[2]
        if hi == 0 or wi == 0:
            # tiny inputs can pool a pyramid level away entirely; every tap is
            # out of bounds → zeros (the per-corner mask semantics)
            out.append(jnp.zeros((b, h, w, (2 * r + 1) ** 2), jnp.float32))
            continue
        ix, iy, fx, fy = _int_window((coords / 2**i).reshape(b, n, 2))
        if impl == "matmul":
            n_chunks, chunk, pad = equalize_chunks(n, chunk_budget // (hi * wi))

            def prep(a):  # (b, n, ...) → (n_chunks, b, chunk, ...)
                a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                return a.reshape((b, n_chunks, chunk) + a.shape[2:]).swapaxes(0, 1)

            vol_in = jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32
            f2f = f2i.astype(vol_in)
            iota_h = jnp.arange(hi, dtype=jnp.int32)
            iota_w = jnp.arange(wi, dtype=jnp.int32)

            def body(_, args):
                f1c, ixc, iyc = args  # (b, chunk, d), (b, chunk, 10), ...
                # fp32 mode: DEFAULT precision — the same contraction
                # precision the gather impl's f1·patch einsum runs at.
                # bf16 mode: bf16 inputs (pre-cast below, so the scanned f1
                # slices are read half-width too), fp32 accumulator
                vol = jnp.einsum("bnc,bijc->bnij", f1c, f2f,
                                 preferred_element_type=jnp.float32)
                sy = (iyc[..., None] == iota_h).astype(jnp.float32)
                sx = (ixc[..., None] == iota_w).astype(jnp.float32)
                # HIGHEST: one-hot selection must pass vol values through
                # unrounded (one nonzero product per output); costs only
                # 10/d of the vol einsum
                rows = jnp.einsum("bnpi,bnij->bnpj", sy, vol,
                                  precision=lax.Precision.HIGHEST)
                patch = jnp.einsum("bnqj,bnpj->bnpq", sx, rows,
                                   precision=lax.Precision.HIGHEST)
                return None, patch * scale

            _, patch = lax.scan(
                body, None,
                (prep(f1.reshape(b, n, d).astype(vol_in)), prep(ix), prep(iy)))
            patch = patch.swapaxes(0, 1).reshape(b, n_chunks * chunk,
                                                 win, win)[:, :n]
            # OOB taps already zero (equality falls off the iota) — same
            # semantics as the gather impl's explicit mask
        else:
            idx, mask = _tap_index_mask(ix, iy, hi, wi)  # (B, HW, 10y, 10x)
            flat = f2i.reshape(b, hi * wi, -1).astype(jnp.float32)
            patch_f = jnp.take_along_axis(
                flat[:, None], idx.reshape(b, 1, n * win * win)[..., None], axis=2
            ).reshape(b, n, win, win, -1)  # (B, HW, 10, 10, D) one gather/level
            patch = jnp.einsum("bnc,bnpqc->bnpq", f1.reshape(b, n, d), patch_f) * scale
            patch = patch * mask
        out.append(_combine_window(patch, fx, fy).reshape(b, h, w, -1))
    return jnp.concatenate(out, axis=-1)


def _motion_encoder(p: dict, flow: jnp.ndarray, corr: jnp.ndarray) -> jnp.ndarray:
    cor = _relu(conv2d(p["convc1"], corr, 1, 0))
    cor = _relu(conv2d(p["convc2"], cor, 1, 1))
    flo = _relu(conv2d(p["convf1"], flow, 1, 3))
    flo = _relu(conv2d(p["convf2"], flo, 1, 1))
    out = _relu(conv2d(p["conv"], jnp.concatenate([cor, flo], -1), 1, 1))
    return jnp.concatenate([out, flow], -1)


def _sep_conv_gru(p: dict, h: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Separable ConvGRU: a 1×5 pass then a 5×1 pass (update.py:37-64).

    MXU shaping: ``convz``/``convr`` consume the same ``hx`` input, so their
    kernels are concatenated along the output-channel axis into ONE conv per
    direction (2 convs per pass instead of 3; the checkpoint keeps the original
    per-gate names — fusion happens here, where the concat is loop-invariant
    and XLA hoists it out of the scan). Bitwise identical to separate convs:
    each output channel's contraction is unchanged.
    """
    for suffix, pad in (("1", (0, 2)), ("2", (2, 0))):
        hx = jnp.concatenate([h, x], -1)
        pz, pr = p[f"convz{suffix}"], p[f"convr{suffix}"]
        zr = conv2d(
            {"kernel": jnp.concatenate([pz["kernel"], pr["kernel"]], -1),
             "bias": jnp.concatenate([pz["bias"], pr["bias"]], -1)},
            hx, 1, pad)
        z = jax.nn.sigmoid(zr[..., :HIDDEN_DIM])
        r = jax.nn.sigmoid(zr[..., HIDDEN_DIM:])
        q = jnp.tanh(conv2d(p[f"convq{suffix}"], jnp.concatenate([r * h, x], -1), 1, pad))
        h = (1 - z) * h + z * q
    return h


def _convex_upsample(flow: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """×8 convex combination of 3×3 neighbors (raft.py:100-111)."""
    from ..ops.nnf import extract_patches_3x3

    b, h, w, _ = flow.shape
    m = mask.astype(jnp.float32).reshape(b, h, w, 9, 8, 8)
    m = jax.nn.softmax(m, axis=3)
    patches = extract_patches_3x3(8.0 * flow)  # (B, H, W, 9, 2)
    up = jnp.einsum("bhwkij,bhwkc->bhwijc", m, patches)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(b, 8 * h, 8 * w, 2)


def raft_forward(params: Dict, image1: jnp.ndarray, image2: jnp.ndarray,
                 iters: int = ITERS, taps: Dict = None,
                 corr_impl: str = "volume", dtype=jnp.float32,
                 n_devices: int = 1) -> jnp.ndarray:
    """Flow from frame1 to frame2. Inputs (B, H, W, 3) RGB in [0, 255] —
    uint8 (the extractors' wire format: the u8→fp32 cast below is the first
    traced op, exact, so host staging ships quarter the bytes) or float32 —
    H and W divisible by 8. Returns (B, H, W, 2) flow in pixels (u, v).

    ``corr_impl``: ``volume`` materializes the all-pairs pyramid (reference
    default path, corr.py:12-60) with the MXU one-hot-matmul window lookup;
    ``volume_gather`` is the same pyramid with the scalar-gather lookup (same
    bits; faster on CPU, ~15× slower on TPU); ``on_demand`` computes window
    correlations per iteration from pooled f2 features (the ``alt_cuda_corr``
    equivalent — O(H·W·D) memory instead of O((H·W)²) for frames whose volume
    outgrows HBM, see :func:`_build_f2_pyramid`; gather-bound, so it trades
    ~40× speed for that memory ceiling); ``on_demand_matmul`` keeps the
    memory ceiling but remats the volume slice per iteration on the MXU
    instead of gathering (``auto``'s big-frame choice is ``on_demand``
    pending a 1080p TPU sweep — see :func:`resolve_corr_impl` and
    :func:`_lookup_on_demand`).

    ``taps``: debug-only dict filled with per-stage activations (fnet/cnet/corr/
    per-iteration flow) for the layer-diff parity harness (tools/layer_diff.py);
    tapping unrolls the update loop in Python instead of ``lax.scan``.

    ``dtype``: conv compute dtype. ``jnp.bfloat16`` runs encoders/GRU convs in
    bf16 and STORES the correlation pyramid in bf16 (fp32-accumulated before
    the cast; halves the framework's largest tensor) with the window lookup at
    default MXU precision — exact selection, bf16-rounded values. The
    coordinate carry and convex upsample stay fp32 (20 accumulated deltas are
    the refinement's sensitive spot). Measured drift vs fp32:
    tests/test_flow_bf16.py, docs/architecture.md.
    """
    corr_impl = resolve_corr_impl(corr_impl, image1.shape[0],
                                  image1.shape[1], image1.shape[2], dtype,
                                  n_devices)
    if corr_impl not in ("volume", "volume_gather", "on_demand", "on_demand_matmul"):
        raise ValueError(
            f"corr_impl must be auto|volume|volume_gather|on_demand|"
            f"on_demand_matmul, got {corr_impl!r}")
    x1 = (2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
    x2 = (2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)

    f1 = _encoder(params["fnet"], x1, "instance").astype(jnp.float32)
    f2 = _encoder(params["fnet"], x2, "instance").astype(jnp.float32)
    cnet = _encoder(params["cnet"], x1, "batch")
    return _refine_flow(params, f1, f2, cnet, iters, taps, corr_impl, dtype)


def raft_forward_frames(params: Dict, frames: jnp.ndarray, iters: int = ITERS,
                        corr_impl: str = "volume", dtype=jnp.float32,
                        n_devices: int = 1) -> jnp.ndarray:
    """Flow for all consecutive frame pairs, sharing per-frame features.

    ``frames``: (F, H, W, 3) → (F−1, H, W, 2), or a clip batch (N, F, H, W, 3)
    → (N, F−1, H, W, 2) — pairs never cross clip boundaries. uint8 or float
    RGB in [0, 255] (uint8 is the wire format; the fp32 cast is traced).

    TPU-first formulation of the reference's pair loop: ``fnet`` runs ONCE per
    frame (clips flattened into the conv batch axis) and pairs are formed by
    slicing the shared features, instead of encoding ``frames[:-1]`` and
    ``frames[1:]`` separately (every interior frame twice); ``cnet`` runs on
    the F−1 source frames as before. Numerics identical to
    :func:`raft_forward` on split pair batches — per-sample conv arithmetic
    does not depend on batch neighbors.
    """
    lead = frames.shape[:-3]  # (F,) or (N, F)
    n = int(np.prod(lead[:-1], dtype=np.int64)) if len(lead) > 1 else 1
    nf = lead[-1]
    h, w = frames.shape[-3:-1]
    corr_impl = resolve_corr_impl(corr_impl, n * (nf - 1), h, w, dtype,
                                  n_devices)
    if corr_impl not in ("volume", "volume_gather", "on_demand", "on_demand_matmul"):
        raise ValueError(
            f"corr_impl must be auto|volume|volume_gather|on_demand|"
            f"on_demand_matmul, got {corr_impl!r}")
    x = (2.0 * (frames.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
    x = x.reshape((n * nf, h, w, 3))
    feat = _encoder(params["fnet"], x, "instance").astype(jnp.float32)

    def pairs(p, keep_first: bool):
        _, ph, pw, c = p.shape
        p = p.reshape(n, nf, ph, pw, c)
        p = p[:, :-1] if keep_first else p[:, 1:]
        return p.reshape(n * (nf - 1), ph, pw, c)

    cnet = _encoder(params["cnet"], pairs(x, True), "batch")
    flow = _refine_flow(params, pairs(feat, True), pairs(feat, False), cnet,
                        iters, None, corr_impl, dtype)
    return flow.reshape(lead[:-1] + (nf - 1, h, w, 2))


def raft_forward_frames_sharded(params: Dict, frames: jnp.ndarray,
                                frame_last: jnp.ndarray, mesh,
                                iters: int = ITERS, corr_impl: str = "volume",
                                dtype=jnp.float32) -> jnp.ndarray:
    """Encode-once flow over a multi-device mesh, frame axis sharded.

    ``frames``: the window's B source frames (B, H, W, 3), sharded on axis 0
    (B divisible by the mesh size); ``frame_last``: the window's final frame
    (1, H, W, 3), replicated. Returns (B, H, W, 2) flow for the pairs
    ``frames[i] → frames[i+1]`` with ``frames[B] := frame_last`` — the flow
    of the (B+1)-frame window ``[frames; frame_last]``, sharded on the pair
    axis.

    Multi-chip counterpart of :func:`raft_forward_frames`: the B+1 frames of
    a window cannot shard evenly, so the pair-split step re-encoded every
    interior frame twice on meshes > 1 device. Here ``fnet``/``cnet`` run
    exactly once per source frame on the shard that owns it, each shard's one
    cross-shard pair is formed by halo-exchanging the NEIGHBOR's first fnet
    feature map over ICI (:func:`video_features_tpu.ops.halo.
    boundary_from_next` — one (1, H/8, W/8, 256) message per shard per step),
    and only the single replicated ``frame_last`` is encoded per-device.
    Numerics match the pair-split forward up to conv reduction order.
    """
    from jax.sharding import PartitionSpec as P

    from ..ops.halo import boundary_from_next, frame_axis_mesh

    b, h, w, _ = frames.shape
    axis, n_dev = frame_axis_mesh(mesh, b)
    corr_impl = resolve_corr_impl(corr_impl, b, h, w, dtype, n_dev)
    if corr_impl not in ("volume", "volume_gather", "on_demand", "on_demand_matmul"):
        raise ValueError(
            f"corr_impl must be auto|volume|volume_gather|on_demand|"
            f"on_demand_matmul, got {corr_impl!r}")

    def local(p, fr, fl):  # per-shard: (k, H, W, 3) main + (1, H, W, 3) last
        x = (2.0 * (fr.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
        xl = (2.0 * (fl.astype(jnp.float32) / 255.0) - 1.0).astype(dtype)
        f_loc = _encoder(p["fnet"], x, "instance").astype(jnp.float32)
        f_extra = _encoder(p["fnet"], xl, "instance").astype(jnp.float32)
        f_next = boundary_from_next(f_loc[:1], f_extra, axis, n_dev)
        f2 = jnp.concatenate([f_loc[1:], f_next], axis=0)
        cnet = _encoder(p["cnet"], x, "batch")  # sources only: no halo needed
        return _refine_flow(p, f_loc, f2, cnet, iters, None, corr_impl, dtype)

    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=(P(), P(axis), P()), out_specs=P(axis))
    return fn(params, frames, frame_last)


def _refine_flow(params: Dict, f1: jnp.ndarray, f2: jnp.ndarray, cnet: jnp.ndarray,
                 iters: int, taps, corr_impl: str, dtype=jnp.float32) -> jnp.ndarray:
    """Shared post-encoder body: correlation pyramid + iterative GRU refinement.

    ``dtype`` drives the motion-encoder/GRU/flow-head convs and the stored
    correlation pyramid (fp32-accumulated, then cast); the coords/flow carry
    stays fp32 regardless — sub-pixel refinement accumulates 20 deltas, and
    bf16's 8 mantissa bits would quantize the carry itself, not just each
    step's conv noise.
    """
    if corr_impl in ("volume", "volume_gather"):
        pyramid = _build_pyramid(f1, f2, dtype)
        impl = "matmul" if corr_impl == "volume" else "gather"
        lookup = lambda coords: _lookup(pyramid, coords, impl)  # noqa: E731
    else:
        f2_pyramid = _build_f2_pyramid(f2)
        od_impl = "matmul" if corr_impl == "on_demand_matmul" else "gather"
        lookup = lambda coords: _lookup_on_demand(  # noqa: E731
            f1, f2_pyramid, coords, od_impl, dtype=dtype)

    net = jnp.tanh(cnet[..., :HIDDEN_DIM]).astype(dtype)
    inp = _relu(cnet[..., HIDDEN_DIM:]).astype(dtype)

    b, h8, w8, _ = f1.shape
    coords0 = coords_grid(b, h8, w8)
    up = params["update_block"]

    if taps is not None:
        taps["fnet1"], taps["fnet2"], taps["cnet"] = f1, f2, cnet
        taps["corr_l0"] = _build_pyramid(f1, f2)[0]

    def body(carry, _):
        net, coords1 = carry
        corr = lookup(coords1).astype(dtype)
        flow = (coords1 - coords0).astype(dtype)
        motion = _motion_encoder(up["encoder"], flow, corr)
        net = _sep_conv_gru(up["gru"], net, jnp.concatenate([inp, motion], -1))
        delta = conv2d(up["flow_head"]["conv2"],
                       _relu(conv2d(up["flow_head"]["conv1"], net, 1, 1)), 1, 1)
        return (net, coords1 + delta.astype(jnp.float32)), None

    if taps is None:
        # under shard_map a scan carry must enter with the mesh variance it
        # leaves with: the coordinate grid is built replicated, while every
        # update derives from the shard's own features
        vma = tuple(jax.typeof(net).vma)
        coords1 = lax.pcast(coords0, vma, to="varying") if vma else coords0
        (net, coords1), _ = lax.scan(body, (net, coords1), None, length=iters)
    else:
        coords1 = coords0
        for it in range(iters):
            (net, coords1), _ = body((net, coords1), None)
            taps[f"flow_iter{it}"] = coords1 - coords0

    mask = 0.25 * conv2d(up["mask.2"], _relu(conv2d(up["mask.0"], net, 1, 1)), 1, 0)
    return _convex_upsample(coords1 - coords0, mask)


def pad_to_multiple(frames: np.ndarray, m: int) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """Replicate-pad (…, H, W, C) to multiples of ``m``, sintel split
    (raft.py:27-39 semantics, generalized from 8 to any bucket size).

    Returns (padded, (top, bottom, left, right)) for :func:`unpad`.
    """
    h, w = frames.shape[-3:-1]
    # delegate to pad_to_shape: the packed loop's byte-parity contract needs
    # the /8 pad and the explicit-bucket pad to be the SAME split forever
    return pad_to_shape(frames, (-(-h // m) * m, -(-w // m) * m))


def pad_to_multiple_of_8(frames: np.ndarray) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """The reference's /8 input pad (raft.py:27-44)."""
    return pad_to_multiple(frames, 8)


def pad_split(h: int, w: int, th: int, tw: int,
              ) -> Tuple[int, int, int, int]:
    """The centered sintel pad split (top, bottom, left, right) taking
    ``h``×``w`` frames to ``th``×``tw`` — the one arithmetic every pad
    variant here (host, in-place, traced) and the unpad slicing share."""
    if th < h or tw < w:
        raise ValueError(f"cannot pad {h}x{w} frames down to bucket {th}x{tw}")
    ph, pw = th - h, tw - w
    return ph // 2, ph - ph // 2, pw // 2, pw - pw // 2


def device_pad_to_shape(x: jnp.ndarray, target_hw: Tuple[int, int],
                        ) -> jnp.ndarray:
    """Traced :func:`pad_to_shape`: replicate-pad (…, H, W, C) to an
    explicit ``(H, W)`` geometry INSIDE the jitted step (``--device_preproc``
    — the host ships RAW decoded frames and the /8-or-bucket pad becomes the
    step's first fused op). Geometry is static at trace time; the same
    centered sintel split as the host pad, on the same wire dtype
    (``jnp.pad(mode="edge")`` replicates values without arithmetic), so the
    padded window is BYTE-identical to ``pad_to_shape`` — pinned by
    tests/test_device_preproc.py, which is why the flag is execution-only
    for the flow extractors (cache/key.py).
    """
    th, tw = target_hw
    h, w = int(x.shape[-3]), int(x.shape[-2])
    top, bottom, left, right = pad_split(h, w, th, tw)
    if not (top or bottom or left or right):
        return x
    pad = [(0, 0)] * (x.ndim - 3) + [(top, bottom), (left, right), (0, 0)]
    return jnp.pad(x, pad, mode="edge")


def pad_to_shape(frames: np.ndarray, target_hw: Tuple[int, int],
                 ) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """Replicate-pad (…, H, W, C) to an explicit ``(H, W)`` bucket geometry.

    Same centered sintel split as :func:`pad_to_multiple` — when the target
    is the geometry's own /8 (or ``--shape_bucket``) padding, the result is
    byte-identical to the per-video path's pad, which is what the packed
    flow loop's byte-parity contract rides on. Dtype-preserving: uint8
    frames pad to uint8 (the wire format — the u8→fp32 cast lives inside
    the jitted step, not here). Returns (padded, pads) for :func:`unpad`.
    """
    th, tw = target_hw
    h, w = frames.shape[-3:-1]
    top, bottom, left, right = pad_split(h, w, th, tw)
    if not (top or bottom or left or right):
        return frames, (0, 0, 0, 0)
    pad = [(0, 0)] * (frames.ndim - 3) + [(top, bottom), (left, right), (0, 0)]
    return np.pad(frames, pad, mode="edge"), (top, bottom, left, right)


def pad_to_shape_into(frame: np.ndarray, out: np.ndarray,
                      ) -> Tuple[int, int, int, int]:
    """:func:`pad_to_shape` into a PREALLOCATED ``(TH, TW, C)`` buffer.

    The staging-ring fast path: one (H, W, C) decoded frame is written
    replicate-padded straight into its row of a reusable device-batch buffer
    — no intermediate ``np.pad`` allocation per frame, and the dtype follows
    ``out`` (uint8 stays uint8; a float32 ring under ``--float32_wire``
    upcasts exactly). Byte-identical to ``pad_to_shape(frame, out.shape[:2])``
    — fill the center, replicate the side columns across the frame's rows,
    then replicate whole padded rows outward (corners land on the frame's
    corner texels, ``np.pad(mode="edge")`` semantics). Returns the same pads
    tuple for :func:`unpad`.
    """
    th, tw = out.shape[0], out.shape[1]
    h, w = frame.shape[0], frame.shape[1]
    top, bottom, left, right = pad_split(h, w, th, tw)
    out[top : th - bottom, left : tw - right] = frame
    if left:
        out[top : th - bottom, :left] = frame[:, :1]
    if right:
        out[top : th - bottom, tw - right :] = frame[:, -1:]
    if top:
        out[:top] = out[top : top + 1]
    if bottom:
        out[th - bottom :] = out[th - bottom - 1 : th - bottom]
    return (top, bottom, left, right)


def unpad(x: np.ndarray, pads: Tuple[int, int, int, int]) -> np.ndarray:
    top, bottom, left, right = pads
    h, w = x.shape[-3:-1]
    return x[..., top : h - bottom, left : w - right, :]


# ---------------------------------------------------------------------------
# Shapes / random init (no torch needed): (cin, cout, kh, kw, pad-implied-by-use)
# ---------------------------------------------------------------------------

def _conv_shapes() -> Dict[str, Tuple[int, int, int, int]]:
    shapes: Dict[str, Tuple[int, int, int, int]] = {}

    def encoder(prefix: str, out_dim: int, batch_norm: bool):
        shapes[f"{prefix}.conv1"] = (3, 64, 7, 7)
        if batch_norm:
            shapes[f"{prefix}.norm1"] = (64,)
        cin = 64
        for stage, dim, stride in (("layer1", 64, 1), ("layer2", 96, 2), ("layer3", 128, 2)):
            for blk in (0, 1):
                s = stride if blk == 0 else 1
                p = f"{prefix}.{stage}.{blk}"
                shapes[f"{p}.conv1"] = (cin if blk == 0 else dim, dim, 3, 3)
                shapes[f"{p}.conv2"] = (dim, dim, 3, 3)
                if batch_norm:
                    shapes[f"{p}.norm1"] = (dim,)
                    shapes[f"{p}.norm2"] = (dim,)
                if blk == 0 and s != 1:
                    shapes[f"{p}.downsample.0"] = (cin, dim, 1, 1)
                    if batch_norm:
                        shapes[f"{p}.norm3"] = (dim,)
            cin = dim
        shapes[f"{prefix}.conv2"] = (128, out_dim, 1, 1)

    encoder("fnet", 256, batch_norm=False)
    encoder("cnet", HIDDEN_DIM + CONTEXT_DIM, batch_norm=True)

    cor_planes = CORR_LEVELS * (2 * CORR_RADIUS + 1) ** 2  # 324
    ub = "update_block"
    shapes[f"{ub}.encoder.convc1"] = (cor_planes, 256, 1, 1)
    shapes[f"{ub}.encoder.convc2"] = (256, 192, 3, 3)
    shapes[f"{ub}.encoder.convf1"] = (2, 128, 7, 7)
    shapes[f"{ub}.encoder.convf2"] = (128, 64, 3, 3)
    shapes[f"{ub}.encoder.conv"] = (192 + 64, 126, 3, 3)
    gru_in = HIDDEN_DIM + 128 + HIDDEN_DIM  # h + (motion 128) + context
    for sfx, k in (("1", (1, 5)), ("2", (5, 1))):
        for gate in ("convz", "convr", "convq"):
            shapes[f"{ub}.gru.{gate}{sfx}"] = (gru_in, HIDDEN_DIM, *k)
    shapes[f"{ub}.flow_head.conv1"] = (HIDDEN_DIM, 256, 3, 3)
    shapes[f"{ub}.flow_head.conv2"] = (256, 2, 3, 3)
    shapes[f"{ub}.mask.0"] = (128, 256, 3, 3)
    shapes[f"{ub}.mask.2"] = (256, 64 * 9, 1, 1)
    return shapes


def raft_init_params(seed: int = 0) -> Dict:
    """Deterministic random param pytree with checkpoint-identical structure."""
    rng = np.random.default_rng(seed)
    tree: Dict = {}

    def put(path, leaf):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf

    for name, shape in _conv_shapes().items():
        path = name.split(".")
        merged = []
        i = 0
        while i < len(path):
            if i + 1 < len(path) and path[i + 1].isdigit():
                merged.append(path[i] + "." + path[i + 1])
                i += 2
            else:
                merged.append(path[i])
                i += 1
        if len(shape) == 1:  # batch norm
            c = shape[0]
            put(merged, {
                "scale": rng.uniform(0.5, 1.5, c).astype(np.float32),
                "bias": (rng.standard_normal(c) * 0.05).astype(np.float32),
                "mean": (rng.standard_normal(c) * 0.05).astype(np.float32),
                "var": rng.uniform(0.5, 1.5, c).astype(np.float32),
            })
        else:
            cin, cout, kh, kw = shape
            put(merged, {
                "kernel": (rng.standard_normal((kh, kw, cin, cout)) * 0.05).astype(np.float32),
                "bias": (rng.standard_normal(cout) * 0.05).astype(np.float32),
            })
    return tree

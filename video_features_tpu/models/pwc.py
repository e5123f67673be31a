"""PWC-Net optical flow in JAX (NHWC, functional).

Behavioral spec — ``/root/reference/models/pwc/pwc_src/pwc_net.py``:
- Input RGB in [0, 255]; the net flips to BGR and scales /255 (``:229-231``) because
  the pretrained weights are BGR-native.
- Bilinear resize (align_corners=False) to /64-multiple sizes (``:241-245``).
- 6-level feature pyramid, 3 convs per level, LeakyReLU 0.1 (``:44-110``).
- Coarse-to-fine decoders at levels 6→2 (``:112-187``): 81-channel cost volume
  (9×9 displacement window, zero-padded, channel-mean — the CUDA kernel semantics of
  ``correlation.py:44-112``: channel k ↔ (dy=k//9−4, dx=k%9−4)), LeakyReLU'd;
  below level 6 the second feature map is backward-warped by the upsampled flow
  scaled per level (0.625/1.25/2.5/5.0), with the partial-tap zeroing mask
  (``:23-41``); DenseNet-style conv block. The reference concatenates each new
  map in front and convolves the growing stack six times; here every map is
  convolved ONCE, against the kernel rows all of its later consumers keep for
  it, and the partial results are added in float32 (:func:`_dense_block`: the
  same products, each 450–2 output columns wide instead of 128–2, and one
  concatenate a level, for the 565-channel map's two readers outside).
- Dilated refiner on the level-2 feature tail (``:189-210``).
- Output: 20 × bilinear resize of (flow₂ + refinement) to the *original* size, u
  scaled by W/W₆₄, v by H/H₆₄ (``:256-261``).

The cost volume lives in :mod:`video_features_tpu.ops.pallas_corr`: a pure-XLA
formulation (default — 81 shifted products XLA fuses into HBM-friendly passes)
and a hand-tiled Pallas kernel, selected by ``corr_impl``.

Functional over a param pytree (torch checkpoint names, e.g.
``moduleExtractor.moduleOne.0`` — see
:func:`video_features_tpu.weights.convert_torch.convert_pwc`).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.nnf import conv2d, conv2d_transpose, leaky_relu
from ..ops.pallas_corr import corr81, warp_corr81
from ..ops.warp import resize_bilinear_torch

CORR_RADIUS = 4
CORR_CHANNELS = (2 * CORR_RADIUS + 1) ** 2  # 81

# pyramid level channel counts (level 1..6)
PYR_CHANNELS = (16, 32, 64, 96, 128, 196)
# decoder input channels per level: 81 + fmap + 2 flow + 2 upfeat (level 6: corr only)
DEC_CURRENT = {6: 81, 5: 81 + 128 + 4, 4: 81 + 96 + 4, 3: 81 + 64 + 4, 2: 81 + 32 + 4}
DEC_BACKWARD = {5: 0.625, 4: 1.25, 3: 2.5, 2: 5.0}
DENSE_NAMES = ("moduleOne", "moduleTwo", "moduleThr", "moduleFou", "moduleFiv", "moduleSix")
DENSE_OUT = (128, 128, 96, 64, 32)  # moduleOne..moduleFiv; moduleSix is the level's flow, 2 wide
LEVEL_NAMES = {2: "moduleTwo", 3: "moduleThr", 4: "moduleFou", 5: "moduleFiv", 6: "moduleSix"}


# re-export: tests and external callers address the cost volume through the model
from ..ops.pallas_corr import corr81_xla as correlation_81  # noqa: E402, F401


# Device scopes (``jax.named_scope``): a profiler trace names every operation
# of the net by its stage — ``pwc/resize_in``, ``pwc/pyramid``,
# ``pwc/warp<level>``, ``pwc/corr<level>``, ``pwc/decoder<level>``,
# ``pwc/refiner``, ``pwc/resize_out`` — whatever fusion the compiler makes of
# it (docs/observability.md "reading a device trace").


@jax.named_scope("pwc/pyramid")
def _pyramid(p: Dict, x: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """6-level feature pyramid (pwc_net.py:44-110); 3 convs per level."""
    names = ("moduleOne", "moduleTwo", "moduleThr", "moduleFou", "moduleFiv", "moduleSix")
    feats = []
    for name in names:
        lvl = p[name]
        x = leaky_relu(conv2d(lvl["0"], x, 2, 1))
        x = leaky_relu(conv2d(lvl["2"], x, 1, 1))
        x = leaky_relu(conv2d(lvl["4"], x, 1, 1))
        feats.append(x)
    return tuple(feats)


def _decoder(p: Dict, level: int, f1: jnp.ndarray, f2: jnp.ndarray, prev,
             corr_impl: str = "xla"):
    """One coarse-to-fine stage (pwc_net.py:152-187)."""
    if prev is None:
        with jax.named_scope(f"pwc/corr{level}"):
            volume = leaky_relu(corr81(f1, f2, corr_impl))
        feat = volume
    else:
        with jax.named_scope(f"pwc/decoder{level}"):
            flow = conv2d_transpose(p["moduleUpflow"], prev["flow"])
            upfeat = conv2d_transpose(p["moduleUpfeat"], prev["feat"])
        volume = leaky_relu(warp_corr81(f1, f2, flow * DEC_BACKWARD[level],
                                        corr_impl, level=str(level)))
        feat = jnp.concatenate([volume, f1, flow, upfeat], axis=-1)

    with jax.named_scope(f"pwc/decoder{level}"):
        flow, feat = _dense_block(p, feat)
    return {"flow": flow, "feat": feat}


def _dense_block(p: Dict, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The DenseNet block of one decoder level (pwc_net.py:166-187), convolved
    by SOURCE. The reference's consumer k (0 … 5: moduleOne … moduleSix) reads
    ``concat([x_k, …, x_1, x_0])``, so every map is re-read by every later
    convolution, behind a fresh concatenate, into 128/128/96/64/32/2 columns.
    A convolution is linear in its input channels, so each source x_j meets
    the MXU once instead, against the kernel rows that every later consumer
    keeps for it, side by side (450/322/194/98/34/2 columns, the consumers in
    order, so the wide slices start on a 128-column tile):

        y_j = conv3x3(x_j, W'_j);  acc_k += y_j[..., columns of consumer k]
        x_{j+1} = leaky_relu(acc_j), complete once sources 0 … j are in

    The same products, a different order of float32 additions. Six separate
    sums and not one buffer: XLA:TPU then evaluates each where it is read, in
    the convolutions' own layout (PERF.md §6, PR 35). ``acc`` is float32
    whatever ``x.dtype`` is: under bfloat16 the concatenated form rounded ONE
    float32 sum over all input channels, and six partial sums each rounded
    first would be a worse number. Returns the level's flow and the 565-channel
    map (new features in front) for its two readers outside the block: the one
    concatenate a level that stays."""
    widths = DENSE_OUT + (2,)
    dtype = x.dtype
    acc = [p[name]["0"]["bias"].astype(jnp.float32) for name in DENSE_NAMES]
    maps = [x]
    for j in range(len(DENSE_NAMES)):
        # consumer k's input is [x_k | … | x_{j+1} | x_j | … | x_0] and W'_j's
        # columns are [consumer j | … | consumer 5]: x_j's first row there and
        # consumer k's first column here are both the summed widths of the
        # maps between, x_{j+1} … x_k
        starts = np.cumsum((0,) + widths[j:-1])
        cin = x.shape[-1]
        kernel = jnp.concatenate(
            [p[name]["0"]["kernel"][:, :, start:start + cin, :]
             for name, start in zip(DENSE_NAMES[j:], starts)], axis=-1)
        y = lax.conv_general_dilated(
            x, kernel.astype(dtype), (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        for k, part in enumerate(jnp.split(y, starts[1:], axis=-1), start=j):
            acc[k] = acc[k] + part
        if j < len(DENSE_OUT):
            x = leaky_relu(acc[j]).astype(dtype)
            maps.append(x)
    return acc[-1].astype(dtype), jnp.concatenate(maps[::-1], axis=-1)


@jax.named_scope("pwc/refiner")
def _refiner(p: Dict, feat: jnp.ndarray) -> jnp.ndarray:
    """Dilated context network (pwc_net.py:189-210)."""
    dilations = (1, 2, 4, 8, 16, 1)
    x = feat
    for idx, d in zip(("0", "2", "4", "6", "8", "10"), dilations):
        x = leaky_relu(conv2d(p[idx], x, 1, d, dilation=d))
    return conv2d(p["12"], x, 1, 1)


def _preprocess(image: jnp.ndarray, h64: int, w64: int) -> jnp.ndarray:
    """RGB [0,255] → BGR /255 (pwc_net.py:230) resized to the /64 grid."""
    x = image[..., ::-1].astype(jnp.float32) / 255.0
    if (h64, w64) != image.shape[-3:-1]:
        with jax.named_scope("pwc/resize_in"):
            x = resize_bilinear_torch(x, h64, w64)
    return x


def _decode(params: Dict, pyr1, pyr2, h: int, w: int, h64: int, w64: int,
            corr_impl: str) -> jnp.ndarray:
    """Coarse-to-fine decoders + refiner + output scaling (pwc_net.py:241-261)."""
    est = None
    for level in (6, 5, 4, 3, 2):
        est = _decoder(params[LEVEL_NAMES[level]], level,
                       pyr1[level - 1], pyr2[level - 1], est, corr_impl)

    flow = est["flow"] + _refiner(params["moduleRefiner"]["moduleMain"], est["feat"])
    with jax.named_scope("pwc/resize_out"):
        flow = 20.0 * resize_bilinear_torch(flow.astype(jnp.float32), h, w)
        scale = jnp.asarray([w / w64, h / h64], jnp.float32)
        return flow * scale


def _grid64(h: int, w: int) -> Tuple[int, int]:
    return (int(math.floor(math.ceil(h / 64.0) * 64.0)),
            int(math.floor(math.ceil(w / 64.0) * 64.0)))


def pwc_forward(params: Dict, image1: jnp.ndarray, image2: jnp.ndarray,
                corr_impl: str = "xla", dtype=jnp.float32) -> jnp.ndarray:
    """Flow frame1→frame2. Inputs (B, H, W, 3) RGB [0, 255] — uint8 (the
    extractors' wire format; ``_preprocess``'s fp32 cast is the first traced
    op, exact) or float — any size. Returns (B, H, W, 2) float32 flow in
    input-resolution pixels.

    ``corr_impl``: cost-volume implementation (``xla`` | ``pallas``), see
    :mod:`video_features_tpu.ops.pallas_corr`. ``dtype``: conv compute dtype —
    ``jnp.bfloat16`` halves HBM traffic and doubles MXU rate; precision-
    sensitive spots (cost-volume accumulation, warp coordinates, final resize/
    scaling) stay fp32 regardless. Measured drift vs fp32 is recorded in
    ``tests/test_flow_bf16.py`` and docs/architecture.md."""
    b, h, w, _ = image1.shape
    h64, w64 = _grid64(h, w)
    x1 = _preprocess(image1, h64, w64).astype(dtype)
    x2 = _preprocess(image2, h64, w64).astype(dtype)
    pyr1 = _pyramid(params["moduleExtractor"], x1)
    pyr2 = _pyramid(params["moduleExtractor"], x2)
    return _decode(params, pyr1, pyr2, h, w, h64, w64, corr_impl)


def pwc_forward_frames(params: Dict, frames: jnp.ndarray,
                       corr_impl: str = "xla", dtype=jnp.float32,
                       pair_chunk: int = None) -> jnp.ndarray:
    """Flow for all consecutive frame pairs, sharing per-frame features.

    ``frames``: (F, H, W, 3) → (F−1, H, W, 2), or a clip batch (N, F, H, W, 3)
    → (N, F−1, H, W, 2) — pairs never cross clip boundaries.

    TPU-first formulation of the reference's pair loop: the feature pyramid —
    PWC's dominant stage by an earlier stage profile (small-channel convs at
    128²/64²; on the v5e the level-2 decoder is, PERF.md §5)
    — is computed ONCE per frame (clips flattened into the conv batch axis) and
    pairs are formed by slicing the shared per-frame features, instead of
    re-encoding ``frames[:-1]`` and ``frames[1:]`` separately (which encodes
    every interior frame twice). Numerics are identical to :func:`pwc_forward`
    on the split pair batches — per-sample conv arithmetic does not depend on
    its batch neighbors.
    """
    lead = frames.shape[:-3]  # (F,) or (N, F)
    n = int(np.prod(lead[:-1], dtype=np.int64)) if len(lead) > 1 else 1
    f = lead[-1]
    h, w = frames.shape[-3:-1]
    h64, w64 = _grid64(h, w)
    flat = _preprocess(frames.reshape((n * f, h, w, 3)), h64, w64).astype(dtype)
    pyr = _pyramid(params["moduleExtractor"], flat)

    def pairs(p, keep_first: bool):
        nf, ph, pw, c = p.shape
        p = p.reshape(n, f, ph, pw, c)
        p = p[:, :-1] if keep_first else p[:, 1:]
        return p.reshape(n * (f - 1), ph, pw, c)

    pyr1 = tuple(pairs(p, True) for p in pyr)
    pyr2 = tuple(pairs(p, False) for p in pyr)
    total = n * (f - 1)
    chunk = min(pair_chunk, total) if pair_chunk else 0
    if chunk > 0 and chunk < total:
        # bound peak decoder memory: the DenseNet decoder activations scale
        # with the pair batch (a 64-pair 65-frame I3D stack at 256×341 was
        # recorded as blowing HBM in one piece); the shared per-frame
        # pyramid above is computed ONCE either way, only the coarse-to-fine
        # decode runs chunk-by-chunk under lax.map (sequential on device).
        # Non-divisible totals zero-pad the pair axis up to a chunk multiple
        # (padded rows decode to garbage and are sliced off) — the protection
        # must never silently disengage on an odd pair count.
        def chunked(level_maps):
            p1, p2 = level_maps
            return _decode(params, p1, p2, h, w, h64, w64, corr_impl)

        nch = -(-total // chunk)
        pad = nch * chunk - total

        def to_chunks(p):
            if pad:
                p = jnp.concatenate(
                    [p, jnp.zeros((pad,) + p.shape[1:], p.dtype)], axis=0)
            return p.reshape((nch, chunk) + p.shape[1:])

        flow = jax.lax.map(chunked, (tuple(to_chunks(p) for p in pyr1),
                                     tuple(to_chunks(p) for p in pyr2)))
        flow = flow.reshape((nch * chunk, h, w, 2))[:total]
    else:
        flow = _decode(params, pyr1, pyr2, h, w, h64, w64, corr_impl)
    return flow.reshape(lead[:-1] + (f - 1, h, w, 2))


def pwc_forward_frames_sharded(params: Dict, frames: jnp.ndarray,
                               frame_last: jnp.ndarray, mesh,
                               corr_impl: str = "xla",
                               dtype=jnp.float32) -> jnp.ndarray:
    """Encode-once flow over a multi-device mesh, frame axis sharded.

    ``frames``: the window's B source frames (B, H, W, 3) sharded on axis 0
    (B divisible by the mesh size); ``frame_last``: the window's final frame
    (1, H, W, 3), replicated. Returns (B, H, W, 2) flow for the pairs
    ``frames[i] → frames[i+1]`` with ``frames[B] := frame_last``, sharded on
    the pair axis.

    Multi-chip counterpart of :func:`pwc_forward_frames`: the feature
    pyramid — PWC's dominant stage — runs exactly once per source frame on
    the shard that owns it; each shard's one cross-shard pair is formed by
    halo-exchanging the neighbor's first feature map AT EVERY PYRAMID LEVEL
    (:func:`video_features_tpu.ops.halo.boundary_from_next`, six small ICI
    messages per shard per step), and only the replicated ``frame_last`` is
    encoded per-device. Numerics match the pair-split forward up to conv
    reduction order.
    """
    from jax.sharding import PartitionSpec as P

    from ..ops.halo import boundary_from_next, frame_axis_mesh

    b, h, w, _ = frames.shape
    axis, n_dev = frame_axis_mesh(mesh, b)
    h64, w64 = _grid64(h, w)

    def local(p, fr, fl):  # per-shard: (k, H, W, 3) main + (1, H, W, 3) last
        x = _preprocess(fr, h64, w64).astype(dtype)
        xl = _preprocess(fl, h64, w64).astype(dtype)
        pyr = _pyramid(p["moduleExtractor"], x)      # 6 levels of (k, hl, wl, c)
        pyr_l = _pyramid(p["moduleExtractor"], xl)   # 6 levels of (1, hl, wl, c)
        pyr2 = tuple(
            jnp.concatenate(
                [lvl[1:], boundary_from_next(lvl[:1], lvl_l, axis, n_dev)],
                axis=0)
            for lvl, lvl_l in zip(pyr, pyr_l))
        return _decode(p, pyr, pyr2, h, w, h64, w64, corr_impl)

    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=(P(), P(axis), P()), out_specs=P(axis))
    return fn(params, frames, frame_last)


# ---------------------------------------------------------------------------
# Shapes / random init. conv: (cin, cout, kh, kw); 'T' prefix marks transpose convs
# whose torch weights are laid out (in, out, kh, kw).
# ---------------------------------------------------------------------------

def pwc_conv_shapes() -> Dict[str, Tuple]:
    shapes: Dict[str, Tuple] = {}
    cin = 3
    for name, cout in zip(
        ("moduleOne", "moduleTwo", "moduleThr", "moduleFou", "moduleFiv", "moduleSix"),
        PYR_CHANNELS,
    ):
        shapes[f"moduleExtractor.{name}.0"] = (cin, cout, 3, 3)
        shapes[f"moduleExtractor.{name}.2"] = (cout, cout, 3, 3)
        shapes[f"moduleExtractor.{name}.4"] = (cout, cout, 3, 3)
        cin = cout

    for level in (6, 5, 4, 3, 2):
        mod = LEVEL_NAMES[level]
        current = DEC_CURRENT[level]
        if level < 6:
            prev_feat = DEC_CURRENT[level + 1] + sum(DENSE_OUT)
            shapes[f"{mod}.moduleUpflow"] = ("T", 2, 2, 4, 4)
            shapes[f"{mod}.moduleUpfeat"] = ("T", prev_feat, 2, 4, 4)
        ch = current
        for name, cout in zip(DENSE_NAMES, DENSE_OUT + (2,)):
            shapes[f"{mod}.{name}.0"] = (ch, cout, 3, 3)
            ch += cout

    ch = DEC_CURRENT[2] + sum(DENSE_OUT)
    for idx, (cout, _d) in zip(("0", "2", "4", "6", "8", "10", "12"),
                               ((128, 1), (128, 2), (128, 4), (96, 8), (64, 16), (32, 1), (2, 1))):
        shapes[f"moduleRefiner.moduleMain.{idx}"] = (ch, cout, 3, 3)
        ch = cout
    return shapes


def pwc_init_params(seed: int = 0) -> Dict:
    """Deterministic random param pytree with checkpoint-identical structure."""
    rng = np.random.default_rng(seed)
    tree: Dict = {}
    for name, shape in pwc_conv_shapes().items():
        if shape[0] == "T":
            _, cin, cout, kh, kw = shape
        else:
            cin, cout, kh, kw = shape
        node = tree
        parts = name.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = {
            "kernel": (rng.standard_normal((kh, kw, cin, cout)) * 0.05).astype(np.float32),
            "bias": (rng.standard_normal(cout) * 0.05).astype(np.float32),
        }
    return tree

"""Plain reference for ``i3d_pwc_fp32``: two-stream I3D (Carreira & Zisserman
2017, Inception-v1 inflated) over PWC-Net flow (Sun et al. 2018), as the
reference implementation ``video_features`` wires them, in straightforward
``jax.numpy`` float32 at ``Precision.HIGHEST``: no kernels, no batching across
stacks, no chunked loops; the cost volume is 81 shifted products.

A video's answer: frames are resized (PIL bilinear, smaller edge 256); every
65 consecutive frames with step 64 form a stack (a trailing partial stack is
dropped). rgb: the first 64 frames, centre crop 224, scaled to [-1, 1], I3D.
flow: PWC-Net between the 64 consecutive pairs at 256-edge size, centre crop
224, clamp to +-20, quantise to uint8 steps (round half to even, not clipped),
scale to [-1, 1], I3D. Each tower gives 1024 numbers per stack.

Departures from the papers, all of them the reference implementation's own:
BatchNorm folded in inference form; TF-"SAME" padding computed from kernel and
stride alone; max pools pad with zeros and use ceil mode; PWC-Net takes BGR
/255 input resized to a multiple of 64 and multiplies its flow by 20.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .common import batch_norm, conv, decode_rgb, resize_smaller_edge

STACK, STEP, EDGE, CROP = 64, 64, 256, 224

# --------------------------------------------------------------------- I3D

I3D_LAYERS = (
    ("conv", "conv3d_1a_7x7", 64, (7, 7, 7), (2, 2, 2)),
    ("pool", "maxPool3d_2a_3x3", (1, 3, 3), (1, 2, 2)),
    ("conv", "conv3d_2b_1x1", 64, (1, 1, 1), (1, 1, 1)),
    ("conv", "conv3d_2c_3x3", 192, (3, 3, 3), (1, 1, 1)),
    ("pool", "maxPool3d_3a_3x3", (1, 3, 3), (1, 2, 2)),
    ("mixed", "mixed_3b", (64, 96, 128, 16, 32, 32)),
    ("mixed", "mixed_3c", (128, 128, 192, 32, 96, 64)),
    ("pool", "maxPool3d_4a_3x3", (3, 3, 3), (2, 2, 2)),
    ("mixed", "mixed_4b", (192, 96, 208, 16, 48, 64)),
    ("mixed", "mixed_4c", (160, 112, 224, 24, 64, 64)),
    ("mixed", "mixed_4d", (128, 128, 256, 24, 64, 64)),
    ("mixed", "mixed_4e", (112, 144, 288, 32, 64, 64)),
    ("mixed", "mixed_4f", (256, 160, 320, 32, 128, 128)),
    ("pool", "maxPool3d_5a_2x2", (2, 2, 2), (2, 2, 2)),
    ("mixed", "mixed_5b", (256, 160, 320, 32, 128, 128)),
    ("mixed", "mixed_5c", (384, 192, 384, 48, 128, 128)),
)
MIXED_BRANCHES = (("branch_0", 0, (1, 1, 1)), ("branch_1.0", 1, (1, 1, 1)),
                  ("branch_1.1", 2, (3, 3, 3)), ("branch_2.0", 3, (1, 1, 1)),
                  ("branch_2.1", 4, (3, 3, 3)), ("branch_3.1", 5, (1, 1, 1)))


def _unit_spec(spec, name, kernel, cin, cout):
    spec[f"{name}/conv3d/kernel"] = tuple(kernel) + (cin, cout)
    for leaf in ("scale", "bias", "mean", "var"):
        spec[f"{name}/batch3d/{leaf}"] = (cout,)


def i3d_spec(cin: int) -> Dict[str, Tuple[int, ...]]:
    spec: Dict[str, Tuple[int, ...]] = {}
    for op, name, *rest in I3D_LAYERS:
        if op == "conv":
            cout, kernel, _stride = rest
            _unit_spec(spec, name, kernel, cin, cout)
            cin = cout
        elif op == "mixed":
            c = rest[0]
            ins = {"branch_0": cin, "branch_1.0": cin, "branch_1.1": c[1],
                   "branch_2.0": cin, "branch_2.1": c[3], "branch_3.1": cin}
            for bname, idx, kernel in MIXED_BRANCHES:
                _unit_spec(spec, f"{name}/{bname}", kernel, ins[bname], c[idx])
            cin = c[0] + c[2] + c[4] + c[5]
    return spec


def _same_pads(kernel, stride):
    pads = []
    for k, s in zip(kernel, stride):
        p = max(k - s, 0)
        pads.append((p // 2, p - p // 2))
    return pads


def _unit(p, x, kernel=(1, 1, 1), stride=(1, 1, 1)):
    y = conv(x, p["conv3d"]["kernel"], stride, _same_pads(kernel, stride))
    return jax.nn.relu(batch_norm(y, p["batch3d"]))


def _max_pool(x, kernel, stride):
    """Zero padding by the SAME rule, then a ceil-mode max pool: windows that
    overhang the padded input ignore the overhang."""
    zero = _same_pads(kernel, stride)
    x = jnp.pad(x, [(0, 0)] + zero + [(0, 0)])
    extra = []
    for size, k, s in zip(x.shape[1:-1], kernel, stride):
        n_out = max(math.ceil((size - k) / s), 0) + 1
        extra.append((0, max((n_out - 1) * s + k - size, 0)))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1,) + tuple(kernel) + (1,),
                             (1,) + tuple(stride) + (1,), [(0, 0)] + extra + [(0, 0)])


def i3d_features(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """(N, T, H, W, C) in [-1, 1] → (N, 1024)."""
    for op, name, *rest in I3D_LAYERS:
        if op == "conv":
            _cout, kernel, stride = rest
            x = _unit(p[name], x, kernel, stride)
        elif op == "pool":
            x = _max_pool(x, *rest)
        else:
            q = p[name]
            b0 = _unit(q["branch_0"], x)
            b1 = _unit(q["branch_1.1"], _unit(q["branch_1.0"], x), (3, 3, 3))
            b2 = _unit(q["branch_2.1"], _unit(q["branch_2.0"], x), (3, 3, 3))
            b3 = _unit(q["branch_3.1"], _max_pool(x, (3, 3, 3), (1, 1, 1)))
            x = jnp.concatenate([b0, b1, b2, b3], axis=-1)
    # AvgPool3d((2, 7, 7), stride 1), squeeze, mean over the remaining time
    x = jnp.mean(x, axis=(2, 3))                       # (N, T', 1024)
    x = 0.5 * (x[:, :-1] + x[:, 1:])
    return jnp.mean(x, axis=1)


# ----------------------------------------------------------------- PWC-Net

PYRAMID = (("moduleOne", 16), ("moduleTwo", 32), ("moduleThr", 64),
           ("moduleFou", 96), ("moduleFiv", 128), ("moduleSix", 196))
LEVELS = {6: "moduleSix", 5: "moduleFiv", 4: "moduleFou", 3: "moduleThr", 2: "moduleTwo"}
LEVEL_FEAT = {6: 196, 5: 128, 4: 96, 3: 64, 2: 32}
DENSE = (("moduleOne", 128), ("moduleTwo", 128), ("moduleThr", 96),
         ("moduleFou", 64), ("moduleFiv", 32))
BACKWARD_SCALE = {5: 0.625, 4: 1.25, 3: 2.5, 2: 5.0}
REFINER = (("0", 128, 1), ("2", 128, 2), ("4", 128, 4), ("6", 96, 8),
           ("8", 64, 16), ("10", 32, 1), ("12", 2, 1))


def _conv_spec(spec, name, kh, cin, cout):
    spec[f"{name}/kernel"] = (kh, kh, cin, cout)
    spec[f"{name}/bias"] = (cout,)


def pwc_spec() -> Dict[str, Tuple[int, ...]]:
    spec: Dict[str, Tuple[int, ...]] = {}
    cin = 3
    for name, cout in PYRAMID:
        _conv_spec(spec, f"moduleExtractor/{name}/0", 3, cin, cout)
        _conv_spec(spec, f"moduleExtractor/{name}/2", 3, cout, cout)
        _conv_spec(spec, f"moduleExtractor/{name}/4", 3, cout, cout)
        cin = cout
    prev_feat = None
    for level in (6, 5, 4, 3, 2):
        mod = LEVELS[level]
        current = 81 if level == 6 else 81 + LEVEL_FEAT[level] + 4
        if level < 6:
            _conv_spec(spec, f"{mod}/moduleUpflow", 4, 2, 2)
            _conv_spec(spec, f"{mod}/moduleUpfeat", 4, prev_feat, 2)
        ch = current
        for name, cout in DENSE:
            _conv_spec(spec, f"{mod}/{name}/0", 3, ch, cout)
            ch += cout
        _conv_spec(spec, f"{mod}/moduleSix/0", 3, ch, 2)
        prev_feat = ch
    ch = prev_feat
    for idx, cout, _d in REFINER:
        _conv_spec(spec, f"moduleRefiner/moduleMain/{idx}", 3, ch, cout)
        ch = cout
    return spec


def _leaky(x):
    return jnp.where(x >= 0, x, 0.1 * x)


def _conv2d(p, x, stride=1, pad=1, dilation=1):
    y = conv(x, p["kernel"], stride, [(pad, pad)] * 2, dilation=(dilation, dilation))
    return y + p["bias"]


def _deconv(p, x):
    """ConvTranspose2d(4, stride 2, padding 1): the gradient of a convolution,
    input dilated by the stride, kernel flipped, padding k - 1 - 1."""
    y = conv(x, jnp.flip(jnp.asarray(p["kernel"]), (0, 1)), 1, [(2, 2)] * 2,
             lhs_dilation=(2, 2))
    return y + p["bias"]


def _sample(img, x, y):
    """Bilinear taps at pixel coordinates, out-of-range taps contribute 0
    (grid_sample, zeros padding, align_corners=True in pixel units)."""
    n, h, w, c = img.shape
    x0, y0 = jnp.floor(x), jnp.floor(y)
    wx, wy = x - x0, y - y0
    flat = img.reshape(n, h * w, c)
    out = 0.0
    for dy, dx, wgt in ((0, 0, (1 - wy) * (1 - wx)), (0, 1, (1 - wy) * wx),
                        (1, 0, wy * (1 - wx)), (1, 1, wy * wx)):
        xi, yi = x0 + dx, y0 + dy
        inside = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
        idx = (jnp.clip(yi, 0, h - 1) * w + jnp.clip(xi, 0, w - 1)).astype(jnp.int32)
        vals = jnp.take_along_axis(flat, idx.reshape(n, -1, 1), axis=1)
        vals = vals.reshape(x.shape + (c,))
        out = out + vals * (wgt * inside)[..., None]
    return out


def _resize(img, out_h, out_w):
    """Bilinear, align_corners=False, edge taps clamped (torch's default)."""
    n, h, w, _ = img.shape
    ys = jnp.clip((jnp.arange(out_h, dtype=jnp.float32) + 0.5) * (h / out_h) - 0.5, 0, h - 1)
    xs = jnp.clip((jnp.arange(out_w, dtype=jnp.float32) + 0.5) * (w / out_w) - 0.5, 0, w - 1)
    gx, gy = jnp.meshgrid(xs, ys)
    return _sample(img, jnp.broadcast_to(gx, (n, out_h, out_w)),
                   jnp.broadcast_to(gy, (n, out_h, out_w)))


def _warp(img, flow):
    """Backward warp; a pixel any of whose taps fell outside is zeroed (the
    sampled ones-channel is at most 0.999 there)."""
    n, h, w, _ = flow.shape
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    ones = jnp.ones(img.shape[:-1] + (1,), jnp.float32)
    s = _sample(jnp.concatenate([img, ones], -1), xs[None] + flow[..., 0],
                ys[None] + flow[..., 1])
    return s[..., :-1] * (s[..., -1:] > 0.999)


def _cost_volume(f1, f2):
    """81 channels: channel k is the channel-mean of f1 * f2 shifted by
    (dy, dx) = (k // 9 - 4, k % 9 - 4), zeros outside."""
    n, h, w, c = f1.shape
    f2p = jnp.pad(f2, ((0, 0), (4, 4), (4, 4), (0, 0)))
    taps = [jnp.mean(f1 * f2p[:, dy:dy + h, dx:dx + w, :], axis=-1)
            for dy in range(9) for dx in range(9)]
    return jnp.stack(taps, axis=-1)


def _extract(p, x):
    feats = []
    for name, _c in PYRAMID:
        q = p[name]
        x = _leaky(_conv2d(q["0"], x, 2))
        x = _leaky(_conv2d(q["2"], x))
        x = _leaky(_conv2d(q["4"], x))
        feats.append(x)
    return feats


def pwc_flow(p: dict, first: jnp.ndarray, second: jnp.ndarray) -> jnp.ndarray:
    """Flow first→second; (N, H, W, 3) RGB in [0, 255] → (N, H, W, 2) pixels."""
    n, h, w, _ = first.shape
    h64, w64 = int(math.ceil(h / 64.0) * 64), int(math.ceil(w / 64.0) * 64)

    def prep(img):
        return _resize(img[..., ::-1].astype(jnp.float32) / 255.0, h64, w64)

    pyr1, pyr2 = _extract(p["moduleExtractor"], prep(first)), \
        _extract(p["moduleExtractor"], prep(second))
    flow = feat = None
    for level in (6, 5, 4, 3, 2):
        q = p[LEVELS[level]]
        f1, f2 = pyr1[level - 1], pyr2[level - 1]
        if flow is None:
            feat = _leaky(_cost_volume(f1, f2))
        else:
            flow = _deconv(q["moduleUpflow"], flow)
            up = _deconv(q["moduleUpfeat"], feat)
            volume = _leaky(_cost_volume(f1, _warp(f2, flow * BACKWARD_SCALE[level])))
            feat = jnp.concatenate([volume, f1, flow, up], axis=-1)
        for name, _c in DENSE:
            feat = jnp.concatenate([_leaky(_conv2d(q[name]["0"], feat)), feat], axis=-1)
        flow = _conv2d(q["moduleSix"]["0"], feat)
    x = feat
    r = p["moduleRefiner"]["moduleMain"]
    for idx, _c, d in REFINER[:-1]:
        x = _leaky(_conv2d(r[idx], x, 1, d, d))
    flow = flow + _conv2d(r["12"], x)
    flow = 20.0 * _resize(flow, h, w)
    return flow * jnp.asarray([w / w64, h / h64], jnp.float32)


# ------------------------------------------------------------- the answer

PAIR_BLOCK = 16


def weight_specs() -> Dict[str, Dict[str, Tuple[int, ...]]]:
    return {"i3d_rgb": i3d_spec(3), "i3d_flow": i3d_spec(2), "pwc-sintel": pwc_spec()}


def _crop(x):
    h, w = x.shape[-3], x.shape[-2]
    fh, fw = (h - CROP) // 2, (w - CROP) // 2
    return x[..., fh:fh + CROP, fw:fw + CROP, :]


def rgb_stream(p, stack_u8):
    """(65, H, W, 3) uint8 → (1024,)"""
    x = 2.0 * _crop(stack_u8[:-1]).astype(jnp.float32) / 255.0 - 1.0
    return i3d_features(p, x[None])[0]


def flow_block(p, first_u8, second_u8):
    return _crop(pwc_flow(p, first_u8, second_u8))


def flow_stream(p, flow):
    """(64, 224, 224, 2) flow in pixels → (1024,)"""
    q = jnp.round(128.0 + 255.0 / 40.0 * jnp.clip(flow, -20.0, 20.0))
    return i3d_features(p, (2.0 * q / 255.0 - 1.0)[None])[0]


def host_stacks(path: str):
    frames, stamps, fps = decode_rgb(path)
    stacks, times, stack = [], [], []
    for rgb, pos in zip(frames, stamps):
        stack.append(resize_smaller_edge(rgb, EDGE))
        if len(stack) - 1 == STACK:
            stacks.append(np.stack(stack))
            times.append(pos)
            stack = stack[STEP:]
    return stacks, np.asarray(times), fps


def make_answer_fn(weights: Dict[str, dict]):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    video must hold. One stack at a time, the flow net 16 pairs at a time."""
    rgb_p = jax.device_put(weights["i3d_rgb"])
    flow_p = jax.device_put(weights["i3d_flow"])
    pwc_p = jax.device_put(weights["pwc-sintel"])
    rgb_fn, block_fn, flow_fn = jax.jit(rgb_stream), jax.jit(flow_block), jax.jit(flow_stream)

    def answer(path: str) -> Dict[str, np.ndarray]:
        stacks, times, fps = host_stacks(path)
        rgb, flo = [], []
        for stack in stacks:
            rgb.append(np.asarray(rgb_fn(rgb_p, stack)))
            flows = [block_fn(pwc_p, stack[a:a + PAIR_BLOCK], stack[a + 1:a + 1 + PAIR_BLOCK])
                     for a in range(0, STACK, PAIR_BLOCK)]
            flo.append(np.asarray(flow_fn(flow_p, jnp.concatenate(flows))))
        empty = np.zeros((0, 1024), np.float32)
        return {"rgb": np.stack(rgb) if rgb else empty,
                "flow": np.stack(flo) if flo else empty,
                "fps": np.asarray(fps), "timestamps_ms": times}

    return answer


FEATURE_KEYS = ("rgb", "flow")
EXACT_KEYS = ("fps", "timestamps_ms")

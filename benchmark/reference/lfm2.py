"""Plain reference for ``lfm2_24b_a2b_bf16``: the layers of LFM2-24B-A2B
(LiquidAI, ``config.json``, ``model_type`` ``lfm2_moe``) that the
configuration's chip holds, as the config and the family's published modelling
code describe them, in straightforward ``jax.numpy`` float32 at
``jax.default_matmul_precision("highest")``. One document at a time, no pages,
no kernels, no sorting, no chunks: the short convolution is three shifted sums
over the document with zeros before its first token, softmax attention's
scores are materialised per block of queries (so that a 16,384-token document
fits), and the routed layer is a loop over the held experts with a mask.
Weights are rounded to bfloat16 once, as the program rounds them, and kept on
the device in that form (the expert bias stays float32). The sums are float32;
the tensors the configuration states in bfloat16 are rounded to it where they
are made (``activations``; tier-1 runs float32 throughout): each norm's output
that a product reads, ``[B | C | u]``, ``B ⊙ u``, ``C ⊙ conv``, ``q``, ``k``, ``v``
and each after its norm, ``q`` (rotated, scaled) and ``k`` after rope,
attention's output, every gate, up and gated product of the dense unit and the
experts, each expert's output, and the residual after every mixer and
feed-forward. Router scores, the softmax and the final norm stay float32.
Imports nothing of the program's models or ops.

Per layer ``l``, input ``x`` (tokens × 2048); no matrix has a bias; RMSNorm
``eps`` 1e-5, a leaf ``…/scale`` the multiplier:

- ``h = RMSNorm(x)`` (``operator_norm``).
- *conv* (``layer_types[l] == "conv"``): ``[B | C | u] = h W_in`` (2048 → 6144, in
  that order); ``v = B ⊙ u``; ``w_t = Σ_{j<3} K[j] ⊙ v_{t−2+j}`` (``K`` depthwise
  ``(3, 2048)``, ``v`` zero before the document's first token); ``x ← x + (C ⊙ w)
  W_out``.
- *attention* (layers 2, 6, …): ``q = h W_q`` (32 heads of 64), ``k = h W_k``,
  ``v = h W_v`` (8 heads of 64); ``q`` and ``k`` each under a per-head RMSNorm
  over 64 (``q_layernorm``, ``k_layernorm``); rope θ 1e6 over all 64 dimensions,
  dimension ``i`` with ``i + 32`` (``rotate_half``), positions from 0 in every
  document; query head ``a`` meets key/value head ``a // 4``; causal softmax of
  ``q kᵀ / 8``; ``x ← x + o W_o``.
- ``h₂ = RMSNorm(x)`` (``ffn_norm``). Layers 0–1 (``num_dense_layers``):
  ``W2(silu(W1 h₂) ⊙ W3 h₂)`` at width 11,776. Later layers: ``s = sigmoid(h₂
  W_r)`` over 64 experts; the chosen set is the top 4 of ``s + b`` (``b``:
  ``expert_bias``, float32, for the choice only); ``g_e = s_e / (Σ_chosen s +
  1e-6) × 1.0``; ``y = Σ g_e expert_e(h₂)`` over the experts HELD (what absent
  ones would add is left out, as in the program: the share), every expert the
  same SwiGLU unit at width 1,536, no shared expert; ``x ← x + y``.
- after the last layer held, the final RMSNorm (``embedding_norm``); a
  segment's feature is the mean of those rows over its tokens. The output head
  (the tied embedding) is never applied.

Departures, each a statement about names or the draw (``assumed`` in the
configuration's file): leaf names are this repository's (``in_proj``,
``conv/kernel`` as ``(3, 2048)`` — the published ``conv.weight`` is ``(2048, 1,
3)`` —, ``out_proj``, ``q_norm/scale``, ``k_norm/scale``, ``router``,
``choice/bias`` for ``expert_bias``, ``mlp/{gate,up,down}_proj`` for ``w1``, ``w3``,
``w2``); the norms' scales are multipliers as published; the harness draws
``expert_bias`` by its last name as a bias, a small normal (std 0.05), where a
trained model's bias is whatever balancing left. ``weight_specs()`` lists ONE
LEAF PER EXPERT MATRIX; the expert ids in the names are also how the program
learns its share.

``activations`` is a dtype's name; ``"float32"`` rounds nothing.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the feature type is the program's; a program without it stops here, before
# 11.2 GB of weights are drawn for it (the one thing read of the program: a
# tuple of names, nothing of its models or ops)
from video_features_tpu.config import FEATURE_TYPES

if "lfm2" not in FEATURE_TYPES:
    raise ImportError("this program has no --feature_type lfm2")

# the catalog row's `config`, the keys the equations use
PUBLISHED = dict(
    vocab_size=65536, hidden_size=2048, intermediate_size=11776, norm_eps=1e-5,
    layer_types=("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 9
    + ("full_attention", "conv"),
    conv_L_cache=3, num_attention_heads=32, num_key_value_heads=8, rope_theta=1000000.0,
    num_dense_layers=2, num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1536,
    routed_scaling_factor=1.0,
)
RENORM_EPS = 1e-6  # the family's modelling code adds it to the chosen scores' sum
# the cut (`reduced` in the configuration's file): layers 0-5, every expert
LAYERS = tuple(range(6))
EXPERTS = tuple(range(64))
QUERY_BLOCK = 512  # scores of one block: 32 heads x 512 x 16,384 x 4 B = 1.07 GB

FEATURE_KEYS = ("lfm2",)
EXACT_KEYS = ("timestamps_ms", "tokens")


def is_attention(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "full_attention"


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["num_dense_layers"]


def weight_specs(cfg: dict = PUBLISHED, layers: Sequence[int] = LAYERS,
                 experts: Sequence[int] = EXPERTS) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    hid, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // heads
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg["vocab_size"], hid),
                                        "final_norm/scale": (hid,)}

    def unit(prefix, width):
        spec[f"{prefix}/gate_proj"] = (hid, width)
        spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if is_attention(cfg, layer):
            spec[f"{pre}/q_proj"] = (hid, heads * d)
            spec[f"{pre}/k_proj"] = (hid, kv * d)
            spec[f"{pre}/v_proj"] = (hid, kv * d)
            spec[f"{pre}/q_norm/scale"] = (d,)
            spec[f"{pre}/k_norm/scale"] = (d,)
            spec[f"{pre}/o_proj"] = (heads * d, hid)
        else:
            spec[f"{pre}/in_proj"] = (hid, 3 * hid)
            spec[f"{pre}/conv/kernel"] = (cfg["conv_L_cache"], hid)
            spec[f"{pre}/out_proj"] = (hid, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        if is_dense(cfg, layer):
            unit(f"{pre}/mlp", cfg["intermediate_size"])
        else:
            spec[f"{pre}/router"] = (hid, cfg["num_experts"])
            spec[f"{pre}/choice/bias"] = (cfg["num_experts"],)
            for e in experts:
                unit(f"{pre}/experts/{e}", cfg["moe_intermediate_size"])
    return {"lfm2": spec}


# --- the equations ------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rounder(activations: str):
    """→ ``r(x)``: float32 ``x`` rounded to ``activations``, kept float32. A
    ``reduce_precision``, not a cast there and back: the TPU's compiler drops
    such a pair inside a fusion, and the value would stay unrounded."""
    info = jnp.finfo(jnp.dtype(activations))
    return lambda x: lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def causal_conv(v, k):
    """``w_t = Σ_j k[j] · v_{t-(K-1)+j}``, ``v`` zero before the first token:
    ``K`` shifted sums. v (tokens, channels), k (K, channels)."""
    taps, n = k.shape[0], v.shape[0]
    padded = jnp.pad(v, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + n] * k[j] for j in range(taps))


def rope_tables(cfg: dict, n: int):
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    inv_freq = cfg["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)


def rotate_half(x, cos, sin):
    """x (tokens, heads, d): dimension ``i`` rotated with ``i + d/2``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(cfg: dict, w: dict, x, activations: str = "float32"):
    f32, r = jnp.float32, rounder(activations)
    hid = cfg["hidden_size"]
    h = r(rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["norm_eps"]))
    bcu = r(h @ w["in_proj"].astype(f32))
    b, c, u = bcu[:, :hid], bcu[:, hid:2 * hid], bcu[:, 2 * hid:]
    y = r(c * causal_conv(r(b * u), w["conv"]["kernel"].astype(f32)))
    return r(x + y @ w["out_proj"].astype(f32))


def attention(cfg: dict, w: dict, x, activations: str = "float32"):
    f32, r = jnp.float32, rounder(activations)
    n, heads, kv = x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["hidden_size"] // heads, cfg["norm_eps"]
    h = r(rms_norm(x, w["attn_norm"]["scale"].astype(f32), eps))
    q = r(h @ w["q_proj"].astype(f32)).reshape(n, heads, d)
    k = r(h @ w["k_proj"].astype(f32)).reshape(n, kv, d)
    v = r(h @ w["v_proj"].astype(f32)).reshape(n, kv, d)
    q = r(rms_norm(q, w["q_norm"]["scale"].astype(f32), eps))
    k = r(rms_norm(k, w["k_norm"]["scale"].astype(f32), eps))
    cos, sin = rope_tables(cfg, n)
    q, k = r(rotate_half(q, cos, sin) * d ** -0.5), r(rotate_half(k, cos, sin))
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))  # query head a meets a // 4
    block = min(QUERY_BLOCK, n)
    pad = -n % block
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    cols = jnp.arange(n)

    def one_block(args):
        qb, start = args
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    starts = jnp.arange(blocks.shape[0]) * block
    o = r(lax.map(one_block, (blocks, starts)).reshape(-1, heads * d)[:n])
    return r(x + o @ w["o_proj"].astype(f32))


def gated_unit(h, gate, up, r):
    """``silu(h W1) ⊙ h W3``, each rounded where the program rounds it."""
    f32 = jnp.float32
    return r(jax.nn.silu(r(h @ gate.astype(f32))) * r(h @ up.astype(f32)))


def routing(cfg: dict, h2, router, bias):
    """→ (weights, expert ids), both (tokens, top-k): the top-k of ``s + b``,
    weighted by ``s`` alone over the chosen sum plus 1e-6."""
    scores = jax.nn.sigmoid(h2 @ router.astype(jnp.float32))
    _, ids = lax.top_k(scores + bias.astype(jnp.float32), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return top / (top.sum(-1, keepdims=True) + RENORM_EPS) * cfg["routed_scaling_factor"], ids


def routed_part(cfg: dict, h2, router, bias, gates, ups, downs, expert_ids, r):
    """Σ over the experts in ``expert_ids`` (their matrices stacked in that
    order) of router weight × expert output: a loop and a mask."""
    weights, ids = routing(cfg, h2, router, bias)

    def one(e, y):
        g = jnp.sum(jnp.where(ids == expert_ids[e], weights, 0.0), axis=-1)
        out = r(gated_unit(h2, gates[e], ups[e], r) @ downs[e].astype(jnp.float32))
        return y + g[:, None] * out

    return lax.fori_loop(0, len(expert_ids), one, jnp.zeros_like(h2))


def layer_forward(cfg: dict, layer: int, w: dict, stacked, expert_ids, x,
                  activations: str = "float32"):
    """→ (the layer's output, the router's choices: (tokens, top-k) expert
    ids, empty for a dense layer)."""
    f32, r = jnp.float32, rounder(activations)
    if is_attention(cfg, layer):
        x = attention(cfg, w, x, activations)
    else:
        x = short_conv(cfg, w, x, activations)
    h2 = r(rms_norm(x, w["mlp_norm"]["scale"].astype(f32), cfg["norm_eps"]))
    if is_dense(cfg, layer):
        m = w["mlp"]
        y = gated_unit(h2, m["gate_proj"], m["up_proj"], r) @ m["down_proj"].astype(f32)
        return r(x + y), jnp.zeros((x.shape[0], 0), jnp.int32)
    bias = w["choice"]["bias"]
    y = routed_part(cfg, h2, w["router"], bias, *stacked, expert_ids, r)
    return r(x + y), routing(cfg, h2, w["router"], bias)[1]


def round_weights(tree, dtype=jnp.bfloat16):
    """Every leaf on the device, rounded to ``dtype`` once; the expert bias
    stays float32, as the program keeps it."""
    def leaf(path, a):
        keep = getattr(path[-1], "key", "") == "bias"
        return jnp.asarray(a, jnp.float32) if keep else jnp.asarray(a).astype(dtype)
    return jax.tree_util.tree_map_with_path(leaf, tree)


def make_forward(weights: dict, cfg: dict = PUBLISHED, precision: str = "highest",
                 activations: str = "bfloat16", choices: bool = False):
    """``weights``: the nested tree of ``weight_specs()``'s leaves, already
    rounded → ``features(ids, segment_ends) -> (segments, hidden) float32``
    (with ``choices``: and each routed layer's router choices)."""
    layers = sorted(int(l) for l in weights["layers"])
    expert_ids, stacked = (), {}
    for l in layers:
        experts = weights["layers"][str(l)].get("experts")
        if experts:  # layers hold the same experts (the share)
            expert_ids = tuple(sorted(int(e) for e in experts))
            stacked[l] = tuple(jnp.stack([experts[str(e)][m] for e in expert_ids])
                               for m in ("gate_proj", "up_proj", "down_proj"))
            del weights["layers"][str(l)]["experts"]  # held once, stacked
    ids_arr = jnp.asarray(expert_ids, jnp.int32)
    step = jax.jit(layer_forward, static_argnums=(0, 1, 6))

    def features(ids: np.ndarray, segment_ends: np.ndarray):
        chosen = []
        with jax.default_matmul_precision(precision):
            x = weights["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
            for l in layers:
                x, picked = step(cfg_key(cfg), l, weights["layers"][str(l)], stacked.get(l),
                                 ids_arr, x, activations)
                if picked.shape[1]:
                    chosen.append(np.asarray(picked))
            x = rms_norm(x, weights["final_norm"]["scale"].astype(jnp.float32), cfg["norm_eps"])
            x = np.asarray(x, np.float64)
        starts = np.concatenate([[0], segment_ends[:-1]])
        rows = np.stack([x[a:b].mean(axis=0) for a, b in zip(starts, segment_ends)]
                        ).astype(np.float32)
        return (rows, chosen) if choices else rows

    return features


class cfg_key(dict):
    """A configuration as a static argument of ``jit``: hashable by value."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def make_answer_fn(weights: Dict[str, dict], cfg: dict = PUBLISHED, activations: str = "bfloat16"):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    transcript (``<stem>.tokens.npz``) must hold."""
    features = make_forward(round_weights(weights["lfm2"]), cfg, activations=activations)

    def answer(path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            ids, ends = z["ids"], z["segment_ends"]
            stamps = np.stack([z["start_ms"], z["end_ms"]], axis=1)
        return {"lfm2": features(ids, ends), "timestamps_ms": stamps,
                "tokens": np.diff(ends, prepend=0).astype(np.int32)}

    return answer

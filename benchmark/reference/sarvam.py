"""Plain reference for ``sarvam_105b_bf16``: the layers of sarvam-105b
(sarvamai, ``config.json``, ``model_type`` ``sarvam_mla``) that the
configuration's chip holds, as the config and the family's published form
describe them, in straightforward ``jax.numpy`` float32 at
``jax.default_matmul_precision("highest")``. One document at a time, no pages,
no kernels, no sorting: scores are materialised per block of queries (so that
a 16,384-token document fits), the shared rope key meets every head's rotated
queries by a plain einsum, and the routed layer is a loop over the held
experts with a mask. Weights are rounded to bfloat16 once, as the program
rounds them, and kept on the device in that form; everything else is float32.
Imports nothing of the program's models or ops.

Per layer ``l``, input ``x`` (tokens × 4096); every matrix without bias,
RMSNorm ``eps`` 1e-6; 64 heads:

- ``h = RMSNorm(x)``; ``q = h W_q`` (→ 64·192); head ``a``:
  ``q_a = [qN_a (128) | qR_a (64)]``.
- ``c' = h W_kva`` (→ 576); ``c = RMSNorm(c'[:, :512])``; ``kR = c'[:, 512:]``:
  one rope key a token, shared by all heads.
- ``[kN_a | v_a] = c W_kvb`` (512 → 64·256; head ``a``'s 256 columns: 128 of
  key, then 128 of value).
- rope on ``qR_a`` and ``kR`` only, positions from 0 in every document:
  ``rope_theta`` 10000 over 64 dimensions, ``deepseek_yarn`` (factor 40,
  original 4096, ``beta_fast`` 32, ``beta_slow`` 1): interpolated and plain
  inverse frequencies blended by a linear ramp between the dimensions that
  turn ``beta_fast`` and ``beta_slow`` times in the original context; cos and
  sin times ``mscale(40, mscale) / mscale(40, mscale_all_dim)`` = 1,
  ``mscale(f, m) = 0.1 m ln f + 1``. Dimension ``2i`` pairs with ``2i + 1``.
- ``s_a[t, u] = (qN_a[t]·kN_a[u] + qR_a[t]·kR[u]) · 192^-0.5 · mscale(40, 1)²``,
  ``u ≤ t``; softmax in float32; ``o_a = Σ p_a v_a``; ``x ← x + [o_0 … o_63] W_o``.
- ``h₂ = RMSNorm(x)``. Layer 0: ``down(silu(gate(h₂)) · up(h₂))`` at width
  16384. Layers ≥ 1: ``z = h₂ W_r`` (→ 128); ``σ = sigmoid(z)``; the chosen
  set is the top 8 of ``σ + b`` (``b``: the expert bias); ``w_e = 2.5 σ_e / Σ_chosen σ``;
  ``y = Σ w_e expert_e(h₂) + shared(h₂)`` over the experts HELD (what the absent
  ones would add is left out, as in the program: the share); every expert is
  the same gated unit at width 2048; ``x ← x + y``.
- after the last layer held, the model's final RMSNorm; a segment's feature is
  the mean of those rows over its tokens. The output head is not held.

Assumed (``assumed`` in the configuration's file): **A1** no low-rank query
path (no ``q_lora_rank``); **A2** ``use_qk_norm`` is the family's latent norm,
on the 512-wide latent only; **A3** router: sigmoid scores, bias for the
choice only, chosen scores renormalised then × 2.5, no expert groups; **A4**
rope pairs ``2i`` with ``2i + 1``; **A5** SiLU, no shared-expert gate, token
ids in.

``weight_specs()`` lists ONE LEAF PER EXPERT MATRIX (``weights.make_leaf``
takes ``prod(shape[:-1])`` as a kernel's fan-in), and the expert bias as
``layers/<l>/choice/bias`` so that it is drawn as a bias is: a small normal.
The expert ids in the names are also how the program learns its share.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the feature type is the program's; a program without it stops here, before
# 13.9 GB of weights are drawn for it (the one thing read of the program: a
# tuple of names, nothing of its models or ops)
from video_features_tpu.config import FEATURE_TYPES

if "sarvam" not in FEATURE_TYPES:
    raise ImportError("this program has no --feature_type sarvam")

# the catalog row's `config`, the keys the equations use
PUBLISHED = dict(
    vocab_size=262144, hidden_size=4096, intermediate_size=16384, num_attention_heads=64,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
    first_k_dense_replace=1, rms_norm_eps=1e-6, num_experts=128, num_experts_per_tok=8,
    moe_intermediate_size=2048, num_shared_experts=1, routed_scaling_factor=2.5,
    rope_theta=10000.0,
    rope_scaling=dict(factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
)
# the cut (`reduced` in the configuration's file): layers 0-4, experts 0-15
LAYERS = (0, 1, 2, 3, 4)
EXPERTS = tuple(range(16))
QUERY_BLOCK = 256  # scores of one block: 64 heads x 256 x 16,384 x 4 B = 1.07 GB

FEATURE_KEYS = ("sarvam",)
EXACT_KEYS = ("timestamps_ms", "tokens")


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def weight_specs(cfg: dict = PUBLISHED, layers: Sequence[int] = LAYERS,
                 experts: Sequence[int] = EXPERTS) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    hid, heads, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg["vocab_size"], hid),
                                        "final_norm/scale": (hid,)}

    def unit(prefix, width):
        spec[f"{prefix}/gate_proj"] = (hid, width)
        spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        spec[f"{pre}/q_proj"] = (hid, heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]))
        spec[f"{pre}/kv_a_proj"] = (hid, rank + cfg["qk_rope_head_dim"])
        spec[f"{pre}/kv_norm/scale"] = (rank,)
        spec[f"{pre}/kv_b_proj"] = (rank, heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))
        spec[f"{pre}/o_proj"] = (heads * cfg["v_head_dim"], hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        if is_dense(cfg, layer):
            unit(f"{pre}/mlp", cfg["intermediate_size"])
        else:
            spec[f"{pre}/router"] = (hid, cfg["num_experts"])
            spec[f"{pre}/choice/bias"] = (cfg["num_experts"],)
            unit(f"{pre}/shared", cfg["moe_intermediate_size"] * cfg["num_shared_experts"])
            for e in experts:
                unit(f"{pre}/experts/{e}", cfg["moe_intermediate_size"])
    return {"sarvam": spec}


# --- the equations ------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: dict) -> float:
    r = cfg["rope_scaling"]
    q_head_dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return mscale(r["factor"], r["mscale_all_dim"]) ** 2 / math.sqrt(q_head_dim)


def rope_tables(cfg: dict, positions: np.ndarray, dtype=np.float32):
    """cos and sin, (positions, rot/2), of ``deepseek_yarn`` over the head's
    ``qk_rope_head_dim`` rotated dimensions."""
    r, rot, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    orig = r["original_max_position_embeddings"]

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / r["factor"] * ramp + inv * (1.0 - ramp)
    factor = mscale(r["factor"], r["mscale"]) / mscale(r["factor"], r["mscale_all_dim"])
    angle = positions.astype(dtype)[:, None] * inv.astype(dtype)[None, :]
    return np.cos(angle) * dtype(factor), np.sin(angle) * dtype(factor)


def rotate(x, cos, sin):
    """x (tokens, heads, rot): dimension ``2i`` rotated with ``2i + 1``."""
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def gated_unit(h, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) @ down.astype(f32)


def attention(cfg: dict, w: dict, x, cos, sin, rope_term: bool = True):
    """``rope_term`` False leaves ``qR·kR`` out of the scores: the planted
    fault's arithmetic, for the tests that must tell the two apart."""
    f32 = jnp.float32
    n, heads = x.shape[0], cfg["num_attention_heads"]
    dn, dr, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"])
    h = rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"])
    q = (h @ w["q_proj"].astype(f32)).reshape(n, heads, dn + dr)
    q_own, q_rot = q[..., :dn], rotate(q[..., dn:], cos, sin)
    latent = h @ w["kv_a_proj"].astype(f32)
    c = rms_norm(latent[:, :rank], w["kv_norm"]["scale"].astype(f32), cfg["rms_norm_eps"])
    k_rot = rotate(latent[:, None, rank:], cos, sin)[:, 0]  # (tokens, 64): one for all heads
    kv = (c @ w["kv_b_proj"].astype(f32)).reshape(n, heads, dn + dv)
    k_own, v = kv[..., :dn], kv[..., dn:]
    block = min(QUERY_BLOCK, n)
    pad = -n % block
    blocks = [jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, a.shape[-1])
              for a in (q_own, q_rot)]
    cols = jnp.arange(n)
    scale = softmax_scale(cfg)

    def one_block(args):
        qo, qr, start = args  # (block, heads, 128), (block, heads, 64)
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        s = jnp.einsum("qhd,khd->hqk", qo, k_own)
        if rope_term:
            s = s + jnp.einsum("qhr,kr->hqk", qr, k_rot)
        p = jax.nn.softmax(jnp.where(seen[None], s * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    starts = jnp.arange(blocks[0].shape[0]) * block
    o = lax.map(one_block, (*blocks, starts)).reshape(-1, heads * dv)[:n]
    return x + o @ w["o_proj"].astype(f32)


def routing(cfg: dict, h2, router, bias):
    """→ (weights, expert ids), both (tokens, top-k): the top-k of ``σ + b``,
    weighted by ``σ`` alone."""
    scores = jax.nn.sigmoid(h2 @ router.astype(jnp.float32))
    _, ids = lax.top_k(scores + bias.astype(jnp.float32), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return top / top.sum(-1, keepdims=True) * cfg["routed_scaling_factor"], ids


def routed_part(cfg: dict, h2, router, bias, gates, ups, downs, expert_ids):
    """Σ over the experts in ``expert_ids`` (their matrices stacked in that
    order) of router weight × expert output: a loop and a mask."""
    weights, ids = routing(cfg, h2, router, bias)

    def one(e, y):
        w = jnp.sum(jnp.where(ids == expert_ids[e], weights, 0.0), axis=-1)
        return y + w[:, None] * gated_unit(h2, gates[e], ups[e], downs[e])

    return lax.fori_loop(0, len(expert_ids), one, jnp.zeros_like(h2))


def shared_part(h2, w: dict):
    return gated_unit(h2, w["gate_proj"], w["up_proj"], w["down_proj"])


def layer_forward(cfg: dict, layer: int, w: dict, stacked, expert_ids, x, cos, sin,
                  rope_term: bool = True):
    """→ (the layer's output, the router's choices: (tokens, top-k) expert
    ids, empty for the dense layer)."""
    x = attention(cfg, w, x, cos, sin, rope_term)
    h2 = rms_norm(x, w["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    if is_dense(cfg, layer):
        return x + gated_unit(h2, w["mlp"]["gate_proj"], w["mlp"]["up_proj"],
                              w["mlp"]["down_proj"]), jnp.zeros((x.shape[0], 0), jnp.int32)
    bias = w["choice"]["bias"]
    return (x + routed_part(cfg, h2, w["router"], bias, *stacked, expert_ids)
            + shared_part(h2, w["shared"])), routing(cfg, h2, w["router"], bias)[1]


def round_weights(tree, dtype=jnp.bfloat16):
    """Every leaf on the device, rounded to ``dtype`` once; the expert bias
    stays float32, as the program keeps it."""
    def leaf(path, a):
        keep = getattr(path[-1], "key", "") == "bias"
        return jnp.asarray(a, jnp.float32) if keep else jnp.asarray(a).astype(dtype)
    return jax.tree_util.tree_map_with_path(leaf, tree)


def make_forward(weights: dict, cfg: dict = PUBLISHED, precision: str = "highest",
                 choices: bool = False, rope_term: bool = True):
    """``weights``: the nested tree of ``weight_specs()``'s leaves, already
    rounded → ``features(ids, segment_ends) -> (segments, hidden) float32``
    (with ``choices``: and each sparse layer's router choices, for the
    readings ``benchmark/tests/sarvam_readings.py`` takes)."""
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
    step = jax.jit(layer_forward, static_argnums=(0, 1, 8))

    def features(ids: np.ndarray, segment_ends: np.ndarray):
        chosen = []
        with jax.default_matmul_precision(precision):
            x = weights["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
            cos, sin = (jnp.asarray(t) for t in rope_tables(cfg, np.arange(len(ids))))
            for l in layers:
                x, picked = step(cfg_key(cfg), l, weights["layers"][str(l)], stacked.get(l),
                                 ids_arr, x, cos, sin, rope_term)
                if picked.shape[1]:
                    chosen.append(np.asarray(picked))
            x = rms_norm(x, weights["final_norm"]["scale"].astype(jnp.float32),
                         cfg["rms_norm_eps"])
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


def make_answer_fn(weights: Dict[str, dict], cfg: dict = PUBLISHED):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    transcript (``<stem>.tokens.npz``) must hold."""
    features = make_forward(round_weights(weights["sarvam"]), cfg)

    def answer(path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            ids, ends = z["ids"], z["segment_ends"]
            stamps = np.stack([z["start_ms"], z["end_ms"]], axis=1)
        return {"sarvam": features(ids, ends), "timestamps_ms": stamps,
                "tokens": np.diff(ends, prepend=0).astype(np.int32)}

    return answer

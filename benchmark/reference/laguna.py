"""Plain reference for ``laguna_s21_bf16``: the layers of Laguna-S-2.1
(poolside, ``config.json``) that the configuration's chip holds, as the
config and the papers it names describe them, in straightforward ``jax.numpy``
float32 at ``jax.default_matmul_precision("highest")``. One document at a
time, no pages, no kernels, no sorting: scores are materialised (per
key/value head and block of queries, so that a 16,384-token document fits), and
the routed layer is a loop over the held experts with a mask. Weights are
rounded to bfloat16 once, as the program rounds them, and kept on the device in
that form; everything else is float32. Imports nothing of the program.

Per layer ``l``, input ``x`` (tokens × 3072); every projection without bias,
RMSNorm ``eps`` 1e-6:

- ``h = RMSNorm(x)``; ``q = h W_q`` (→ ``H_l``·128), ``k = h W_k``, ``v = h W_v``
  (→ 8·128); ``H_l`` = 48 in a ``full_attention`` layer (``l % 4 == 0``), 72 in
  a ``sliding_attention`` layer; ``H_l``/8 query heads share a key/value head.
- rope (``rope_parameters``): full layers rotate the first 64 of a head's 128
  dimensions with YaRN (``rope_theta`` 500000, ``factor`` 128,
  ``original_max_position_embeddings`` 8192, ``beta_fast`` 32, ``beta_slow`` 1;
  cos and sin times ``attention_factor`` 1.4852030263919618); sliding layers
  rotate all 128 with ``rope_theta`` 10000. Dimension ``i`` pairs with
  ``i + rot/2`` (``rotate_half``, the published implementations' convention).
  Positions start at 0 in every document.
- scores ``q·k/√128``, softmax in float32 over keys ``j ≤ i``, in a sliding
  layer also ``i − j < 512``.
- the per-head gate: ``g = σ(h W_g)`` (3072 → ``H_l``), head ``a``'s output
  times ``g[:, a]`` before ``W_o``; ``x ← x + o``.
- ``h₂ = RMSNorm(x)``. Layer 0: ``down(silu(gate(h₂)) · up(h₂))`` at width
  12288. Layers ≥ 1: router logits ``h₂ W_r`` (→ 256), softmax over all 256,
  the 10 largest renormalised to sum 1, times 2.5, applied to the experts'
  outputs; every routed expert and the shared expert is the same gated unit at
  width 1024; ``y = Σ wₑ·expertₑ(h₂) + shared(h₂)`` over the experts HELD
  (what the absent ones would add is left out, as in the program: the share);
  ``x ← x + y``.
- after the last layer held, the model's final RMSNorm; a segment's feature is
  the mean of those rows over its tokens.

Assumed, where the config gives a name or nothing (``assumed`` in the
configuration's file): **A1** the gate's form above (the head-wise gate of Qiu
et al. 2025, arXiv:2505.06708: sigmoid, from the layer's normed input, on the
attention output); **A2** router scores are a softmax over the 256 logits (the
key set is the Qwen-MoE family's, whose router is; there is no
``scoring_func``); **A3** SiLU, no shared-expert gate, no query/key norm (no
key names one); **A4** no tokenizer: inputs are token ids.

``weight_specs()`` lists ONE LEAF PER EXPERT MATRIX: ``weights.make_leaf``
scales a kernel by ``prod(shape[:-1])`` as its fan-in, which is right for a
``(3072, 1024)`` matrix and would read 196,608 for a stacked ``(64, 3072,
1024)``. The expert ids in the names are also how the program learns its share.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the catalog row's `config`, the keys the equations use
PUBLISHED = dict(
    vocab_size=100352, hidden_size=3072, intermediate_size=12288,
    num_key_value_heads=8, head_dim=128, heads_full=48, heads_sliding=72,
    sliding_window=512, rms_norm_eps=1e-6, num_experts=256, num_experts_per_tok=10,
    moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
    moe_routed_scaling_factor=2.5,
    full_rope=dict(rope_theta=500000.0, factor=128.0, original_max_position_embeddings=8192,
                   beta_fast=32.0, beta_slow=1.0, attention_factor=1.4852030263919618,
                   partial_rotary_factor=0.5),
    sliding_rope=dict(rope_theta=10000.0, partial_rotary_factor=1.0),
)
# the cut (`reduced` in the configuration's file): layers 0-4, experts 0-63
LAYERS = (0, 1, 2, 3, 4)
EXPERTS = tuple(range(64))
QUERY_BLOCK = 256  # scores of one block: 72 heads x 256 x 16,384 x 4 B = 1.2 GB

FEATURE_KEYS = ("laguna",)
EXACT_KEYS = ("timestamps_ms", "tokens")


def is_full(layer: int) -> bool:
    return layer % 4 == 0


def heads_of(cfg: dict, layer: int) -> int:
    return cfg["heads_full"] if is_full(layer) else cfg["heads_sliding"]


def weight_specs(cfg: dict = PUBLISHED, layers: Sequence[int] = LAYERS,
                 experts: Sequence[int] = EXPERTS) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    hid, kvw = cfg["hidden_size"], cfg["num_key_value_heads"] * cfg["head_dim"]
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg["vocab_size"], hid),
                                        "final_norm/scale": (hid,)}

    def unit(prefix, width):
        spec[f"{prefix}/gate_proj"] = (hid, width)
        spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    for layer in layers:
        pre, qw = f"layers/{layer}", heads_of(cfg, layer) * cfg["head_dim"]
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        spec[f"{pre}/q_proj"] = (hid, qw)
        spec[f"{pre}/k_proj"] = (hid, kvw)
        spec[f"{pre}/v_proj"] = (hid, kvw)
        spec[f"{pre}/g_proj"] = (hid, heads_of(cfg, layer))
        spec[f"{pre}/o_proj"] = (qw, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        if layer == 0:
            unit(f"{pre}/mlp", cfg["intermediate_size"])
        else:
            spec[f"{pre}/router"] = (hid, cfg["num_experts"])
            unit(f"{pre}/shared", cfg["shared_expert_intermediate_size"])
            for e in experts:
                unit(f"{pre}/experts/{e}", cfg["moe_intermediate_size"])
    return {"laguna": spec}


# --- the equations ------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_tables(cfg: dict, full: bool, positions: np.ndarray, dtype=np.float32):
    """cos and sin, (positions, rot/2), and the rotated width ``rot``."""
    r = cfg["full_rope" if full else "sliding_rope"]
    rot = int(cfg["head_dim"] * r["partial_rotary_factor"])
    exponent = np.arange(0, rot, 2, dtype=np.float64) / rot
    inv = 1.0 / r["rope_theta"] ** exponent
    factor = 1.0
    if full:  # YaRN
        orig, base = r["original_max_position_embeddings"], r["rope_theta"]

        def correction_dim(turns):
            return rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(correction_dim(r["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(r["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / r["factor"] * ramp + inv * (1.0 - ramp)
        factor = r["attention_factor"]
    angle = positions.astype(dtype)[:, None] * inv.astype(dtype)[None, :]
    return np.cos(angle) * dtype(factor), np.sin(angle) * dtype(factor), rot


def rotate(x, cos, sin, rot: int):
    """x (tokens, heads, head_dim): the first ``rot`` dimensions rotated."""
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def gated_unit(h, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) @ down.astype(f32)


def attention(cfg: dict, layer: int, w: dict, x, cos, sin, rot: int):
    f32 = jnp.float32
    n, d, kv = x.shape[0], cfg["head_dim"], cfg["num_key_value_heads"]
    heads = heads_of(cfg, layer)
    group = heads // kv
    h = rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"])
    q = rotate((h @ w["q_proj"].astype(f32)).reshape(n, heads, d), cos, sin, rot)
    k = rotate((h @ w["k_proj"].astype(f32)).reshape(n, kv, d), cos, sin, rot)
    v = (h @ w["v_proj"].astype(f32)).reshape(n, kv, d)
    gate = jax.nn.sigmoid(h @ w["g_proj"].astype(f32))
    block = min(QUERY_BLOCK, n)
    pad = -n % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, kv, group, d)
    cols = jnp.arange(n)

    def one_block(args):
        qi, start = args  # (block, kv, group, d)
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        if not is_full(layer):
            seen &= rows[:, None] - cols[None, :] < cfg["sliding_window"]
        s = jnp.einsum("qhgd,khd->hgqk", qi, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    starts = jnp.arange(qb.shape[0]) * block
    o = lax.map(one_block, (qb, starts)).reshape(-1, heads, d)[:n]
    return x + (o * gate[..., None]).reshape(n, heads * d) @ w["o_proj"].astype(f32)


def routing(cfg: dict, h2, router):
    """→ (weights, expert ids), both (tokens, top-k)."""
    probs = jax.nn.softmax(h2 @ router.astype(jnp.float32), axis=-1)
    top, ids = lax.top_k(probs, cfg["num_experts_per_tok"])
    return top / top.sum(-1, keepdims=True) * cfg["moe_routed_scaling_factor"], ids


def routed_part(cfg: dict, h2, router, gates, ups, downs, expert_ids):
    """Σ over the experts in ``expert_ids`` (their matrices stacked in that
    order) of router weight × expert output: a loop and a mask."""
    weights, ids = routing(cfg, h2, router)

    def one(e, y):
        w = jnp.sum(jnp.where(ids == expert_ids[e], weights, 0.0), axis=-1)
        return y + w[:, None] * gated_unit(h2, gates[e], ups[e], downs[e])

    return lax.fori_loop(0, len(expert_ids), one, jnp.zeros_like(h2))


def shared_part(h2, w: dict):
    return gated_unit(h2, w["gate_proj"], w["up_proj"], w["down_proj"])


def layer_forward(cfg: dict, layer: int, w: dict, stacked, expert_ids, x, cos, sin, rot: int):
    """→ (the layer's output, the router's choices: (tokens, top-k) expert
    ids, empty for the dense layer)."""
    x = attention(cfg, layer, w, x, cos, sin, rot)
    h2 = rms_norm(x, w["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    if layer == 0:
        return x + gated_unit(h2, w["mlp"]["gate_proj"], w["mlp"]["up_proj"],
                              w["mlp"]["down_proj"]), jnp.zeros((x.shape[0], 0), jnp.int32)
    return (x + routed_part(cfg, h2, w["router"], *stacked, expert_ids)
            + shared_part(h2, w["shared"])), routing(cfg, h2, w["router"])[1]


def round_weights(tree, dtype=jnp.bfloat16):
    """Every leaf on the device, rounded to ``dtype`` once."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(dtype), tree)


def make_forward(weights: dict, cfg: dict = PUBLISHED, precision: str = "highest",
                 choices: bool = False):
    """``weights``: the nested tree of ``weight_specs()``'s leaves, already
    rounded → ``features(ids, segment_ends) -> (segments, hidden) float32``
    (with ``choices``: and each sparse layer's router choices, for the
    readings ``benchmark/tests/laguna_readings.py`` takes)."""
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
            for l in layers:
                cos, sin, rot = rope_tables(cfg, is_full(l), np.arange(len(ids)))
                x, picked = step(cfg_key(cfg), l, weights["layers"][str(l)], stacked.get(l),
                                 ids_arr, x, jnp.asarray(cos), jnp.asarray(sin), rot)
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
    features = make_forward(round_weights(weights["laguna"]), cfg)

    def answer(path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            ids, ends = z["ids"], z["segment_ends"]
            stamps = np.stack([z["start_ms"], z["end_ms"]], axis=1)
        return {"laguna": features(ids, ends), "timestamps_ms": stamps,
                "tokens": np.diff(ends, prepend=0).astype(np.int32)}

    return answer

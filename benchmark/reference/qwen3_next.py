"""Plain reference for ``qwen3_next_80b_bf16``: the layers of
Qwen3-Next-80B-A3B (Qwen, ``config.json``, ``model_type`` ``qwen3_next``) that
the configuration's chip holds, as the config and the family's published
implementation describe them, in straightforward ``jax.numpy`` float32 at
``jax.default_matmul_precision("highest")``. One document at a time, no pages,
no kernels, no chunks, no sorting: the gated delta rule is a ``lax.scan`` over
TOKENS, the causal convolution four shifted sums, softmax attention's scores
are materialised per block of queries, and the routed layer is a loop over the
held experts with a mask. Weights are rounded to bfloat16 once, as the program
rounds them, and kept on the device in that form (``…/bias`` leaves stay
float32); everything else is float32. Imports nothing of the program's models
or ops.

``h = norm(x)`` is RMSNorm, ``eps`` 1e-6, a leaf ``…/scale`` the multiplier
itself (the published parameterisation multiplies by ``1 + w``; a converter
adds the 1), the gated norm's weight as published. Layer ``l`` is
``full_attention`` where ``(l + 1) % 4 == 0`` and ``linear_attention`` (Gated
DeltaNet) otherwise; every matrix without bias.

*Gated DeltaNet layer* (16 key heads, 32 value heads, all 128 wide; value head
``j`` belongs to key head ``j // 2``):

1. ``q, k = h W_q, h W_k`` (16·128 each); ``v, z = h W_v, h W_z`` (32·128
   each); ``b, a = h W_b, h W_a`` (32 each).
2. ``[q; k; v] ← silu(conv(cat(q, k, v)))``: per channel ``c`` of the 8,192,
   ``y_t = Σ_{j=0..3} w[j, c] · u_{t-3+j}``, ``u`` zero before the document's
   first token.
3. ``β_t = sigmoid(b_t)``; ``g_t = -exp(A_log) · softplus(a_t + dt_bias)``;
   ``α_t = exp(g_t)``.
4. ``q̂ = q / sqrt(Σ q² + 1e-6) / sqrt(128)``; ``k̂ = k / sqrt(Σ k² + 1e-6)``.
5. Per value head a state ``S`` (128 × 128), zero at the document's first
   token: ``S ← α_t S``; ``δ_t = β_t (v_t − Sᵀ k̂_t)``; ``S ← S + k̂_t δ_tᵀ``;
   ``o_t = Sᵀ q̂_t``.
6. ``o ← RMSNorm_128(o) · w_norm · silu(z)`` head by head; ``x ← x + o W_out``.

*Full-attention layer* (16 query heads, 2 key/value heads, 256 wide):
``q_proj`` gives each head ``[q | gate]`` (256 + 256); per-head RMSNorm on ``q``
and on ``k``; rope on the first 64 of the 256 (``rope_theta`` 1e7, dimension
``i`` with ``i + 32``); causal softmax of ``q kᵀ / 16``; ``o ← o · sigmoid(gate)``
element-wise; ``x ← x + o W_o``.

*Sparse unit, every layer:* ``h₂ = norm(x)``; ``softmax(h₂ W_r)`` over all 512 in
float32, top 10, renormalised to sum 1; the HELD experts' ``down(silu(gate) ·
up)`` (what the absent ones would add is left out, as in the program: the
share); plus ``sigmoid(h₂ w_s) · shared(h₂)``. After the last layer held, the
model's final RMSNorm; a segment's feature is the mean of those rows over its
tokens. The output head and the multi-token-prediction module are not held.

Departures from the published checkpoint, both statements about names and
column order that the program's ``stack_checkpoint`` would settle once: the
projections are separate leaves (the checkpoint interleaves ``q, k, v, z`` and
``b, a`` by key-head group), and norm scales are multipliers.

``weight_specs()`` lists ONE LEAF PER EXPERT MATRIX (``weights.make_leaf``
takes ``prod(shape[:-1])`` as a kernel's fan-in) and names ``A_log`` and
``dt_bias`` ``…/a_log/bias`` and ``…/dt/bias`` so that they are drawn as a bias
is: small normals, ``A ≈ 1`` and ``α_t = exp(−softplus(a_t))``, median 0.5. The
expert ids in the names are also how the program learns its share.

``fault`` (for the tests that must tell them apart, never for ``correct``):
``"carry"`` drops the state every ``FAULT_CHUNK`` tokens (a chunked form that
loses what it carries between chunks); ``"delta"`` leaves the correction out,
``δ_t = β_t v_t`` (decayed linear attention).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the feature type is the program's; a program without it stops here, before
# 8.3 GB of weights are drawn for it (the one thing read of the program: a
# tuple of names, nothing of its models or ops)
from video_features_tpu.config import FEATURE_TYPES

if "qwen3_next" not in FEATURE_TYPES:
    raise ImportError("this program has no --feature_type qwen3_next")

# the catalog row's `config`, the keys the equations use
PUBLISHED = dict(
    vocab_size=151936, hidden_size=2048, rms_norm_eps=1e-6, full_attention_interval=4,
    linear_num_key_heads=16, linear_num_value_heads=32, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel_dim=4,
    num_attention_heads=16, num_key_value_heads=2, head_dim=256, partial_rotary_factor=0.25,
    rope_theta=10000000.0,
    num_experts=512, num_experts_per_tok=10, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, norm_topk_prob=True,
)
# the cut (`reduced` in the configuration's file): layers 0-3, experts 0-127
LAYERS = (0, 1, 2, 3)
EXPERTS = tuple(range(128))
QUERY_BLOCK = 512  # scores of one block: 16 heads x 512 x 16,384 x 4 B = 0.54 GB
FAULT_CHUNK = 64

FEATURE_KEYS = ("qwen3_next",)
EXACT_KEYS = ("timestamps_ms", "tokens")


def is_full(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def weight_specs(cfg: dict = PUBLISHED, layers: Sequence[int] = LAYERS,
                 experts: Sequence[int] = EXPERTS) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    hid = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg["vocab_size"], hid),
                                        "final_norm/scale": (hid,)}

    def unit(prefix, width):
        spec[f"{prefix}/gate_proj"] = (hid, width)
        spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if is_full(cfg, layer):
            spec[f"{pre}/q_proj"] = (hid, heads * 2 * d)
            spec[f"{pre}/k_proj"] = (hid, kv * d)
            spec[f"{pre}/v_proj"] = (hid, kv * d)
            spec[f"{pre}/q_norm/scale"] = (d,)
            spec[f"{pre}/k_norm/scale"] = (d,)
            spec[f"{pre}/o_proj"] = (heads * d, hid)
        else:
            spec[f"{pre}/q_proj"] = (hid, keys)
            spec[f"{pre}/k_proj"] = (hid, keys)
            spec[f"{pre}/v_proj"] = (hid, values)
            spec[f"{pre}/z_proj"] = (hid, values)
            spec[f"{pre}/b_proj"] = (hid, cfg["linear_num_value_heads"])
            spec[f"{pre}/a_proj"] = (hid, cfg["linear_num_value_heads"])
            spec[f"{pre}/conv"] = (cfg["linear_conv_kernel_dim"], 2 * keys + values)
            spec[f"{pre}/dt/bias"] = (cfg["linear_num_value_heads"],)
            spec[f"{pre}/a_log/bias"] = (cfg["linear_num_value_heads"],)
            spec[f"{pre}/gdn_norm/scale"] = (cfg["linear_value_head_dim"],)
            spec[f"{pre}/out_proj"] = (values, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        spec[f"{pre}/router"] = (hid, cfg["num_experts"])
        unit(f"{pre}/shared", cfg["shared_expert_intermediate_size"])
        spec[f"{pre}/shared_gate"] = (hid, 1)
        for e in experts:
            unit(f"{pre}/experts/{e}", cfg["moe_intermediate_size"])
    return {"qwen3_next": spec}


# --- the equations ------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_tables(cfg: dict, positions: np.ndarray, dtype=np.float32):
    """cos and sin, (positions, rot/2), over the head's first
    ``partial_rotary_factor`` dimensions; no scaling."""
    rot = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = positions.astype(dtype)[:, None] * inv.astype(dtype)[None, :]
    return np.cos(angle), np.sin(angle)


def rotate(x, cos, sin):
    """x (tokens, heads, head_dim): dimension ``i`` of the first ``rot``
    rotated with ``i + rot/2``; the rest passes."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def causal_conv(u, w):
    """``y_t = Σ_j w[j] · u_{t-(K-1)+j}``, ``u`` zero before the first token:
    ``K`` shifted sums. u (tokens, channels), w (K, channels)."""
    taps, n = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + n] * w[j] for j in range(taps))


def decay_and_beta(w: dict, a, b):
    """→ (g, β), both (tokens, value heads) float32: ``g = -exp(A_log) ·
    softplus(a + dt_bias)``, ``β = sigmoid(b)``."""
    g = -jnp.exp(w["a_log"]["bias"]) * jax.nn.softplus(a + w["dt"]["bias"])
    return g, jax.nn.sigmoid(b)


def delta_rule(q, k, v, g, beta, fault: str = ""):
    """Step 5 token by token. q, k (tokens, value heads, 128) normalised, v
    the same shape, g, beta (tokens, value heads) → o like v."""
    def one(state, row):
        qt, kt, vt, gt, bt, t = row
        if fault == "carry":
            state = jnp.where(t % FAULT_CHUNK == 0, 0.0, state)
        state = state * jnp.exp(gt)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, kt)
        delta = bt[:, None] * (vt if fault == "delta" else vt - seen)
        state = state + kt[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, out = lax.scan(one, jnp.zeros((heads, dk, dv), jnp.float32),
                      (q, k, v, g, beta, jnp.arange(q.shape[0])))
    return out


def gated_delta_net(cfg: dict, w: dict, x, fault: str = "", stats: bool = False):
    f32 = jnp.float32
    n = x.shape[0]
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    h = rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"])
    u = jnp.concatenate([h @ w[m].astype(f32) for m in ("q_proj", "k_proj", "v_proj")], axis=-1)
    u = jax.nn.silu(causal_conv(u, w["conv"].astype(f32)))
    q, k, v = jnp.split(u, [kh * dk, 2 * kh * dk], axis=-1)
    q, k, v = q.reshape(n, kh, dk), k.reshape(n, kh, dk), v.reshape(n, vh, dv)
    z = (h @ w["z_proj"].astype(f32)).reshape(n, vh, dv)
    g, beta = decay_and_beta(w, h @ w["a_proj"].astype(f32), h @ w["b_proj"].astype(f32))
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, vh // kh, axis=1) for a in (q, k))  # value head j: key head j // 2
    o = delta_rule(q, k, v, g, beta, fault)
    o = rms_norm(o, w["gdn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"]) * jax.nn.silu(z)
    out = x + o.reshape(n, vh * dv) @ w["out_proj"].astype(f32)
    return (out, jnp.exp(g)) if stats else out


def full_attention(cfg: dict, w: dict, x, cos, sin):
    f32 = jnp.float32
    n, heads, kv, d = (x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                       cfg["head_dim"])
    h = rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"])
    qg = (h @ w["q_proj"].astype(f32)).reshape(n, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ w["k_proj"].astype(f32)).reshape(n, kv, d)
    v = (h @ w["v_proj"].astype(f32)).reshape(n, kv, d)
    q = rotate(rms_norm(q, w["q_norm"]["scale"].astype(f32), cfg["rms_norm_eps"]), cos, sin)
    k = rotate(rms_norm(k, w["k_norm"]["scale"].astype(f32), cfg["rms_norm_eps"]), cos, sin)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    block = min(QUERY_BLOCK, n)
    pad = -n % block
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    cols = jnp.arange(n)

    def one_block(args):
        qb, start = args
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    starts = jnp.arange(blocks.shape[0]) * block
    o = lax.map(one_block, (blocks, starts)).reshape(-1, heads, d)[:n]
    o = (o * jax.nn.sigmoid(gate)).reshape(n, heads * d)
    return x + o @ w["o_proj"].astype(f32)


def gated_unit(h, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) @ down.astype(f32)


def routing(cfg: dict, h2, router):
    """→ (weights, expert ids), both (tokens, top-k): softmax over all
    experts, the top-k renormalised to sum 1."""
    scores = jax.nn.softmax(h2 @ router.astype(jnp.float32), axis=-1)
    top, ids = lax.top_k(scores, cfg["num_experts_per_tok"])
    return top / top.sum(-1, keepdims=True), ids


def routed_part(cfg: dict, h2, router, gates, ups, downs, expert_ids):
    """Σ over the experts in ``expert_ids`` (their matrices stacked in that
    order) of router weight × expert output: a loop and a mask."""
    weights, ids = routing(cfg, h2, router)

    def one(e, y):
        w = jnp.sum(jnp.where(ids == expert_ids[e], weights, 0.0), axis=-1)
        return y + w[:, None] * gated_unit(h2, gates[e], ups[e], downs[e])

    return lax.fori_loop(0, len(expert_ids), one, jnp.zeros_like(h2))


def shared_part(h2, w: dict, w_gate):
    """``sigmoid(h₂ w_s) · shared(h₂)``: the shared expert behind its
    per-token gate."""
    gate = jax.nn.sigmoid(h2 @ w_gate.astype(jnp.float32))
    return gate * gated_unit(h2, w["gate_proj"], w["up_proj"], w["down_proj"])


def layer_forward(cfg: dict, layer: int, w: dict, stacked, expert_ids, x, cos, sin,
                  fault: str = ""):
    """→ (the layer's output, the router's choices (tokens, top-k), the
    decays ``α`` of a linear layer (tokens, value heads), empty for a full
    one)."""
    if is_full(cfg, layer):
        x, alpha = full_attention(cfg, w, x, cos, sin), jnp.zeros((x.shape[0], 0), jnp.float32)
    else:
        x, alpha = gated_delta_net(cfg, w, x, fault, stats=True)
    h2 = rms_norm(x, w["mlp_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    y = (routed_part(cfg, h2, w["router"], *stacked, expert_ids)
         + shared_part(h2, w["shared"], w["shared_gate"]))
    return x + y, routing(cfg, h2, w["router"])[1], alpha


def round_weights(tree, dtype=jnp.bfloat16):
    """Every leaf on the device, rounded to ``dtype`` once; ``…/bias`` leaves
    (``A_log``, ``dt_bias``) stay float32, as the program keeps them."""
    def leaf(path, a):
        keep = getattr(path[-1], "key", "") == "bias"
        return jnp.asarray(a, jnp.float32) if keep else jnp.asarray(a).astype(dtype)
    return jax.tree_util.tree_map_with_path(leaf, tree)


def make_forward(weights: dict, cfg: dict = PUBLISHED, precision: str = "highest",
                 choices: bool = False, fault: str = ""):
    """``weights``: the nested tree of ``weight_specs()``'s leaves, already
    rounded → ``features(ids, segment_ends) -> (segments, hidden) float32``
    (with ``choices``: and each layer's router choices and each linear layer's
    decays, for the readings ``benchmark/tests/qwen3_next_readings.py``
    takes)."""
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
        chosen, decays = [], []
        with jax.default_matmul_precision(precision):
            x = weights["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
            cos, sin = (jnp.asarray(t) for t in rope_tables(cfg, np.arange(len(ids))))
            for l in layers:
                x, picked, alpha = step(cfg_key(cfg), l, weights["layers"][str(l)], stacked[l],
                                        ids_arr, x, cos, sin, fault)
                chosen.append(np.asarray(picked))
                if alpha.shape[1]:
                    decays.append(np.asarray(alpha))
            x = rms_norm(x, weights["final_norm"]["scale"].astype(jnp.float32),
                         cfg["rms_norm_eps"])
            x = np.asarray(x, np.float64)
        starts = np.concatenate([[0], segment_ends[:-1]])
        rows = np.stack([x[a:b].mean(axis=0) for a, b in zip(starts, segment_ends)]
                        ).astype(np.float32)
        return (rows, chosen, decays) if choices else rows

    return features


class cfg_key(dict):
    """A configuration as a static argument of ``jit``: hashable by value."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def make_answer_fn(weights: Dict[str, dict], cfg: dict = PUBLISHED):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    transcript (``<stem>.tokens.npz``) must hold."""
    features = make_forward(round_weights(weights["qwen3_next"]), cfg)

    def answer(path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            ids, ends = z["ids"], z["segment_ends"]
            stamps = np.stack([z["start_ms"], z["end_ms"]], axis=1)
        return {"qwen3_next": features(ids, ends), "timestamps_ms": stamps,
                "tokens": np.diff(ends, prepend=0).astype(np.int32)}

    return answer

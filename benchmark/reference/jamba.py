"""Plain reference for ``jamba2_3b_bf16``: AI21-Jamba2-3B (AI21, ``config.json``,
``model_type`` ``jamba``), all 28 layers, as the config and the family's
published implementation describe them, in straightforward ``jax.numpy``
float32 at ``jax.default_matmul_precision("highest")``. One document at a time,
no pages, no kernels, no chunks: the selective scan is a ``lax.scan`` over
TOKENS that makes each token's decays ``exp(Δ_t A)`` inside its step (the
``(tokens, 5120, 16)`` decays are never held), the causal convolution four
shifted sums, and softmax attention's scores are materialised per block of
queries. Weights are rounded to bfloat16 once, as the program rounds them, and
kept on the device in that form (``…/bias`` leaves stay float32). The sums are
float32; the tensors the configuration states in bfloat16 are rounded to it
where they are made (``activations``; tier-1 runs float32 throughout): each
norm's output that a product reads, ``u`` and ``z`` after the in-projection,
``u`` after the convolution, ``δ`` after its norm, the gated scan's output,
attention's ``q`` (scaled first), ``k``, ``v`` and its output, the dense
unit's gate, up and product, and the residual after every mixer and every
dense unit. ``B``, ``C``, ``Δ``, the state, the softmax and the final norm stay
float32. Imports nothing of the program's models or ops.

``h = norm(x)`` is RMSNorm, ``eps`` 1e-6, a leaf ``…/scale`` the multiplier
(as published). Layer ``l`` is attention where ``l % 14 == 7`` (layers 7 and
21) and a Mamba mixer otherwise; every layer is ``x ← x + mixer(norm₁ x)``
then ``x ← x + mlp(norm₂ x)``, the dense unit ``down(silu(gate h) · up h)`` of
width 8192 (``num_experts`` 1).

*Mamba mixer* (``d_inner`` 5120, ``N = d_state`` 16, ``R = dt_rank`` 160,
``d_conv`` 4):

1. ``[u, z] = h W_in`` (``2560 × 10240``, no bias).
2. ``u ← silu(conv(u) + b_conv)``: per channel ``y_t = Σ_{j<4} w[j, c] u_{t−3+j}``,
   ``u`` zero before the document's first token.
3. ``[δ, B, C] = u W_x`` (160, 16, 16); each under its own RMSNorm.
4. ``Δ = softplus(δ W_dt + b_dt)``.
5. ``A = −exp(A_log)`` ``(5120, 16)``; from ``H = 0``: ``H_t = exp(Δ_t A) ⊙ H_{t−1}
   + (Δ_t u_t) B_tᵀ``; ``y_t = H_t C_t + D ⊙ u_t``.
6. ``x ← x + (y ⊙ silu(z)) W_out``.

*Attention* (20 query heads over ONE key/value head of 128, no bias): causal
softmax of ``q kᵀ / √128`` with no positional encoding; ``x ← x + o W_o``.

After the last layer the final RMSNorm; a segment's feature is the mean of
those rows over its tokens. The output head (the tied embedding) is never
applied.

``weight_specs()`` names ``A_log``, ``b_dt`` and ``b_conv`` ``…/a_log/bias``,
``…/dt_proj/bias`` and ``…/conv/bias`` and ``D`` ``…/d/scale``, so that the
harness draws them by a leaf's last name as a bias (a small normal: ``A ≈
−1``) and as a norm scale (0.8–1.2, near the published ``D = 1``).

``activations`` is a dtype's name; ``"float32"`` rounds nothing.

``fault`` (for the tests and readings that must tell them apart, never for
``correct``): ``"reset"`` does not restart the state at a document's first
token when documents run back to back (each layer's last state of one call is
the next call's first); ``"carry"`` drops the state every ``FAULT_CHUNK``
tokens (a chunked form that loses what it carries between chunks).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the feature type is the program's; a program without it stops here, before
# 12 GB of weights are drawn for it (the one thing read of the program: a
# tuple of names, nothing of its models or ops)
from video_features_tpu.config import FEATURE_TYPES

if "jamba" not in FEATURE_TYPES:
    raise ImportError("this program has no --feature_type jamba")

# the catalog row's `config`, the keys the equations use
PUBLISHED = dict(
    vocab_size=65536, hidden_size=2560, num_hidden_layers=28, intermediate_size=8192,
    rms_norm_eps=1e-6, attn_layer_period=14, attn_layer_offset=7, num_attention_heads=20,
    num_key_value_heads=1, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
)
LAYERS = tuple(range(28))  # the whole model: `reduced` is empty
QUERY_BLOCK = 512  # scores of one block: 20 heads x 512 x 16,384 x 4 B = 0.67 GB
FAULT_CHUNK = 256
SCAN_UNROLL = 8

FEATURE_KEYS = ("jamba",)
EXACT_KEYS = ("timestamps_ms", "tokens")


def is_attention(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def weight_specs(cfg: dict = PUBLISHED, layers: Sequence[int] = LAYERS
                 ) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    hid, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // heads
    inner = cfg["mamba_expand"] * hid
    state, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg["vocab_size"], hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if is_attention(cfg, layer):
            spec[f"{pre}/q_proj"] = (hid, heads * d)
            spec[f"{pre}/k_proj"] = (hid, kv * d)
            spec[f"{pre}/v_proj"] = (hid, kv * d)
            spec[f"{pre}/o_proj"] = (heads * d, hid)
        else:
            spec[f"{pre}/in_proj"] = (hid, 2 * inner)
            spec[f"{pre}/conv/kernel"] = (cfg["mamba_d_conv"], inner)
            spec[f"{pre}/conv/bias"] = (inner,)
            spec[f"{pre}/x_proj"] = (inner, rank + 2 * state)
            spec[f"{pre}/dt_norm/scale"] = (rank,)
            spec[f"{pre}/b_norm/scale"] = (state,)
            spec[f"{pre}/c_norm/scale"] = (state,)
            spec[f"{pre}/dt_proj/kernel"] = (rank, inner)
            spec[f"{pre}/dt_proj/bias"] = (inner,)
            spec[f"{pre}/a_log/bias"] = (inner, state)
            spec[f"{pre}/d/scale"] = (inner,)
            spec[f"{pre}/out_proj"] = (inner, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        spec[f"{pre}/mlp/gate_proj"] = (hid, cfg["intermediate_size"])
        spec[f"{pre}/mlp/up_proj"] = (hid, cfg["intermediate_size"])
        spec[f"{pre}/mlp/down_proj"] = (cfg["intermediate_size"], hid)
    return {"jamba": spec}


# --- the equations ------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rounder(activations: str):
    """→ ``r(x)``: float32 ``x`` rounded to ``activations``, kept float32. A
    ``reduce_precision``, not a cast there and back: the TPU's compiler drops
    such a pair inside a fusion, and the value would stay unrounded."""
    info = jnp.finfo(jnp.dtype(activations))
    return lambda x: lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def causal_conv(u, w, b):
    """``y_t = Σ_j w[j] · u_{t-(K-1)+j} + b``, ``u`` zero before the first
    token: ``K`` shifted sums. u (tokens, channels), w (K, channels)."""
    taps, n = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + n] * w[j] for j in range(taps)) + b


def selective_scan(u, dt, b, c, a, d, first, fault: str = ""):
    """Step 5 token by token from the state ``first`` (channels, N). u, dt
    (tokens, channels); b, c (tokens, N); a (channels, N) → (y (tokens,
    channels), the last state)."""
    def one(state, row):
        ut, dtt, bt, ct, t = row
        if fault == "carry":
            state = jnp.where(t % FAULT_CHUNK == 0, 0.0, state)
        state = jnp.exp(dtt[:, None] * a) * state + (dtt * ut)[:, None] * bt[None, :]
        return state, jnp.sum(state * ct[None, :], axis=1) + d * ut

    last, y = lax.scan(one, first, (u, dt, b, c, jnp.arange(u.shape[0])), unroll=SCAN_UNROLL)
    return y, last


def mamba(cfg: dict, w: dict, x, first, fault: str = "", activations: str = "float32"):
    """→ (x after the mixer, the scan's last state)."""
    f32, r = jnp.float32, rounder(activations)
    eps = cfg["rms_norm_eps"]
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    state, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    h = r(rms_norm(x, w["attn_norm"]["scale"].astype(f32), eps))
    uz = r(h @ w["in_proj"].astype(f32))
    u, z = uz[:, :inner], uz[:, inner:]
    u = r(jax.nn.silu(causal_conv(u, w["conv"]["kernel"].astype(f32), w["conv"]["bias"])))
    dbc = u @ w["x_proj"].astype(f32)
    delta = r(rms_norm(dbc[:, :rank], w["dt_norm"]["scale"].astype(f32), eps))
    b = rms_norm(dbc[:, rank:rank + state], w["b_norm"]["scale"].astype(f32), eps)
    c = rms_norm(dbc[:, rank + state:], w["c_norm"]["scale"].astype(f32), eps)
    dt = jax.nn.softplus(delta @ w["dt_proj"]["kernel"].astype(f32) + w["dt_proj"]["bias"])
    a = -jnp.exp(w["a_log"]["bias"])
    y, last = selective_scan(u, dt, b, c, a, w["d"]["scale"].astype(f32), first, fault)
    return r(x + r(y * jax.nn.silu(z)) @ w["out_proj"].astype(f32)), last


def attention(cfg: dict, w: dict, x, activations: str = "float32"):
    f32, r = jnp.float32, rounder(activations)
    n, heads = x.shape[0], cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    h = r(rms_norm(x, w["attn_norm"]["scale"].astype(f32), cfg["rms_norm_eps"]))
    q = r((h @ w["q_proj"].astype(f32)) * d ** -0.5).reshape(n, heads, d)
    k = r(h @ w["k_proj"].astype(f32))  # the one key/value head every query head shares
    v = r(h @ w["v_proj"].astype(f32))
    block = min(QUERY_BLOCK, n)
    pad = -n % block
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    cols = jnp.arange(n)

    def one_block(args):
        qb, start = args
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        s = jnp.einsum("qhd,kd->hqk", qb, k)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->qhd", p, v)

    starts = jnp.arange(blocks.shape[0]) * block
    o = r(lax.map(one_block, (blocks, starts)).reshape(-1, heads, d)[:n])
    return r(x + o.reshape(n, heads * d) @ w["o_proj"].astype(f32))


def layer_forward(cfg: dict, attends: bool, w: dict, x, first, fault: str = "",
                  activations: str = "float32"):
    """→ (the layer's output, a Mamba layer's last state; ``first`` as it
    came for an attention layer). One compile a kind of layer and length."""
    f32, r = jnp.float32, rounder(activations)
    if attends:
        x, last = attention(cfg, w, x, activations), first
    else:
        x, last = mamba(cfg, w, x, first, fault, activations)
    h2 = r(rms_norm(x, w["mlp_norm"]["scale"].astype(f32), cfg["rms_norm_eps"]))
    m = w["mlp"]
    gate, up = r(h2 @ m["gate_proj"].astype(f32)), r(h2 @ m["up_proj"].astype(f32))
    return r(x + r(jax.nn.silu(gate) * up) @ m["down_proj"].astype(f32)), last


def round_weights(tree, dtype=jnp.bfloat16):
    """Every leaf on the device, rounded to ``dtype`` once; ``…/bias`` leaves
    (``A_log``, ``b_dt``, ``b_conv``) stay float32, as the program keeps them."""
    def leaf(path, a):
        keep = getattr(path[-1], "key", "") == "bias"
        return jnp.asarray(a, jnp.float32) if keep else jnp.asarray(a).astype(dtype)
    return jax.tree_util.tree_map_with_path(leaf, tree)


def make_forward(weights: dict, cfg: dict = PUBLISHED, precision: str = "highest",
                 fault: str = "", activations: str = "bfloat16"):
    """``weights``: the nested tree of ``weight_specs()``'s leaves, already
    rounded → ``features(ids, segment_ends) -> (segments, hidden) float32``.
    With ``fault="reset"`` each call starts every Mamba layer from the state
    the previous call ended in."""
    layers = sorted(int(l) for l in weights["layers"])
    step = jax.jit(layer_forward, static_argnums=(0, 1, 5, 6))
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    zero = jnp.zeros((inner, cfg["mamba_d_state"]), jnp.float32)
    carried = {l: zero for l in layers}

    def features(ids: np.ndarray, segment_ends: np.ndarray):
        with jax.default_matmul_precision(precision):
            x = weights["embed"]["embedding"][jnp.asarray(ids)].astype(jnp.float32)
            for l in layers:
                first = carried[l] if fault == "reset" else zero
                x, carried[l] = step(cfg_key(cfg), is_attention(cfg, l), weights["layers"][str(l)],
                                     x, first, fault, activations)
            x = rms_norm(x, weights["final_norm"]["scale"].astype(jnp.float32),
                         cfg["rms_norm_eps"])
            x = np.asarray(x, np.float64)
        starts = np.concatenate([[0], segment_ends[:-1]])
        return np.stack([x[a:b].mean(axis=0) for a, b in zip(starts, segment_ends)]
                        ).astype(np.float32)

    return features


class cfg_key(dict):
    """A configuration as a static argument of ``jit``: hashable by value."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def make_answer_fn(weights: Dict[str, dict], cfg: dict = PUBLISHED, activations: str = "bfloat16"):
    """→ ``answer(path) -> {key: array}``: what the ``.npy`` files of one
    transcript (``<stem>.tokens.npz``) must hold."""
    features = make_forward(round_weights(weights["jamba"]), cfg, activations=activations)

    def answer(path: str) -> Dict[str, np.ndarray]:
        with np.load(path) as z:
            ids, ends = z["ids"], z["segment_ends"]
            stamps = np.stack([z["start_ms"], z["end_ms"]], axis=1)
        return {"jamba": features(ids, ends), "timestamps_ms": stamps,
                "tokens": np.diff(ends, prepend=0).astype(np.int32)}

    return answer

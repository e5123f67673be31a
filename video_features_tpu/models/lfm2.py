"""LFM2-24B-A2B (LiquidAI, ``model_type`` ``lfm2_moe``) as a page program: the
layers this chip holds, over a page of packed token documents, down to one
feature row per timed segment.

Published shape (``config.json``; docs/models/lfm2.md has the equations):
hidden 2048, 40 layers whose mixer ``layer_types`` names — 30 gated short
convolutions and 10 attention layers (2, 6, …, 38). A conv layer:
``[B | C | u] = h W_in`` (2048 → 3 × 2048), ``v = B ⊙ u``, a causal depthwise
convolution of ``conv_L_cache`` (3) taps with no bias, ``y = (C ⊙ conv(v))
W_out``. An attention layer: 32 query heads over 8 key/value heads of 64,
each ``q`` and ``k`` head under its own RMSNorm, rope with θ 1e6 over all 64
dimensions (``rotate_half``), causal softmax scaled by 1/8. Layers 0–1
(``num_dense_layers``) feed forward through the dense SwiGLU unit of 11,776;
every later layer through 64 routed experts of 1,536 (sigmoid scores, the top
4 chosen by score plus the float32 ``expert_bias``, the chosen scores over
their sum plus 1e-6, factor 1) and NO shared expert. RMSNorm ``eps`` 1e-5, a
leaf ``…/scale`` the multiplier; nothing has a bias. What the checkpoint's
leaf names say this chip holds and the precisions are the stream's
(``models/text_layers.py``).

No token sees another document: the convolution reads zeros before a
document's first token (from the page's ``pos`` plane) and attention masks by
document. A feature extractor never decodes, so no convolution state or cache
outlives a page; the output head (the tied embedding) is never applied.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.segment_attention import segment_attention
from . import text_layers as tl
from .text_layers import Share, share_of  # noqa: F401 — the model's interface


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    intermediate_size: int = 11776
    norm_eps: float = 1e-5
    # as published: full_attention at layers 2, 6, …, 38, conv at the other 30
    layer_types: Tuple[str, ...] = (("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 9
                                    + ("full_attention", "conv"))
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    renorm_eps: float = 1e-6  # the chosen scores' sum gets it (the family's modelling code)

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == "full_attention"

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


PUBLISHED = Lfm2Config()


def rope_inv_freq(cfg: Lfm2Config) -> np.ndarray:
    """(head_dim/2,) inverse frequencies in float64 over the whole head."""
    d = cfg.head_dim
    return cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


# --- layers -----------------------------------------------------------------

def short_conv(cfg: Lfm2Config, p: dict, x, pos):
    hid = cfg.hidden_size
    with jax.named_scope("proj"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        bcu = tl.dot(h, p["w_in"]).astype(tl.DTYPE)  # [B | C | u]
    with jax.named_scope("core"):
        b, c, u = bcu[:, :hid], bcu[:, hid:2 * hid], bcu[:, 2 * hid:]
        v = (b.astype(jnp.float32) * u.astype(jnp.float32)).astype(tl.DTYPE)
        y = (c.astype(jnp.float32) * tl.causal_conv(v, p["conv"], pos)).astype(tl.DTYPE)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(y, p["w_out"])).astype(tl.DTYPE)


def attention(cfg: Lfm2Config, p: dict, x, doc, pos, block: int, interpret: bool = False):
    heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    tokens = x.shape[0]
    with jax.named_scope("qkv"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = jnp.split(tl.dot(h, p["wqkv"]).astype(tl.DTYPE),
                            np.cumsum([heads * d, kv * d]), axis=-1)
    with jax.named_scope("norm"):
        q = tl.rms_norm(q.reshape(tokens, heads, d), p["q_norm"], cfg.norm_eps)
        k = tl.rms_norm(k.reshape(tokens, kv, d), p["k_norm"], cfg.norm_eps)
    with jax.named_scope("rope"):
        inv_freq = rope_inv_freq(cfg)
        q = tl.apply_rope(q, pos, inv_freq, 1.0, scale=d ** -0.5).reshape(tokens, heads * d)
        k = tl.apply_rope(k, pos, inv_freq, 1.0).reshape(tokens, kv * d)
    with jax.named_scope("core"):
        o = segment_attention(q, k, v, doc, kv_heads=kv, head_dim=d, block=block,
                              interpret=interpret)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def route(cfg: Lfm2Config, p: dict, h):
    """Sigmoid scores, the expert bias for the choice only, the chosen scores
    over their sum plus ``renorm_eps``, factor 1."""
    return moe.route(h, p["router"], cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                     scoring="sigmoid", bias=p["router_bias"], eps=cfg.renorm_eps)


def forward(cfg: Lfm2Config, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body (``tl.page_forward``: the planes of ``page``,
    what it returns, the scopes). ``interpret``: the Pallas kernels in the
    interpreter (a backend that is not a TPU)."""
    def mixer(layer, p, x, doc, pos):
        if cfg.is_attention(layer):
            return attention(cfg, p, x, doc, pos, block, interpret)
        with jax.named_scope("short_conv"):
            return short_conv(cfg, p, x, pos)

    return tl.page_forward("lfm2", share, cfg.num_experts, cfg.is_dense, mixer,
                           functools.partial(route, cfg), cfg.norm_eps, page_rows, params,
                           page, interpret)


# --- checkpoint → the program's tree ------------------------------------------

def stack_checkpoint(cfg: Lfm2Config, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (``read(name)`` → host array) → the
    program's tree on the device, bfloat16 as each leaf arrives: an attention
    layer's ``q, k, v`` side by side (one product); the expert bias stays
    float32."""
    share = share_of(names)
    get, side_by_side = tl.leaf_reader(read)
    # the largest leaf first: it arrives as float32 beside its bfloat16 cast
    params = {"embed": get("embed/embedding"), "final_norm": get("final_norm/scale")}
    layers = params["layers"] = []
    for layer in share.layers:
        pre = f"layers/{layer}"
        p = {"attn_norm": get(f"{pre}/attn_norm/scale"), "mlp_norm": get(f"{pre}/mlp_norm/scale")}
        if cfg.is_attention(layer):
            p["wqkv"] = side_by_side(pre, ("q_proj", "k_proj", "v_proj"))
            p["q_norm"], p["k_norm"] = get(f"{pre}/q_norm/scale"), get(f"{pre}/k_norm/scale")
            p["wo"] = get(f"{pre}/o_proj")
        else:
            p["w_in"] = get(f"{pre}/in_proj")
            p["conv"] = get(f"{pre}/conv/kernel")
            p["w_out"] = get(f"{pre}/out_proj")
        dense = cfg.is_dense(layer)
        tl.stack_mlp(p, pre, dense, share.experts, get, side_by_side, shared=False)
        if not dense:
            p["router_bias"] = jnp.asarray(read(f"{pre}/choice/bias"), jnp.float32)
        layers.append(p)
    return params, share


def leaf_shapes(cfg: Lfm2Config, layers: Sequence[int], experts: Sequence[int]
                ) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every checkpoint leaf of a share (random weights for
    smoke runs and tests; a benchmark's reference states its own table)."""
    hid, heads, kv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg.vocab_size, hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if cfg.is_attention(layer):
            spec[f"{pre}/q_proj"] = (hid, heads * d)
            spec[f"{pre}/k_proj"] = spec[f"{pre}/v_proj"] = (hid, kv * d)
            spec[f"{pre}/q_norm/scale"] = spec[f"{pre}/k_norm/scale"] = (d,)
            spec[f"{pre}/o_proj"] = (heads * d, hid)
        else:
            spec[f"{pre}/in_proj"] = (hid, 3 * hid)
            spec[f"{pre}/conv/kernel"] = (cfg.conv_L_cache, hid)
            spec[f"{pre}/out_proj"] = (hid, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        dense = cfg.is_dense(layer)
        tl.mlp_leaf_shapes(spec, pre, hid, cfg.intermediate_size if dense else None,
                           cfg.num_experts, 0, cfg.moe_intermediate_size, experts)
        if not dense:
            spec[f"{pre}/choice/bias"] = (cfg.num_experts,)
    return spec


def random_checkpoint(cfg: Lfm2Config, layers: Sequence[int], experts: Sequence[int],
                      seed: int = 0) -> Dict[str, np.ndarray]:
    return tl.random_leaves(leaf_shapes(cfg, layers, experts), seed)

"""Qwen3-Next-80B-A3B (Qwen, ``model_type`` ``qwen3_next``) as a page program:
the layers this chip holds, over a page of packed token documents, down to one
feature row per timed segment.

Published shape (``config.json``; docs/models/qwen3_next.md has the equations):
hidden 2048; layer ``l`` is ``full_attention`` where ``(l + 1) % 4 == 0`` and
``linear_attention`` (Gated DeltaNet) otherwise. A Gated DeltaNet layer: 16 key
heads and 32 value heads of 128 (value head ``j`` belongs to key head ``j //
2``), a causal depthwise convolution of 4 taps over the 8,192 channels of ``q,
k, v``, per value head a 128 × 128 state under the gated delta rule
(``ops/gated_delta.py``), a gated per-head norm. A full layer: 16 query heads
over 2 key/value heads of 256, per-head RMSNorm on queries and keys, rope on
the first 64 of the 256, an element-wise sigmoid gate on the output. Every
layer sparse: 512 routed experts of width 512 (top 10 by softmax,
renormalised) plus one shared expert behind a per-token sigmoid gate.
Everything is without bias; RMSNorm ``eps`` 1e-6, a leaf ``…/scale`` the
multiplier itself. What the checkpoint's leaf names say this chip holds, the
share rule and the precisions are the stream's (``models/text_layers.py``).

Neither kind of layer lets a token see another document: the delta rule's
state restarts at a document's first token, the convolution reads zeros before
it (from the page's ``pos`` plane), and attention masks by document. A feature
extractor never decodes, so neither a state nor a key/value cache outlives a
page; the multi-token-prediction module and the output head are not held.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.gated_delta import CHUNK, gated_delta, gated_norm
from ..ops.segment_attention import segment_attention
from . import text_layers as tl
from .text_layers import Share, share_of  # noqa: F401 — the model's interface

# what a page counts beside the routing counters, in the order `forward`
# returns them (``extractors/token_pages.py`` names them in ``_pack_stats``)
PAGE_COUNTERS = ("gdn_chunks", "gdn_boundary_chunks")


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    # linear_attention layers (Gated DeltaNet)
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # full_attention layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    # the sparse unit, every layer
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512

    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    def is_dense(self, layer: int) -> bool:
        return False  # decoder_sparse_step 1, mlp_only_layers empty

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim


PUBLISHED = Qwen3NextConfig()


def rope_inv_freq(cfg: Qwen3NextConfig) -> np.ndarray:
    """(rot/2,) inverse frequencies in float64 over the head's first
    ``partial_rotary_factor`` dimensions; no scaling."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    return cfg.rope_theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)


# --- layers -----------------------------------------------------------------

def gated_delta_net(cfg: Qwen3NextConfig, p: dict, x, doc, pos, interpret: bool = False):
    kh, vh = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    mixed = 2 * cfg.key_width + cfg.value_width  # the columns the convolution mixes: q, k, v
    with jax.named_scope("proj"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        qkvz = tl.dot(h, p["wqkvz"]).astype(tl.DTYPE)
        b, a = jnp.split(tl.dot(h, p["wba"]), 2, axis=-1)  # float32: they make the decay
    with jax.named_scope("conv"):
        qkv = jax.nn.silu(tl.causal_conv(qkvz[:, :mixed], p["conv"], pos)).astype(tl.DTYPE)
    with jax.named_scope("gates"):
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    with jax.named_scope("core"):  # the unit rows of q and k are the kernel's
        o = gated_delta(qkv, g, beta, doc, key_heads=kh, interpret=interpret)
    with jax.named_scope("norm"):
        o = gated_norm(o, qkvz, p["gdn_norm"], heads=vh, gate_column=mixed,
                       eps=cfg.rms_norm_eps, interpret=interpret)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def full_attention(cfg: Qwen3NextConfig, p: dict, x, doc, pos, block: int,
                   interpret: bool = False):
    heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    tokens = x.shape[0]
    with jax.named_scope("qkv"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, gate, k, v = jnp.split(tl.dot(h, p["wqgkv"]).astype(tl.DTYPE),
                                  np.cumsum([heads * d, heads * d, kv * d]), axis=-1)
    with jax.named_scope("norm"):
        q = tl.rms_norm(q.reshape(tokens, heads, d), p["q_norm"], cfg.rms_norm_eps)
        k = tl.rms_norm(k.reshape(tokens, kv, d), p["k_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        inv_freq = rope_inv_freq(cfg)
        q = tl.apply_rope(q, pos, inv_freq, 1.0, scale=d ** -0.5).reshape(tokens, heads * d)
        k = tl.apply_rope(k, pos, inv_freq, 1.0).reshape(tokens, kv * d)
    with jax.named_scope("core"):
        o = segment_attention(q, k, v, doc, kv_heads=kv, head_dim=d, block=block,
                              interpret=interpret)
    with jax.named_scope("gate"):
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(tl.DTYPE)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def route(cfg: Qwen3NextConfig, p: dict, h):
    """Softmax over all experts, the published top-k renormalised, factor 1."""
    return moe.route(h, p["router"], cfg.num_experts_per_tok, 1.0)


def page_counters(cfg: Qwen3NextConfig, share: Share, doc):
    """int32 (2,): chunks the delta rule walks in this page (over the linear
    layers held) and those of them that hold a document's start or pads, where
    the chunk's masks do work."""
    chunks = doc.shape[0] // CHUNK
    before = jnp.concatenate([jnp.full((1,), -2, doc.dtype), doc[:-1]])
    boundary = jnp.any(((doc != before) | (doc < 0)).reshape(chunks, CHUNK), axis=1)
    linear = sum(1 for l in share.layers if not cfg.is_full(l))
    return linear * jnp.stack([jnp.asarray(chunks, jnp.int32), jnp.sum(boundary, dtype=jnp.int32)])


def forward(cfg: Qwen3NextConfig, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body (``tl.page_forward``: the planes of ``page``,
    what it returns, the scopes; :data:`PAGE_COUNTERS` after the routing
    totals). ``interpret``: the three Pallas kernels in the interpreter (a
    backend that is not a TPU)."""
    def attn(layer, p, x, doc, pos):
        if cfg.is_full(layer):
            return full_attention(cfg, p, x, doc, pos, block, interpret)
        with jax.named_scope("gdn"):
            return gated_delta_net(cfg, p, x, doc, pos, interpret)

    return tl.page_forward("qwen3_next", share, cfg.num_experts, cfg.is_dense, attn,
                           functools.partial(route, cfg), cfg.rms_norm_eps, page_rows, params,
                           page, interpret, page_counters(cfg, share, page[1]))


# --- checkpoint → the program's tree ------------------------------------------

def stack_checkpoint(cfg: Qwen3NextConfig, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (``read(name)`` → host array; a linear
    layer's projections as separate leaves, a full layer's ``q_proj`` a head at
    a time ``[256 query | 256 gate]``) → the program's tree on the device,
    bfloat16 as each leaf arrives, in the layouts the products want: ``wqkvz``
    and ``wba`` side by side, ``wqgkv`` every head's query, every head's gate,
    keys, values (one product, split on lane-row boundaries); ``A_log`` and
    ``dt_bias`` stay float32."""
    share = share_of(names)
    get, side_by_side = tl.leaf_reader(read)
    heads, d = cfg.num_attention_heads, cfg.head_dim
    # the largest leaf first: it arrives as float32 beside its bfloat16 cast
    params = {"embed": get("embed/embedding"), "final_norm": get("final_norm/scale")}
    layers = params["layers"] = []
    for layer in share.layers:
        pre = f"layers/{layer}"
        p = {"attn_norm": get(f"{pre}/attn_norm/scale"), "mlp_norm": get(f"{pre}/mlp_norm/scale")}
        if cfg.is_full(layer):
            per_head = get(f"{pre}/q_proj").reshape(cfg.hidden_size, heads, 2 * d)
            p["wqgkv"] = jnp.concatenate(
                [per_head[..., :d].reshape(-1, heads * d), per_head[..., d:].reshape(-1, heads * d),
                 get(f"{pre}/k_proj"), get(f"{pre}/v_proj")], axis=-1)
            p["q_norm"], p["k_norm"] = get(f"{pre}/q_norm/scale"), get(f"{pre}/k_norm/scale")
            p["wo"] = get(f"{pre}/o_proj")
        else:
            p["wqkvz"] = side_by_side(pre, ("q_proj", "k_proj", "v_proj", "z_proj"))
            p["wba"] = side_by_side(pre, ("b_proj", "a_proj"))
            p["conv"] = get(f"{pre}/conv")
            p["a_log"] = jnp.asarray(read(f"{pre}/a_log/bias"), jnp.float32)
            p["dt_bias"] = jnp.asarray(read(f"{pre}/dt/bias"), jnp.float32)
            p["gdn_norm"] = get(f"{pre}/gdn_norm/scale")
            p["wo"] = get(f"{pre}/out_proj")
        tl.stack_mlp(p, pre, False, share.experts, get, side_by_side,
                     gated_shared=f"{pre}/shared_gate" in names)
        layers.append(p)
    return params, share


def leaf_shapes(cfg: Qwen3NextConfig, layers: Sequence[int], experts: Sequence[int]
                ) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every checkpoint leaf of a share (random weights for
    smoke runs and tests; a benchmark's reference states its own table)."""
    hid, heads, kv, d = (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
    vh = cfg.linear_num_value_heads
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg.vocab_size, hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if cfg.is_full(layer):
            spec[f"{pre}/q_proj"] = (hid, heads * 2 * d)
            spec[f"{pre}/k_proj"] = spec[f"{pre}/v_proj"] = (hid, kv * d)
            spec[f"{pre}/q_norm/scale"] = spec[f"{pre}/k_norm/scale"] = (d,)
            spec[f"{pre}/o_proj"] = (heads * d, hid)
        else:
            spec[f"{pre}/q_proj"] = spec[f"{pre}/k_proj"] = (hid, cfg.key_width)
            spec[f"{pre}/v_proj"] = spec[f"{pre}/z_proj"] = (hid, cfg.value_width)
            spec[f"{pre}/b_proj"] = spec[f"{pre}/a_proj"] = (hid, vh)
            spec[f"{pre}/conv"] = (cfg.linear_conv_kernel_dim, 2 * cfg.key_width + cfg.value_width)
            spec[f"{pre}/dt/bias"] = spec[f"{pre}/a_log/bias"] = (vh,)
            spec[f"{pre}/gdn_norm/scale"] = (cfg.linear_value_head_dim,)
            spec[f"{pre}/out_proj"] = (cfg.value_width, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        tl.mlp_leaf_shapes(spec, pre, hid, None, cfg.num_experts,
                           cfg.shared_expert_intermediate_size, cfg.moe_intermediate_size, experts,
                           gated_shared=True)
    return spec


def random_checkpoint(cfg: Qwen3NextConfig, layers: Sequence[int], experts: Sequence[int],
                      seed: int = 0) -> Dict[str, np.ndarray]:
    return tl.random_leaves(leaf_shapes(cfg, layers, experts), seed)

"""Laguna-S-2.1 (poolside) as a page program: the layers this chip holds, over
a page of packed token documents, down to one feature row per timed segment.

Published shape (``config.json``; docs/models/laguna.md has the equations):
hidden 3072, 8 key/value heads of 128, 48 query heads in a ``full_attention``
layer and 72 in a ``sliding_attention`` layer (window 512), three sliding
layers after each full one, a per-head sigmoid output gate, YaRN rope on the
first half of each head in full layers and plain rope on the whole head in
sliding ones, layer 0 a dense gated MLP of width 12288, every later layer 256
routed experts of width 1024 (top 10, renormalised, times 2.5) plus one shared
expert. Everything is without bias; RMSNorm ``eps`` 1e-6.

The checkpoint says what this chip holds: ``layers/<l>/…`` names the layers,
``layers/<l>/experts/<e>/…`` the experts (a share of a stated deployment, as
expert parallelism gives one chip). The router keeps all its outputs and its
top-k; the part of the result that absent experts would add is left out and the
partial result goes on (``ops/moe.py``). Weights and activations are bfloat16,
products accumulate in float32; router, softmaxes, norm statistics and the
segment mean are float32.

Assumed where the config names a thing without defining it (A1–A3 of
``benchmark/configs/laguna_s21_bf16.json``): the gate is ``sigmoid(h W_g)`` from
the layer's normed input on the attention output, head by head; router scores
are a softmax over all experts; SiLU, no shared-expert gate, no query/key norm.
Rope pairs dimension ``i`` with ``i + rot/2`` (``rotate_half``), as the
published implementations of this config family do.
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
from .text_layers import Share, apply_rope, share_of  # noqa: F401 — the model's interface


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_key_value_heads: int = 8
    head_dim: int = 128
    heads_full: int = 48
    heads_sliding: int = 72
    full_every: int = 4            # layer l is full_attention iff l % 4 == 0
    mlp_only_layers: Tuple[int, ...] = (0,)
    sliding_window: int = 512
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    moe_routed_scaling_factor: float = 2.5
    # rope_parameters, by layer type
    full_rope_theta: float = 500000.0
    full_partial_rotary_factor: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    sliding_rope_theta: float = 10000.0

    def is_full(self, layer: int) -> bool:
        return layer % self.full_every == 0

    def heads(self, layer: int) -> int:
        return self.heads_full if self.is_full(layer) else self.heads_sliding

    def is_dense(self, layer: int) -> bool:
        return layer in self.mlp_only_layers


PUBLISHED = LagunaConfig()


# --- rope -------------------------------------------------------------------

def rope_inv_freq(cfg: LagunaConfig, full: bool) -> Tuple[np.ndarray, float]:
    """(rot/2,) inverse frequencies in float64 and the factor cos and sin are
    scaled by. Sliding layers: plain rope over the whole head. Full layers:
    YaRN over the first ``partial_rotary_factor`` of it (``tl.yarn_inv_freq``)."""
    if not full:
        rot = cfg.head_dim
        return cfg.sliding_rope_theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot), 1.0
    rot = int(cfg.head_dim * cfg.full_partial_rotary_factor)
    inv = tl.yarn_inv_freq(rot, cfg.full_rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    return inv, cfg.yarn_attention_factor


# --- layers -----------------------------------------------------------------

def attention(cfg: LagunaConfig, layer: int, p: dict, x, doc, pos, block: int,
              interpret: bool = False):
    heads, kv, d = cfg.heads(layer), cfg.num_key_value_heads, cfg.head_dim
    full = cfg.is_full(layer)
    tokens = x.shape[0]
    with jax.named_scope("qkv"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v, g = jnp.split(tl.dot(h, p["wqkvg"]).astype(tl.DTYPE),
                               np.cumsum([heads * d, kv * d, kv * d]), axis=-1)
    with jax.named_scope("rope"):
        inv_freq, factor = rope_inv_freq(cfg, full)
        q = apply_rope(q.reshape(tokens, heads, d), pos, inv_freq, factor,
                       scale=d ** -0.5).reshape(tokens, heads * d)
        k = apply_rope(k.reshape(tokens, kv, d), pos, inv_freq, factor).reshape(tokens, kv * d)
    with jax.named_scope("core"):
        o = segment_attention(q, k, v, doc, kv_heads=kv, head_dim=d, block=block,
                              window=None if full else cfg.sliding_window,
                              interpret=interpret)
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(g[:, :heads].astype(jnp.float32))
        o = (o.reshape(tokens, heads, d).astype(jnp.float32) * gate[..., None]
             ).astype(tl.DTYPE).reshape(tokens, heads * d)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def route(cfg: LagunaConfig, p: dict, h):
    """Softmax over all experts, the published top-k and scaling factor."""
    return moe.route(h, p["router"], cfg.num_experts_per_tok, cfg.moe_routed_scaling_factor)


def forward(cfg: LagunaConfig, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body (``tl.page_forward``: the planes of ``page``,
    what it returns, the scopes). ``interpret``: the two Pallas kernels in
    the interpreter (a backend that is not a TPU)."""
    def attn(layer, p, x, doc, pos):
        return attention(cfg, layer, p, x, doc, pos, block, interpret)

    return tl.page_forward("laguna", share, cfg.num_experts, cfg.is_dense, attn,
                           functools.partial(route, cfg), cfg.rms_norm_eps, page_rows, params,
                           page, interpret)


# --- checkpoint → the program's tree ------------------------------------------

def stack_checkpoint(cfg: LagunaConfig, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (one matrix per projection and expert,
    ``read(name)`` → host array) → the program's tree on the device, cast to
    bfloat16 once as each leaf arrives and stacked there: ``wqkvg`` (query, key,
    value and gate projections side by side, the gate padded to whole lanes),
    ``w_gate_up`` pairs, experts stacked on a leading axis in ``share.experts``
    order."""
    share = share_of(names)
    get, side_by_side = tl.leaf_reader(read)
    layers = []
    for layer in share.layers:
        pre = f"layers/{layer}"
        g = get(f"{pre}/g_proj")
        p = {"attn_norm": get(f"{pre}/attn_norm/scale"),
             "mlp_norm": get(f"{pre}/mlp_norm/scale"),
             "wqkvg": jnp.concatenate(
                 [get(f"{pre}/q_proj"), get(f"{pre}/k_proj"), get(f"{pre}/v_proj"),
                  jnp.pad(g, ((0, 0), (0, -g.shape[1] % 128)))], axis=-1),
             "wo": get(f"{pre}/o_proj")}
        tl.stack_mlp(p, pre, cfg.is_dense(layer), share.experts, get, side_by_side)
        layers.append(p)
    params = {"embed": get("embed/embedding"), "final_norm": get("final_norm/scale"),
              "layers": layers}
    return params, share


def leaf_shapes(cfg: LagunaConfig, layers: Sequence[int], experts: Sequence[int]
                ) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every checkpoint leaf of a share (random weights for
    smoke runs and tests; a benchmark's reference states its own table)."""
    hid, kvw = cfg.hidden_size, cfg.num_key_value_heads * cfg.head_dim
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg.vocab_size, hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre, qw = f"layers/{layer}", cfg.heads(layer) * cfg.head_dim
        spec[f"{pre}/attn_norm/scale"] = spec[f"{pre}/mlp_norm/scale"] = (hid,)
        spec[f"{pre}/q_proj"], spec[f"{pre}/o_proj"] = (hid, qw), (qw, hid)
        spec[f"{pre}/k_proj"] = spec[f"{pre}/v_proj"] = (hid, kvw)
        spec[f"{pre}/g_proj"] = (hid, cfg.heads(layer))
        tl.mlp_leaf_shapes(spec, pre, hid, cfg.intermediate_size if cfg.is_dense(layer) else None,
                           cfg.num_experts, cfg.shared_expert_intermediate_size,
                           cfg.moe_intermediate_size, experts)
    return spec


def random_checkpoint(cfg: LagunaConfig, layers: Sequence[int], experts: Sequence[int],
                      seed: int = 0) -> Dict[str, np.ndarray]:
    return tl.random_leaves(leaf_shapes(cfg, layers, experts), seed)

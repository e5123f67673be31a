"""sarvam-105b (sarvamai, ``model_type`` ``sarvam_mla``) as a page program: the
layers this chip holds, over a page of packed token documents, down to one
feature row per timed segment.

Published shape (``config.json``; docs/models/sarvam.md has the equations):
hidden 4096, 64 heads of latent attention (MLA) — a query of 192 per head, 128
that meet the head's own key and 64 rotated ones that meet ONE rotated key a
token shared by all heads; keys and values of 128 per head projected up from a
normed 512-wide latent — scores scaled by ``192^-0.5`` times YaRN's
``mscale²``, layer 0 a dense gated MLP of width 16384, every later layer 128
routed experts of width 2048 (top 8 by sigmoid score plus a per-expert bias
that moves the CHOICE only, chosen scores renormalised, times 2.5) plus one
shared expert. Everything is without bias but the router's choice; RMSNorm
``eps`` 1e-6. What the checkpoint's leaf names say this chip holds, the share
rule and the precisions are the stream's (``models/text_layers.py``).

A feature extractor never decodes, so the 576-wide latent cache does no work
here: this is MLA's prefill form, through the one attention kernel
(``ops/segment_attention.py``, its shared-key term).

Assumed where the config names a thing without defining it (A1–A5 of
``benchmark/configs/sarvam_105b_bf16.json``): a plain query projection (no
``q_lora_rank``); ``use_qk_norm`` is the family's latent norm, on the 512-wide
latent only; router by sigmoid, bias for the choice only, no expert groups;
rope pairs dimension ``2i`` with ``2i + 1`` of the 64 — a statement about the
checkpoint's column order, which ``stack_checkpoint`` undoes once so that the
program rotates halves; SiLU, no shared-expert gate.
"""

from __future__ import annotations

import functools
import math
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
class SarvamConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_attention_heads: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 10000.0
    # rope_scaling (deepseek_yarn)
    yarn_factor: float = 40.0
    yarn_original_max_position_embeddings: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def mscale(self, m: float) -> float:
        return 0.1 * m * math.log(self.yarn_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        return self.q_head_dim ** -0.5 * self.mscale(self.yarn_mscale_all_dim) ** 2


PUBLISHED = SarvamConfig()


def rope_inv_freq(cfg: SarvamConfig) -> Tuple[np.ndarray, float]:
    """(rot/2,) YaRN inverse frequencies in float64 over the head's 64 rotated
    dimensions, and the factor cos and sin are scaled by (1 as published)."""
    inv = tl.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    return inv, cfg.mscale(cfg.yarn_mscale) / cfg.mscale(cfg.yarn_mscale_all_dim)


def attention(cfg: SarvamConfig, p: dict, x, doc, pos, block: int, interpret: bool = False):
    heads, dn, dr, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.kv_lora_rank)
    tokens = x.shape[0]
    with jax.named_scope("q"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        qn, qr = jnp.split((tl.dot(h, p["wq"]) * cfg.softmax_scale).astype(tl.DTYPE),
                           [heads * dn], axis=-1)
    with jax.named_scope("latent"):
        latent, kr = jnp.split(tl.dot(h, p["wkva"]), [rank], axis=-1)
        c = tl.rms_norm(latent, p["kv_norm"], cfg.rms_norm_eps)
    with jax.named_scope("up"):
        kn, v = jnp.split(tl.dot(c, p["wkvb"]).astype(tl.DTYPE), 2, axis=-1)
    with jax.named_scope("rope"):
        inv_freq, factor = rope_inv_freq(cfg)
        qr = tl.apply_rope(qr.reshape(tokens, heads, dr), pos, inv_freq, factor
                           ).reshape(tokens, heads * dr)
        kr = tl.apply_rope(kr.astype(tl.DTYPE)[:, None, :], pos, inv_freq, factor)[:, 0]
    with jax.named_scope("core"):
        o = segment_attention(qn, kn, v, doc, kv_heads=heads, head_dim=dn, block=block,
                              interpret=interpret, q_shared=qr, k_shared=kr)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def route(cfg: SarvamConfig, p: dict, h):
    """Sigmoid scores, the expert bias for the choice only, the published
    top-k and scaling factor."""
    return moe.route(h, p["router"], cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                     scoring="sigmoid", bias=p["router_bias"])


def forward(cfg: SarvamConfig, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body (``tl.page_forward``: the planes of ``page``,
    what it returns, the scopes). ``interpret``: the two Pallas kernels in
    the interpreter (a backend that is not a TPU)."""
    def attn(_layer, p, x, doc, pos):
        return attention(cfg, p, x, doc, pos, block, interpret)

    return tl.page_forward("sarvam", share, cfg.num_experts, cfg.is_dense, attn,
                           functools.partial(route, cfg), cfg.rms_norm_eps, page_rows, params,
                           page, interpret)


# --- checkpoint → the program's tree ------------------------------------------

def stack_checkpoint(cfg: SarvamConfig, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (published layouts: ``q_proj`` a head at a
    time ``[128 | 64 rotated]``, ``kv_a_proj`` ``[512 latent | 64 rotated]``,
    ``kv_b_proj`` a head at a time ``[128 key | 128 value]``; ``read(name)`` →
    host array) → the program's tree on the device, bfloat16 as each leaf
    arrives, in the layouts the products want: ``wq`` every head's 128 then
    every head's 64, ``wkvb`` every head's key then every head's value (each
    one product, split on a lane-row boundary), the rotated columns regrouped
    from pairs ``(2i, 2i + 1)`` to halves; the expert bias stays float32."""
    if cfg.qk_nope_head_dim != cfg.v_head_dim:
        raise ValueError("the attention kernel takes one width for a head's own key and its "
                         f"value: {cfg.qk_nope_head_dim} and {cfg.v_head_dim}")
    share = share_of(names)
    get, side_by_side = tl.leaf_reader(read)
    heads, dn, dr, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.kv_lora_rank)
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def by_kind(w, first: int):
        """(in, heads * (first + rest)) a head at a time → (in, heads * first)
        beside (in, heads * rest)."""
        per_head = w.reshape(w.shape[0], heads, -1)
        return per_head[..., :first], per_head[..., first:]

    # the largest leaf first: it arrives as float32 (4.3 GB) beside its
    # bfloat16 cast, and last it stood on the whole tree (11.3 GB at the peak)
    params = {"embed": get("embed/embedding"), "final_norm": get("final_norm/scale")}
    layers = params["layers"] = []
    for layer in share.layers:
        pre = f"layers/{layer}"
        q_own, q_rot = by_kind(get(f"{pre}/q_proj"), dn)
        k_own, value = by_kind(get(f"{pre}/kv_b_proj"), dn)
        kva = get(f"{pre}/kv_a_proj")
        p = {"attn_norm": get(f"{pre}/attn_norm/scale"),
             "kv_norm": get(f"{pre}/kv_norm/scale"),
             "mlp_norm": get(f"{pre}/mlp_norm/scale"),
             "wq": jnp.concatenate([q_own.reshape(-1, heads * dn),
                                    q_rot[..., halves].reshape(-1, heads * dr)], axis=-1),
             "wkva": jnp.concatenate([kva[:, :rank], kva[:, rank:][:, halves]], axis=-1),
             "wkvb": jnp.concatenate([k_own.reshape(rank, -1), value.reshape(rank, -1)], axis=-1),
             "wo": get(f"{pre}/o_proj")}
        tl.stack_mlp(p, pre, cfg.is_dense(layer), share.experts, get, side_by_side)
        if not cfg.is_dense(layer):
            p["router_bias"] = jnp.asarray(read(f"{pre}/choice/bias"), jnp.float32)
        layers.append(p)
    return params, share


def leaf_shapes(cfg: SarvamConfig, layers: Sequence[int], experts: Sequence[int]
                ) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every checkpoint leaf of a share (random weights for
    smoke runs and tests; a benchmark's reference states its own table)."""
    hid, heads, rank = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg.vocab_size, hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = spec[f"{pre}/mlp_norm/scale"] = (hid,)
        spec[f"{pre}/q_proj"] = (hid, heads * cfg.q_head_dim)
        spec[f"{pre}/kv_a_proj"] = (hid, rank + cfg.qk_rope_head_dim)
        spec[f"{pre}/kv_norm/scale"] = (rank,)
        spec[f"{pre}/kv_b_proj"] = (rank, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        spec[f"{pre}/o_proj"] = (heads * cfg.v_head_dim, hid)
        dense = cfg.is_dense(layer)
        tl.mlp_leaf_shapes(spec, pre, hid, cfg.intermediate_size if dense else None,
                           cfg.num_experts, cfg.moe_intermediate_size * cfg.num_shared_experts,
                           cfg.moe_intermediate_size, experts)
        if not dense:
            spec[f"{pre}/choice/bias"] = (cfg.num_experts,)
    return spec


def random_checkpoint(cfg: SarvamConfig, layers: Sequence[int], experts: Sequence[int],
                      seed: int = 0) -> Dict[str, np.ndarray]:
    return tl.random_leaves(leaf_shapes(cfg, layers, experts), seed)

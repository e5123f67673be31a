"""AI21-Jamba2-3B (AI21, ``model_type`` ``jamba``) as a page program: the whole
28-layer model over a page of packed token documents, down to one feature row
per timed segment.

Published shape (``config.json``; docs/models/jamba.md has the equations):
hidden 2560; layer ``l`` is attention where ``l % attn_layer_period (14) ==
attn_layer_offset (7)`` (layers 7 and 21) and a Mamba-1 mixer otherwise. A
Mamba layer: ``d_inner = expand · hidden`` (5120) channels, an in-projection to
``[u, z]``, a causal depthwise convolution of 4 taps with a bias, ``[δ, B, C] =
u W_x`` (160, 16, 16) each under its own RMSNorm, ``Δ = softplus(δ W_dt +
b_dt)``, the selective scan with ``A = −exp(A_log)`` of ``(5120, 16)`` and a
skip ``D`` (``ops/selective_scan.py``), ``y ⊙ silu(z)`` and an out-projection.
An attention layer: 20 query heads over ONE key/value head of 128, causal
softmax of ``q kᵀ / √128``, no positional encoding at all (the Mamba layers
carry position). Every layer's feed-forward is the dense SwiGLU unit of 8192
(``num_experts`` 1: no layer is sparse). RMSNorm ``eps`` 1e-6, a leaf
``…/scale`` the multiplier as published; no bias but the convolution's and
``dt_proj``'s. What the checkpoint's leaf names say this chip holds and the
precisions are the stream's (``models/text_layers.py``).

No token sees another document: the scan's state restarts at a document's first
token and the convolution reads zeros before it (both from the page's ``pos``
plane), and attention masks by document. A feature extractor never decodes, so
no state outlives a page; the output head (the tied embedding) is never
applied.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.segment_attention import segment_attention
from ..ops.selective_scan import selective_scan
from . import text_layers as tl
from .text_layers import Share, share_of  # noqa: F401 — the model's interface


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    intermediate_size: int = 8192
    rms_norm_eps: float = 1e-6
    # attention layers
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    # Mamba layers
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    # one expert: every layer's feed-forward is the dense unit
    num_experts: int = 1

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    def is_dense(self, layer: int) -> bool:
        return True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.hidden_size


PUBLISHED = JambaConfig()


# --- layers -----------------------------------------------------------------

def mamba(cfg: JambaConfig, p: dict, x, pos, interpret: bool = False):
    inner, state, rank = cfg.inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("proj"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        uz = tl.dot(h, p["w_in"]).astype(tl.DTYPE)  # [u | z]: z stays in place for the scan
    with jax.named_scope("conv"):
        u = jax.nn.silu(tl.causal_conv(uz[:, :inner], p["conv"], pos, p["conv_bias"])).astype(tl.DTYPE)
    with jax.named_scope("ssm_params"):
        dbc = tl.dot(u, p["w_x"])
        delta = tl.rms_norm(dbc[:, :rank], p["dt_norm"], cfg.rms_norm_eps)
        b = tl.rms_norm(dbc[:, rank:rank + state], p["b_norm"], cfg.rms_norm_eps, jnp.float32)
        c = tl.rms_norm(dbc[:, rank + state:], p["c_norm"], cfg.rms_norm_eps, jnp.float32)
        dt = jax.nn.softplus(tl.dot(delta, p["w_dt"]) + p["dt_bias"])
        a = -jnp.exp(p["a_log"])
    with jax.named_scope("scan"):
        y = selective_scan(u, dt, b, c, a, p["d"], uz, pos, gate_column=inner, interpret=interpret)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(y, p["w_out"])).astype(tl.DTYPE)


def attention(cfg: JambaConfig, p: dict, x, doc, block: int, interpret: bool = False):
    heads, d = cfg.num_attention_heads, cfg.head_dim
    kv = cfg.num_key_value_heads
    with jax.named_scope("qkv"):
        h = tl.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        qkv = tl.dot(h, p["wqkv"])
        q = (qkv[:, :heads * d] * d ** -0.5).astype(tl.DTYPE)  # scaled once, in float32
        k = qkv[:, heads * d:(heads + kv) * d].astype(tl.DTYPE)
        v = qkv[:, (heads + kv) * d:].astype(tl.DTYPE)
    with jax.named_scope("core"):  # no rope: the Mamba layers carry position
        o = segment_attention(q, k, v, doc, kv_heads=kv, head_dim=d, block=block,
                              interpret=interpret)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + tl.dot(o, p["wo"])).astype(tl.DTYPE)


def forward(cfg: JambaConfig, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body (``tl.page_forward``: the planes of ``page``,
    what it returns, the scopes; every layer's feed-forward the dense unit, so
    the routing counters read 0). ``interpret``: the two Pallas kernels in the
    interpreter (a backend that is not a TPU)."""
    def mixer(layer, p, x, doc, pos):
        if cfg.is_attention(layer):
            return attention(cfg, p, x, doc, block, interpret)
        with jax.named_scope("mamba"):
            return mamba(cfg, p, x, pos, interpret)

    return tl.page_forward("jamba", share, cfg.num_experts, cfg.is_dense, mixer, None,
                           cfg.rms_norm_eps, page_rows, params, page, interpret)


# --- checkpoint → the program's tree ------------------------------------------

def stack_checkpoint(cfg: JambaConfig, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (``read(name)`` → host array) → the
    program's tree on the device, bfloat16 as each leaf arrives: an attention
    layer's ``q, k, v`` side by side (one product); a Mamba layer's ``A_log``
    transposed to ``(state, channels)``, the scan's layout; ``A_log`` and the
    two biases (``…/bias``) stay float32."""
    share = share_of(names)
    get, side_by_side = tl.leaf_reader(read)

    def f32(name):
        return jnp.asarray(read(name), jnp.float32)

    # the largest leaf first: it arrives as float32 beside its bfloat16 cast
    params = {"embed": get("embed/embedding"), "final_norm": get("final_norm/scale")}
    layers = params["layers"] = []
    for layer in share.layers:
        pre = f"layers/{layer}"
        p = {"attn_norm": get(f"{pre}/attn_norm/scale"), "mlp_norm": get(f"{pre}/mlp_norm/scale")}
        if cfg.is_attention(layer):
            p["wqkv"] = side_by_side(pre, ("q_proj", "k_proj", "v_proj"))
            p["wo"] = get(f"{pre}/o_proj")
        else:
            p["w_in"] = get(f"{pre}/in_proj")
            p["conv"] = get(f"{pre}/conv/kernel")
            p["conv_bias"] = f32(f"{pre}/conv/bias")
            p["w_x"] = get(f"{pre}/x_proj")
            p["dt_norm"], p["b_norm"], p["c_norm"] = (
                get(f"{pre}/{n}_norm/scale") for n in ("dt", "b", "c"))
            p["w_dt"] = get(f"{pre}/dt_proj/kernel")
            p["dt_bias"] = f32(f"{pre}/dt_proj/bias")
            p["a_log"] = f32(f"{pre}/a_log/bias").T
            p["d"] = get(f"{pre}/d/scale")
            p["w_out"] = get(f"{pre}/out_proj")
        tl.stack_mlp(p, pre, True, (), get, side_by_side)
        layers.append(p)
    return params, share


def leaf_shapes(cfg: JambaConfig, layers: Sequence[int]) -> Dict[str, Tuple[int, ...]]:
    """Name and shape of every checkpoint leaf of the layers held (random
    weights for smoke runs and tests; a benchmark's reference states its own
    table)."""
    hid, d = cfg.hidden_size, cfg.head_dim
    inner, state, rank = cfg.inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    spec: Dict[str, Tuple[int, ...]] = {"embed/embedding": (cfg.vocab_size, hid),
                                        "final_norm/scale": (hid,)}
    for layer in layers:
        pre = f"layers/{layer}"
        spec[f"{pre}/attn_norm/scale"] = (hid,)
        if cfg.is_attention(layer):
            spec[f"{pre}/q_proj"] = (hid, cfg.num_attention_heads * d)
            spec[f"{pre}/k_proj"] = spec[f"{pre}/v_proj"] = (hid, cfg.num_key_value_heads * d)
            spec[f"{pre}/o_proj"] = (cfg.num_attention_heads * d, hid)
        else:
            spec[f"{pre}/in_proj"] = (hid, 2 * inner)
            spec[f"{pre}/conv/kernel"] = (cfg.mamba_d_conv, inner)
            spec[f"{pre}/conv/bias"] = (inner,)
            spec[f"{pre}/x_proj"] = (inner, rank + 2 * state)
            spec[f"{pre}/dt_norm/scale"] = (rank,)
            spec[f"{pre}/b_norm/scale"] = spec[f"{pre}/c_norm/scale"] = (state,)
            spec[f"{pre}/dt_proj/kernel"] = (rank, inner)
            spec[f"{pre}/dt_proj/bias"] = (inner,)
            spec[f"{pre}/a_log/bias"] = (inner, state)
            spec[f"{pre}/d/scale"] = (inner,)
            spec[f"{pre}/out_proj"] = (inner, hid)
        spec[f"{pre}/mlp_norm/scale"] = (hid,)
        tl.mlp_leaf_shapes(spec, pre, hid, cfg.intermediate_size, 0, 0, 0, ())
    return spec


def random_checkpoint(cfg: JambaConfig, layers: Sequence[int], experts: Sequence[int] = (),
                      seed: int = 0) -> Dict[str, np.ndarray]:
    return tl.random_leaves(leaf_shapes(cfg, layers), seed)

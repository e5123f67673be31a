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

import math
from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from ..ops.segment_attention import segment_attention

DTYPE = jnp.bfloat16


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


@dataclass(frozen=True)
class Share:
    """What the checkpoint holds: layer ids in order, expert ids in the order
    their weights are stacked."""
    layers: Tuple[int, ...]
    experts: Tuple[int, ...]


# --- rope -------------------------------------------------------------------

def rope_inv_freq(cfg: LagunaConfig, full: bool) -> Tuple[np.ndarray, float]:
    """(rot/2,) inverse frequencies in float64 and the factor cos and sin are
    scaled by. Sliding layers: plain rope over the whole head. Full layers:
    YaRN over the first ``partial_rotary_factor`` of it — interpolated
    (``/ factor``) frequencies blended into the unscaled ones by a linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times in
    the original context."""
    if not full:
        rot = cfg.head_dim
        return cfg.sliding_rope_theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot), 1.0
    rot = int(cfg.head_dim * cfg.full_partial_rotary_factor)
    base, orig = cfg.full_rope_theta, cfg.yarn_original_max_position_embeddings
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    inv = (1.0 / (cfg.yarn_factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv, cfg.yarn_attention_factor


def apply_rope(x, pos, inv_freq: np.ndarray, factor: float, scale: float = 1.0):
    """(tokens, heads, head_dim) → the same, its first ``2 * len(inv_freq)``
    dimensions rotated by ``pos`` (float32 inside), all of it times ``scale``."""
    half = len(inv_freq)
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(angle) * (factor * scale))[:, None, :]
    sin = (jnp.sin(angle) * (factor * scale))[:, None, :]
    xf = x.astype(jnp.float32)
    a, b, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest * scale],
                           axis=-1).astype(x.dtype)


# --- layers -----------------------------------------------------------------

def rms_norm(x, scale, eps: float, out_dtype=None):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(out_dtype or DTYPE)


def dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def gated_mlp(h, w_gate_up, w_down):
    """``down(silu(gate(h)) · up(h))``; gate and up are one product."""
    gate, up = jnp.split(dot(h, w_gate_up).astype(h.dtype), 2, axis=-1)
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return dot(act.astype(h.dtype), w_down)


def attention(cfg: LagunaConfig, layer: int, p: dict, x, doc, pos, block: int,
              interpret: bool = False):
    heads, kv, d = cfg.heads(layer), cfg.num_key_value_heads, cfg.head_dim
    full = cfg.is_full(layer)
    tokens = x.shape[0]
    with jax.named_scope("qkv"):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v, g = jnp.split(dot(h, p["wqkvg"]).astype(DTYPE),
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
             ).astype(DTYPE).reshape(tokens, heads * d)
    with jax.named_scope("out"):
        return (x.astype(jnp.float32) + dot(o, p["wo"])).astype(DTYPE)


def expert_layer(cfg: LagunaConfig, p: dict, h, valid, slot_of, num_held: int,
                 interpret: bool = False):
    """→ (routed + shared, float32), (routed_total, routed_held, rows per held expert)."""
    with jax.named_scope("route"):
        weights, experts = moe.route(h, p["router"], cfg.num_experts_per_tok,
                                     cfg.moe_routed_scaling_factor)
    with jax.named_scope("dispatch"):
        d = moe.dispatch(experts, valid, slot_of, num_held)
        rows = lax.optimization_barrier(h)[d.token_of_row]
    with jax.named_scope("experts"):
        gate, up = jnp.split(moe.grouped_matmul(rows, p["experts_gate_up"], d.group_sizes,
                                               interpret), 2, axis=-1)
        act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(DTYPE)
        out = moe.grouped_matmul(act, p["experts_down"], d.group_sizes, interpret)
    with jax.named_scope("shared"):
        shared = gated_mlp(h, p["shared_gate_up"], p["shared_down"])
    with jax.named_scope("combine"):
        y = moe.combine(out, weights, d) + shared
    routed_total = jnp.sum(valid).astype(jnp.int32) * cfg.num_experts_per_tok
    return y, (routed_total, jnp.sum(d.group_sizes), d.group_sizes)


def segment_mean(x, seg, page_rows: int):
    """(tokens, width) float32 → (page_rows, width): the mean over each
    segment's tokens; a row with no token is zero. ``seg`` is -1 on pads."""
    onehot = (seg[None, :] == jnp.arange(page_rows, dtype=jnp.int32)[:, None])
    # float32 sums on bfloat16 products: the 0/1 matrix is exact in bfloat16
    # and three bfloat16 parts hold all of a float32
    sums, rest = jnp.zeros((page_rows, x.shape[1]), jnp.float32), x
    for _ in range(3):
        part = rest.astype(DTYPE)
        sums = sums + dot(onehot.astype(DTYPE), part)
        rest = rest - part.astype(jnp.float32)
    counts = jnp.sum(onehot, axis=1, dtype=jnp.int32)
    return sums / jnp.maximum(counts, 1)[:, None].astype(jnp.float32)


def forward(cfg: LagunaConfig, share: Share, page_rows: int, block: int, params: dict, page,
            interpret: bool = False):
    """The page program's body. ``page``: int32 (4, page_tokens) — token id,
    document index in the page (-1 on pads), position in its document, row of
    its segment in the page's table (-1 on pads). → ((page_rows, hidden)
    float32 segment features, int32 counters: routed_total, routed_held, then
    rows per held expert for every sparse layer). ``interpret``: the two Pallas
    kernels in the interpreter (a backend that is not a TPU)."""
    ids, doc, pos, seg = page[0], page[1], page[2], page[3]
    valid = doc >= 0
    slot_of = np.full((cfg.num_experts,), -1, np.int32)
    slot_of[list(share.experts)] = np.arange(len(share.experts), dtype=np.int32)
    slot_of = jnp.asarray(slot_of, jnp.int32)
    with jax.named_scope("laguna/embed"):
        x = params["embed"][ids]
    counters = []
    for p, layer in zip(params["layers"], share.layers):
        with jax.named_scope(f"laguna/L{layer}/attn"):
            x = attention(cfg, layer, p, x, doc, pos, block, interpret)
        with jax.named_scope(f"laguna/L{layer}/{'mlp' if cfg.is_dense(layer) else 'moe'}"):
            with jax.named_scope("norm"):
                h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            if cfg.is_dense(layer):
                y = gated_mlp(h, p["w_gate_up"], p["w_down"])
            else:
                y, counts = expert_layer(cfg, p, h, valid, slot_of, len(share.experts),
                                         interpret)
                counters.append(counts)
            x = (x.astype(jnp.float32) + y).astype(DTYPE)
    with jax.named_scope("laguna/pool"):
        rows = segment_mean(rms_norm(x, params["final_norm"], cfg.rms_norm_eps, jnp.float32),
                            seg, page_rows)
    zero = jnp.zeros((), jnp.int32)
    totals = [sum((c[i] for c in counters), zero) for i in (0, 1)]
    return rows, jnp.concatenate([jnp.stack(totals)] + [c[2] for c in counters])


# --- checkpoint → the program's tree ------------------------------------------

def share_of(names: Sequence[str]) -> Share:
    """Which layers and experts a checkpoint's leaf names hold; every sparse
    layer must hold the same experts."""
    layers = sorted({int(n.split("/")[1]) for n in names if n.startswith("layers/")})
    per_layer = {}
    for n in names:
        parts = n.split("/")
        if len(parts) > 3 and parts[0] == "layers" and parts[2] == "experts":
            per_layer.setdefault(int(parts[1]), set()).add(int(parts[3]))
    shares = {tuple(sorted(s)) for s in per_layer.values()}
    if len(shares) > 1:
        raise ValueError(f"layers hold different experts: {sorted(shares)[:2]} …")
    return Share(tuple(layers), shares.pop() if shares else ())


def stack_checkpoint(cfg: LagunaConfig, names: Sequence[str], read) -> Tuple[dict, Share]:
    """The checkpoint's flat leaves (one matrix per projection and expert,
    ``read(name)`` → host array) → the program's tree on the device, cast to
    bfloat16 once as each leaf arrives and stacked there: ``wqkvg`` (query, key,
    value and gate projections side by side, the gate padded to whole lanes),
    ``w_gate_up`` pairs, experts stacked on a leading axis in ``share.experts``
    order."""
    share = share_of(names)
    cast = jax.jit(lambda a: a.astype(DTYPE))

    def get(name):
        return cast(read(name))

    def side_by_side(prefix, leaves):
        return jnp.concatenate([get(f"{prefix}/{leaf}") for leaf in leaves], axis=-1)

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
        if cfg.is_dense(layer):
            p["w_gate_up"] = side_by_side(f"{pre}/mlp", ("gate_proj", "up_proj"))
            p["w_down"] = get(f"{pre}/mlp/down_proj")
        else:
            p["router"] = get(f"{pre}/router")
            p["shared_gate_up"] = side_by_side(f"{pre}/shared", ("gate_proj", "up_proj"))
            p["shared_down"] = get(f"{pre}/shared/down_proj")
            p["experts_gate_up"] = jnp.stack(
                [side_by_side(f"{pre}/experts/{e}", ("gate_proj", "up_proj"))
                 for e in share.experts])
            p["experts_down"] = jnp.stack(
                [get(f"{pre}/experts/{e}/down_proj") for e in share.experts])
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

    def mlp(prefix, width):
        spec[f"{prefix}/gate_proj"] = spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    for layer in layers:
        pre, qw = f"layers/{layer}", cfg.heads(layer) * cfg.head_dim
        spec[f"{pre}/attn_norm/scale"] = spec[f"{pre}/mlp_norm/scale"] = (hid,)
        spec[f"{pre}/q_proj"], spec[f"{pre}/o_proj"] = (hid, qw), (qw, hid)
        spec[f"{pre}/k_proj"] = spec[f"{pre}/v_proj"] = (hid, kvw)
        spec[f"{pre}/g_proj"] = (hid, cfg.heads(layer))
        if cfg.is_dense(layer):
            mlp(f"{pre}/mlp", cfg.intermediate_size)
        else:
            spec[f"{pre}/router"] = (hid, cfg.num_experts)
            mlp(f"{pre}/shared", cfg.shared_expert_intermediate_size)
            for e in experts:
                mlp(f"{pre}/experts/{e}", cfg.moe_intermediate_size)
    return spec


def random_checkpoint(cfg: LagunaConfig, layers: Sequence[int], experts: Sequence[int],
                      seed: int = 0) -> Dict[str, np.ndarray]:
    """He-scaled normals by each matrix's own fan-in, norm scales in 0.8–1.2."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in leaf_shapes(cfg, layers, experts).items():
        if name.endswith("/scale"):
            out[name] = rng.uniform(0.8, 1.2, shape).astype(np.float32)
        else:
            out[name] = (rng.standard_normal(shape, dtype=np.float32)
                         * np.float32((2.0 / shape[0]) ** 0.5))
    return out

"""What the text stream's models share (``models/laguna.py``, ``models/sarvam.py``,
``models/qwen3_next.py``, ``models/jamba.py``, ``models/lfm2.py``):
the page program's arithmetic outside attention, and how a checkpoint's leaf
names say what this chip holds.

A model's ``forward`` runs over a page of packed token documents
(``parallel/pages.py``) with the layers and experts its checkpoint holds
(``layers/<l>/…`` names the layers, ``layers/<l>/experts/<e>/…`` the experts:
a share of a stated deployment, as expert parallelism gives one chip). Weights
and activations are ``DTYPE`` (bfloat16), products accumulate in float32;
router scores, softmaxes, norm statistics, rope angles and the segment mean
are float32. Read ``DTYPE`` through the module, so that a test's float32 run
reaches every layer.
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

DTYPE = jnp.bfloat16


@dataclass(frozen=True)
class Share:
    """What the checkpoint holds: layer ids in order, expert ids in the order
    their weights are stacked."""
    layers: Tuple[int, ...]
    experts: Tuple[int, ...]


# --- rope -------------------------------------------------------------------

def yarn_inv_freq(rot: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """(rot/2,) float64 inverse frequencies of YaRN over ``rot`` rotated
    dimensions: interpolated (``/ factor``) frequencies blended into the
    unscaled ones by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times in the original context."""
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(turns):
        return rot * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)


def apply_rope(x, pos, inv_freq: np.ndarray, factor: float, scale: float = 1.0):
    """(tokens, heads, head_dim) → the same, its first ``2 * len(inv_freq)``
    dimensions rotated by ``pos`` (dimension ``i`` pairs with ``i + rot/2``;
    float32 inside), all of it times ``scale``."""
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


def causal_conv(u, w, pos, bias=None):
    """``y_t = Σ_j w[j] · u_{t-(K-1)+j}`` (``+ bias``) per channel, with ``u``
    zero before the document's first token: a tap ``s`` tokens back counts
    where ``pos_t ≥ s``. u (tokens, channels), w (K, channels), bias
    (channels,) or None → float32 (the sums; the shifted rows are read in
    ``u``'s own type, so nothing wider than ``u`` is written on the way)."""
    taps = w.shape[0]
    wf = w.astype(jnp.float32)
    y = u.astype(jnp.float32) * wf[taps - 1]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    for back in range(1, taps):
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:-back].astype(jnp.float32)
        y = y + jnp.where((pos >= back)[:, None], shifted, 0.0) * wf[taps - 1 - back]
    return y


def gated_mlp(h, w_gate_up, w_down):
    """``down(silu(gate(h)) · up(h))``; gate and up are one product."""
    gate, up = jnp.split(dot(h, w_gate_up).astype(h.dtype), 2, axis=-1)
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return dot(act.astype(h.dtype), w_down)


def expert_layer(p: dict, h, valid, slot_of, num_held: int, route, interpret: bool = False):
    """→ (routed + shared, float32), (routed_total, routed_held, rows per held
    expert, chunks run, runs the combine read). ``route(p, h)`` is the model's
    call of ``moe.route``: its top-k, scaling factor and scoring. The held
    rows go through the experts in chunks (``ops/moe.py``): one where the
    router is near even.
    Where the checkpoint has a ``shared_gate`` leaf the shared expert's output
    is scaled token by token by ``sigmoid(h · shared_gate)``; where it has no
    shared expert (no ``shared_gate_up``) the routed sum starts at zero."""
    with jax.named_scope("route"):
        weights, experts = route(p, h)
    with jax.named_scope("dispatch"):
        d = moe.dispatch(experts, valid, slot_of, num_held, weights)
        trips, part = moe.chunks(d, moe.chunk_rows(experts.size, num_held, slot_of.shape[0]), interpret)
    if "shared_gate_up" in p:
        with jax.named_scope("shared"):
            shared = gated_mlp(h, p["shared_gate_up"], p["shared_down"])
            if "shared_gate" in p:
                shared = jax.nn.sigmoid(dot(h, p["shared_gate"])) * shared
    else:
        shared = jnp.zeros(h.shape, jnp.float32)

    def one_chunk(state):
        # a loop's body starts its own name stack: each scope opens in here,
        # under the names the traces are read by (…/moe/dispatch and so on)
        c, y, runs = state
        with jax.named_scope("moe/dispatch"):
            chunk = part(c)
            rows = h[chunk.token_of_row]
        with jax.named_scope("moe/experts"):
            gate, up = jnp.split(moe.grouped_matmul(rows, p["experts_gate_up"], chunk.group_sizes,
                                                   interpret), 2, axis=-1)
            act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(DTYPE)
            out = moe.grouped_matmul(act, p["experts_down"], chunk.group_sizes, interpret)
        with jax.named_scope("moe/combine"):
            y, read = moe.combine(out, y, chunk)
        return c + 1, y, runs + read

    zero = jnp.zeros((), jnp.int32)
    _, y, runs = lax.while_loop(lambda state: state[0] < trips, one_chunk, (zero, shared, zero))
    routed_total = jnp.sum(valid).astype(jnp.int32) * experts.shape[1]
    return y, (routed_total, jnp.sum(d.group_sizes), d.group_sizes, trips, runs)


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


def page_forward(name: str, share: Share, num_experts: int, is_dense, attention, route,
                 eps: float, page_rows: int, params: dict, page, interpret: bool = False,
                 page_counters=None):
    """The page program's body, the same for every model of the stream:
    embed → per held layer ``attention(layer, p, x, doc, pos)`` then the dense
    unit or the routed layer (``route``: :func:`expert_layer`'s) → final norm
    → segment mean. ``page``: int32 (4, page_tokens) — token id, document
    index in the page (-1 on pads), position in its document, row of its
    segment in the page's table (-1 on pads). → ((page_rows, hidden) float32
    segment features, int32 counters: routed_total, routed_held, expert_chunks
    (chunks run, over the sparse layers), expert_chunk_calls (the sparse
    layers), combine_runs (the (token tile, expert) runs the combine read, over
    the sparse layers), then the model's own ``page_counters`` (int32, its module's
    ``PAGE_COUNTERS`` names them), then rows per held expert for every sparse
    layer). Scopes are ``<name>/embed``, ``<name>/L<k>/attn/…``,
    ``<name>/L<k>/{mlp,moe}/…``, ``<name>/pool``."""
    ids, doc, pos, seg = page[0], page[1], page[2], page[3]
    valid = doc >= 0
    slot_of = np.full((num_experts,), -1, np.int32)
    slot_of[list(share.experts)] = np.arange(len(share.experts), dtype=np.int32)
    slot_of = jnp.asarray(slot_of, jnp.int32)
    with jax.named_scope(f"{name}/embed"):
        x = params["embed"][ids]
    counters = []
    for p, layer in zip(params["layers"], share.layers):
        with jax.named_scope(f"{name}/L{layer}/attn"):
            x = attention(layer, p, x, doc, pos)
        with jax.named_scope(f"{name}/L{layer}/{'mlp' if is_dense(layer) else 'moe'}"):
            with jax.named_scope("norm"):
                h = rms_norm(x, p["mlp_norm"], eps)
            if is_dense(layer):
                y = gated_mlp(h, p["w_gate_up"], p["w_down"])
            else:
                y, counts = expert_layer(p, h, valid, slot_of, len(share.experts), route,
                                         interpret)
                counters.append(counts)
            x = (x.astype(jnp.float32) + y).astype(DTYPE)
    with jax.named_scope(f"{name}/pool"):
        rows = segment_mean(rms_norm(x, params["final_norm"], eps, jnp.float32), seg, page_rows)
    zero = jnp.zeros((), jnp.int32)
    totals = ([sum((c[i] for c in counters), zero) for i in (0, 1, 3)] + [zero + len(counters)]
              + [sum((c[4] for c in counters), zero)])
    own = [] if page_counters is None else [page_counters.astype(jnp.int32)]
    return rows, jnp.concatenate([jnp.stack(totals)] + own + [c[2] for c in counters])


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


def leaf_reader(read):
    """``get(name)``: the checkpoint's leaf on the device, cast to ``DTYPE``
    once as it arrives; ``side_by_side(prefix, leaves)``: several of them as
    the columns of one matrix."""
    cast = jax.jit(lambda a: a.astype(DTYPE))

    def get(name):
        return cast(read(name))

    def side_by_side(prefix, leaves):
        return jnp.concatenate([get(f"{prefix}/{leaf}") for leaf in leaves], axis=-1)

    return get, side_by_side


def stack_mlp(p: dict, pre: str, dense: bool, experts: Sequence[int], get, side_by_side,
              gated_shared: bool = False, shared: bool = True) -> None:
    """Layer ``pre``'s dense unit, or its router, shared expert (where the
    model has one: ``shared``; with its per-token gate where the checkpoint
    has one: ``gated_shared``) and held experts (stacked on a leading axis in
    ``experts`` order), into ``p``: gate and up projections side by side, the
    products' own layout."""
    pair = ("gate_proj", "up_proj")
    if dense:
        p["w_gate_up"] = side_by_side(f"{pre}/mlp", pair)
        p["w_down"] = get(f"{pre}/mlp/down_proj")
        return
    p["router"] = get(f"{pre}/router")
    if shared:
        p["shared_gate_up"] = side_by_side(f"{pre}/shared", pair)
        p["shared_down"] = get(f"{pre}/shared/down_proj")
    if gated_shared:
        p["shared_gate"] = get(f"{pre}/shared_gate")
    p["experts_gate_up"] = jnp.stack([side_by_side(f"{pre}/experts/{e}", pair) for e in experts])
    p["experts_down"] = jnp.stack([get(f"{pre}/experts/{e}/down_proj") for e in experts])


def mlp_leaf_shapes(spec: Dict[str, Tuple[int, ...]], pre: str, hid: int, dense_width,
                    router_width: int, shared_width: int, expert_width: int,
                    experts: Sequence[int], gated_shared: bool = False) -> None:
    """The leaves :func:`stack_mlp` reads, into ``spec``; ``dense_width`` is
    None for a sparse layer, ``shared_width`` 0 where it has no shared
    expert."""
    def unit(prefix, width):
        spec[f"{prefix}/gate_proj"] = spec[f"{prefix}/up_proj"] = (hid, width)
        spec[f"{prefix}/down_proj"] = (width, hid)

    if dense_width is not None:
        unit(f"{pre}/mlp", dense_width)
        return
    spec[f"{pre}/router"] = (hid, router_width)
    if shared_width:
        unit(f"{pre}/shared", shared_width)
    if gated_shared:
        spec[f"{pre}/shared_gate"] = (hid, 1)
    for e in experts:
        unit(f"{pre}/experts/{e}", expert_width)


def random_leaves(shapes: Dict[str, Tuple[int, ...]], seed: int = 0) -> Dict[str, np.ndarray]:
    """He-scaled normals by each matrix's own fan-in, norm scales in 0.8–1.2,
    a ``bias`` a small normal."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in shapes.items():
        if name.endswith("/scale"):
            out[name] = rng.uniform(0.8, 1.2, shape).astype(np.float32)
        elif name.endswith("/bias"):
            out[name] = (rng.standard_normal(shape) * 0.05).astype(np.float32)
        else:
            out[name] = (rng.standard_normal(shape, dtype=np.float32)
                         * np.float32((2.0 / shape[0]) ** 0.5))
    return out

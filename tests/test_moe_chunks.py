"""The routed layer's chunks (``ops/moe.py``, ``models/text_layers.expert_layer``)
in float32 at tiny widths on the CPU, the grouped product in the Pallas
interpreter: the layer against a plain per-token, per-choice loop (no sort, no
chunk) on routings that force every branch of the chunk loop, the counters the
page program carries, and the scopes the benchmark's readers find the layer by.
"""

# fast-registry: jitted routed layers (the grouped product in the Pallas interpreter)

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from video_features_tpu.models import text_layers
from video_features_tpu.ops import moe

TOKENS, HIDDEN, WIDTH, EXPERTS, TOP_K = 48, 64, 32, 16, 4
ROW_TILE = 8  # the grouped product's row tile here: a chunk is a few of them, not all 192 assignments


@pytest.fixture(autouse=True)
def tiny_float32(monkeypatch):
    monkeypatch.setattr(text_layers, "DTYPE", jnp.float32)
    monkeypatch.setattr(moe, "GMM_TILING", (ROW_TILE,) + moe.GMM_TILING[1:])


def layer_weights(rng, held: int) -> dict:
    def he(*shape):
        return jnp.asarray(rng.standard_normal(shape) * (2.0 / shape[-2]) ** 0.5, jnp.float32)

    return {"shared_gate_up": he(HIDDEN, 2 * WIDTH), "shared_down": he(WIDTH, HIDDEN),
            "experts_gate_up": he(held, HIDDEN, 2 * WIDTH), "experts_down": he(held, WIDTH, HIDDEN)}


def draw_routing(rng, among, tokens: int = TOKENS):
    """Every token's ``TOP_K`` distinct experts out of ``among``, with weights
    as a router gives them (positive, summing to the scaling factor)."""
    experts = np.stack([rng.permutation(among)[:TOP_K] for _ in range(tokens)]).astype(np.int32)
    weights = rng.uniform(0.1, 1.0, experts.shape)
    return (weights / weights.sum(-1, keepdims=True) * 2.5).astype(np.float32), experts


def plain_loop(p, h, weights, experts, valid, slot_of):
    """Token by token, choice by choice, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.asarray(h, np.float64)

    def unit(x, gate_up, down):
        gate, up = np.split(x @ gate_up, 2)
        return (gate / (1.0 + np.exp(-gate)) * up) @ down

    y = np.stack([unit(x, p["shared_gate_up"], p["shared_down"]) for x in h])
    for t in range(h.shape[0]):
        for k in range(experts.shape[1]):
            slot = slot_of[experts[t, k]]
            if valid[t] and slot >= 0:
                y[t] += weights[t, k] * unit(h[t], p["experts_gate_up"][slot], p["experts_down"][slot])
    return y


def run_layer(p, h, weights, experts, valid, slot_of, held):
    route = lambda _p, _h: (jnp.asarray(weights), jnp.asarray(experts))  # noqa: E731
    y, counts = jax.jit(lambda p, h: text_layers.expert_layer(
        p, h, jnp.asarray(valid), jnp.asarray(slot_of), held, route, interpret=True))(p, h)
    return np.asarray(y), [np.asarray(c) for c in counts]


def share(held_ids):
    slot_of = np.full((EXPERTS,), -1, np.int32)
    slot_of[list(held_ids)] = np.arange(len(held_ids))
    return slot_of


def nan_past_the_groups(real):
    def grouped_matmul(lhs, rhs, group_sizes, interpret=False):
        out = real(lhs, rhs, group_sizes, interpret)
        return jnp.where(jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes), out, jnp.nan)
    return grouped_matmul


ALL = tuple(range(EXPERTS))
CASES = {
    # name: (held ids, the experts the router may choose, real tokens, chunks that must run or None)
    "quarter_held": (ALL[:4], ALL, TOKENS, None),
    "eighth_held": (ALL[4:6], ALL, TOKENS, None),
    "every_expert_held": (ALL, ALL, TOKENS, 1),
    "none_chosen_is_held": (ALL[:4], ALL[4:], TOKENS, 0),
    "all_chosen_are_held_three_chunks": (ALL[:4], ALL[:4], TOKENS, 3),
    "an_expert_across_a_chunks_edge": (ALL[:4], ALL[:5], TOKENS, None),
    "pads_never_routed": (ALL[:4], ALL[:6], TOKENS - 11, None),
    "nan_past_the_groups": (ALL[:4], ALL[:6], TOKENS - 5, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_layer_against_a_plain_loop(case, monkeypatch):
    held_ids, among, real_tokens, chunks_wanted = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    slot_of, held = share(held_ids), len(held_ids)
    p = layer_weights(rng, held)
    h = jnp.asarray(rng.standard_normal((TOKENS, HIDDEN)), jnp.float32)
    weights, experts = draw_routing(rng, np.asarray(among))
    valid = np.arange(TOKENS) < real_tokens
    if case == "nan_past_the_groups":
        sound = run_layer(p, h, weights, experts, valid, slot_of, held)[0]
        monkeypatch.setattr(moe, "grouped_matmul", nan_past_the_groups(moe.grouped_matmul))
    y, (routed_total, routed_held, rows, chunks, runs) = run_layer(p, h, weights, experts, valid, slot_of, held)

    want = plain_loop(p, h, weights, experts, valid, slot_of)
    assert np.isfinite(y).all()
    gaps = np.linalg.norm(y - want, axis=1) / np.linalg.norm(want, axis=1)
    assert gaps.max() < 1e-6, (case, gaps.max())
    if case == "nan_past_the_groups":
        np.testing.assert_array_equal(y, sound)

    # the counters: assignments of real tokens only, the held ones by expert in weight order
    held_mask = (slot_of[experts] >= 0) & valid[:, None]
    assert int(routed_total) == TOP_K * real_tokens and int(routed_held) == int(held_mask.sum())
    np.testing.assert_array_equal(rows, np.bincount(slot_of[experts][held_mask], minlength=held))
    chunk = moe.chunk_rows(TOKENS * TOP_K, held, EXPERTS)
    assert chunk == (TOKENS * TOP_K if held == EXPERTS else
                     -(-int(1.5 * TOKENS * TOP_K * held / EXPERTS) // ROW_TILE) * ROW_TILE)
    assert int(chunks) == -(-int(routed_held) // chunk)
    # the combine reads each (token tile, expert) run once a chunk: one at least a non-empty expert, a row at most
    assert np.count_nonzero(rows) <= int(runs) <= int(routed_held)
    if chunks_wanted is not None:
        assert int(chunks) == chunks_wanted
    if case == "an_expert_across_a_chunks_edge":
        bounds = np.cumsum(rows)
        assert any(lo < chunk < hi for lo, hi in zip(np.concatenate([[0], bounds]), bounds)), rows
    if case == "pads_never_routed":
        d = moe.dispatch(jnp.asarray(experts), jnp.asarray(valid), jnp.asarray(slot_of), held)
        assert (np.asarray(d.token_of_row)[:int(routed_held)] < real_tokens).all()
        assert not np.asarray(d.held)[real_tokens:].any()


def test_chunks_cut_the_groups_exactly():
    """Every chunk's groups are its slice of the page's: together they cover
    each expert's rows once, in order, and nothing past the held prefix."""
    rng = np.random.default_rng(3)
    weights, experts = draw_routing(rng, np.arange(6))
    valid = np.arange(TOKENS) < TOKENS - 7
    d = moe.dispatch(jnp.asarray(experts), jnp.asarray(valid), jnp.asarray(share(ALL[:4])), 4,
                     jnp.asarray(weights))
    rows = moe.chunk_rows(TOKENS * TOP_K, 4, EXPERTS)
    trips, part = moe.chunks(d, rows)
    sizes, tokens, router = np.asarray(d.group_sizes), [], []
    assert int(trips) == -(-int(sizes.sum()) // rows) >= 2
    total = np.zeros_like(sizes)
    for c in range(int(trips)):
        chunk = part(c)
        inside = int(np.sum(chunk.group_sizes))
        assert chunk.token_of_row.shape == (rows,) and inside == min(rows, int(sizes.sum()) - c * rows)
        total += np.asarray(chunk.group_sizes)
        tokens.append(np.asarray(chunk.token_of_row)[:inside])
        router.append(np.asarray(chunk.weight_of_row)[:inside])
    np.testing.assert_array_equal(total, sizes)
    np.testing.assert_array_equal(np.concatenate(tokens), np.asarray(d.token_of_row)[:sizes.sum()])
    # a sorted row carries its own assignment's weight
    flat = np.flatnonzero(np.asarray(d.held).reshape(-1))
    by_expert = flat[np.argsort(share(ALL[:4])[experts.reshape(-1)[flat]], kind="stable")]
    np.testing.assert_array_equal(np.concatenate(router), weights.reshape(-1)[by_expert])


COMBINE_CASES = {
    # name: (tokens, width, token tile, rows of the chunk, rows per held expert
    # in the chunk, tokens an expert draws from: None all, or a (lo, hi) range)
    "a_token_tile_that_owns_no_row": (64, 128, 16, 64, (20, 0, 21), "skip_tile_1"),
    "a_run_longer_than_one_slab": (64, 128, 32, 96, (40, 9, 14), None),
    "a_run_cut_by_the_chunks_edge": (64, 128, 16, 48, (11, 7, 30), None),
    "tokens_not_a_multiple_of_the_tile": (40, 128, 16, 64, (25, 6, 10), None),
    "a_width_not_a_multiple_of_128": (48, 200, 16, 64, (30, 0, 11), None),
}


@pytest.mark.parametrize("case", list(COMBINE_CASES))
def test_combine_sums_bfloat16_rows_in_float32(case, monkeypatch):
    """The combine alone (the ``moe_combine`` kernel in the interpreter) on
    bfloat16 rows, as the chip's configurations have them, against a float64
    sum: the router's float32 weight meets every row whole (a weight rounded
    to the rows' type would miss by 2**-9), the sums are float32, whatever the
    rows past the groups hold (NaN here), and each edge of the token tiles and
    the slabs of rows is met."""
    tokens, width, tile, rows, sizes, draw = COMBINE_CASES[case]
    monkeypatch.setattr(moe, "COMBINE_VMEM", 20 * tile * width)  # five float32 rows a token of ``tile``
    monkeypatch.setattr(moe, "COMBINE_VREGS", 8)  # groups of 8 rows: a slab holds two or more
    seen = {}
    real = moe._combine

    def spy(expert_out, into, chunk, tile, slab, align, group):
        seen.update(tile=tile, slab=slab, group=group)
        return real(expert_out, into, chunk, tile, slab, align, group)

    monkeypatch.setattr(moe, "_combine", spy)
    rng = np.random.default_rng(sorted(COMBINE_CASES).index(case))
    among = np.arange(tokens)
    if draw == "skip_tile_1":
        among = among[(among < tile) | (among >= 2 * tile)]
    # each held expert's rows: distinct tokens in token order (the dispatch sort is stable)
    token = [np.sort(rng.choice(among, n, replace=False)) for n in sizes]
    inside = sum(sizes)
    token = np.concatenate(token + [rng.integers(0, tokens, rows - inside)]).astype(np.int32)
    out = jnp.asarray(rng.standard_normal((rows, width)), jnp.bfloat16)
    out = jnp.where(jnp.arange(rows)[:, None] < inside, out, jnp.nan)
    weight = rng.uniform(0.05, 1.0, rows).astype(np.float32)
    into = rng.standard_normal((tokens, width)).astype(np.float32)
    chunk = moe.Chunk(jnp.asarray(token), jnp.asarray(weight), jnp.asarray(sizes, jnp.int32), True)
    got, runs = jax.jit(lambda o, i: moe.combine(o, i, chunk))(out, jnp.asarray(into))
    got = np.asarray(got, np.float64)
    want = into.astype(np.float64)
    np.add.at(want, token[:inside], np.asarray(out[:inside].astype(jnp.float32), np.float64)
              * weight[:inside, None].astype(np.float64))
    assert np.isfinite(got).all()
    assert (np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)).max() < 1e-6

    # the runs read: the non-empty (token tile, expert) pairs, and each case's edge is there
    assert seen["tile"] == tile and seen["group"] == 8 and seen["slab"] % 8 == 0
    expert = np.repeat(np.arange(len(sizes)), sizes)
    pairs = {(t // tile, e) for t, e in zip(token[:inside], expert)}
    assert int(runs) == len(pairs)
    longest = max(np.sum((token[:inside] // tile == t) & (expert == e)) for t, e in pairs)
    assert {"a_token_tile_that_owns_no_row": not any(t == 1 for t, _ in pairs),
            "a_run_longer_than_one_slab": longest > seen["slab"],
            "a_run_cut_by_the_chunks_edge": inside == rows,
            "tokens_not_a_multiple_of_the_tile": tokens % tile != 0,
            "a_width_not_a_multiple_of_128": width % 128 != 0}[case]


def tiny_page_program(held_ids, sparse_layers: int = 2):
    """``text_layers.page_forward`` over a dense layer and ``sparse_layers``
    routed ones, attention left out (it is not this file's)."""
    rng = np.random.default_rng(5)
    experts_share = text_layers.Share(tuple(range(sparse_layers + 1)), tuple(held_ids))

    def layer(dense):
        p = {"mlp_norm": jnp.ones((HIDDEN,), jnp.float32)}
        if dense:
            p.update(w_gate_up=jnp.asarray(rng.standard_normal((HIDDEN, 2 * WIDTH)) * 0.1, jnp.float32),
                     w_down=jnp.asarray(rng.standard_normal((WIDTH, HIDDEN)) * 0.1, jnp.float32))
        else:
            p.update(layer_weights(rng, len(held_ids)),
                     router=jnp.asarray(rng.standard_normal((HIDDEN, EXPERTS)), jnp.float32))
        return p

    params = {"embed": jnp.asarray(rng.standard_normal((100, HIDDEN)), jnp.float32),
              "final_norm": jnp.ones((HIDDEN,), jnp.float32),
              "layers": [layer(k == 0) for k in range(sparse_layers + 1)]}
    real = TOKENS - 9
    page = np.zeros((4, TOKENS), np.int32)
    page[0] = rng.integers(0, 100, TOKENS)
    page[1, real:] = page[3, real:] = -1
    page[2, :real] = np.arange(real)
    page[3, :real] = np.arange(real) // 16
    route = lambda p, h: moe.route(h, p["router"], TOP_K, 2.5)  # noqa: E731
    program = jax.jit(lambda params, page: text_layers.page_forward(
        "tiny", experts_share, EXPERTS, lambda layer: layer == 0, lambda _l, _p, x, _d, _pos: x, route,
        1e-6, 4, params, page, interpret=True))
    return program, params, jnp.asarray(page), real


def test_page_counters_layout():
    """routed_total, routed_held, expert_chunks, expert_chunk_calls,
    combine_runs, then the rows per held expert of every sparse layer
    (``expert_rows``: sparse layers × experts held, as the extractor's stats
    reshape them)."""
    program, params, page, real = tiny_page_program(ALL[:4], sparse_layers=2)
    rows, counters = program(params, page)
    counters = np.asarray(counters)
    assert rows.shape == (4, HIDDEN) and counters.shape == (5 + 2 * 4,) and counters.dtype == np.int32
    routed_total, routed_held, chunks, calls, runs = counters[:5]
    assert routed_total == 2 * TOP_K * real and calls == 2
    expert_rows = counters[5:].reshape(-1, 4)
    assert expert_rows.shape == (2, 4) and expert_rows.sum() == routed_held
    chunk = moe.chunk_rows(TOKENS * TOP_K, 4, EXPERTS)
    assert chunks == sum(-(-int(n) // chunk) for n in expert_rows.sum(axis=1)) >= calls
    # a non-empty expert is read in one run at least, a run holds a row at least
    assert np.count_nonzero(expert_rows) <= runs <= routed_held


def test_the_readers_scopes_survive_the_loop():
    """The benchmark's readers find the routed layer's work by the substrings
    ``/moe/route``, ``/moe/dispatch``, ``/moe/experts`` and ``/moe/combine``
    of an operation's ``op_name``; a loop's body starts a name stack of its
    own, so the scopes are opened inside it. A renamed scope fails here and
    not at the driver."""
    program, params, page, _real = tiny_page_program(ALL[:4], sparse_layers=1)
    hlo = program.lower(params, page).compile().as_text()
    names = {line.split('op_name="', 1)[1].split('"', 1)[0] for line in hlo.splitlines() if 'op_name="' in line}
    for scope in ("/moe/route", "/moe/dispatch", "/moe/experts", "/moe/combine", "/moe/shared"):
        assert any(scope in name for name in names), scope
    in_the_loop = [name for name in names if "/while/body/" in name]
    for scope in ("/moe/dispatch", "/moe/experts", "/moe/combine"):
        assert any(scope in name.split("/while/body", 1)[1] for name in in_the_loop), scope
    # the combine is the ``moe_combine`` kernel: the by-token sort and the transposed grouped product are gone
    combine = [name.split("/moe/combine", 1)[1].split("/") for name in in_the_loop if "/moe/combine" in name]
    assert any("moe_combine" in part for parts in combine for part in parts)
    assert not any(part == "sort" or "tgmm" in part for parts in combine for part in parts)

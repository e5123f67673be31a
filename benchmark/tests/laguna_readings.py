"""The two readings that ``gap.laguna``'s limit lies under, taken on the chip
at the cell's own size (``laguna_s21_bf16``); neither is a flag of the program.

    chiprun -- python3 benchmark/tests/laguna_readings.py fault <seed>
    chiprun -- python3 benchmark/tests/laguna_readings.py expert <seed>
    chiprun -- python3 benchmark/tests/laguna_readings.py float8 <seed>
    chiprun -- python3 benchmark/tests/laguna_readings.py gmm <seed>
    chiprun -- python3 benchmark/tests/laguna_readings.py attention <seed>

``fault``: the cell's own run with an answer altered where it is produced, so
that everything after it is the program's own: the routed scaling factor left
out (``ops/moe.route`` called with 1.0 for the published 2.5), the fault a
routed layer of this kind can have and a comparison at bfloat16's floor can
see. Exit 0 when the run is NOT correct. ``expert`` is the fault ISSUE 34 asked
for, the first held expert's output scaled by 1.05 in every sparse layer (a
patch of ``ops/moe.combine``'s input): by arithmetic it moves a row by 6e-4 (one
token in 26 meets that expert, at a tenth of the routed weight, in a mean over
16-64 tokens), two hundred times under what bfloat16 itself moves it, and the
run reads as a sound one; it is kept to show that (PERF.md section 2).

``float8``: the nearest precision below the one the configuration states.
The reference against itself: once as ``correct`` takes it (weights rounded
to bfloat16) and once with every projection and expert matrix (attention's
five, the dense unit, the shared and the routed experts; not the embedding,
the norms or the router) rounded to ``float8_e4m3fn`` first, on the
documents ``correct`` would sample. No program runs. Prints ``gap.laguna``
as ``check.row_gaps`` computes it; exit 0 when it is over the cell's limit.
Beside it, for the reason the limit is given: the same reference with its
products at the default precision (operands rounded to bfloat16 per product,
float32 sums: the arithmetic the configuration states, without the program),
and for both how many of the router's choices changed.

``gmm``: the routed experts' grouped products as the chip runs them
(``ops/moe.grouped_matmul``: the Pallas ``megablox.gmm``, compiled by Mosaic;
tier-1 runs the same kernel, but in the interpreter) against
``lax.ragged_dot`` with float32 sums, on one full page's real dispatch at the
published widths (16,384 tokens routed over 256 experts by ``ops/moe.route``,
the 64 held sorted by ``ops/moe.dispatch``; 3072 -> 2 x 1024 and 1024 -> 3072),
and both against float32 products at ``highest`` for three of the groups. The
two differ only where a float32 sum is rounded to bfloat16 and in the order
of the sums: a row may differ by bfloat16's rounding, 2**-8 of its norm, and
no more. Exit 0 when every row of both products is inside that.

``attention``: the other kernel Mosaic compiles only on the chip,
``ops/segment_attention``, against scores materialised in float32 at
``highest`` (per key/value head and block of queries), on two pages of the
seed's own corpus (the fullest mixed page with its pads, and the page that is
one 16,384-token document) in both layer kinds at the published head counts
(48 heads full, 72 heads with the window of 512). The kernel rounds the
softmax's weights to bfloat16 for their product with the values and its result
to bfloat16: a real token's row may differ by two such roundings, 2**-7 of its
norm. Exit 0 when every real token's row is inside that.
"""

from __future__ import annotations

import contextlib
import json
import sys

FACTOR = 1.05
GMM_TOKENS = 16384  # one full page
ATTENTION_TOKENS, ATTENTION_BLOCK = None, 512  # the page's own size, the extractor's block
FLOAT8_LEAVES = ("q_proj", "k_proj", "v_proj", "g_proj", "o_proj", "gate_proj", "up_proj",
                 "down_proj")


@contextlib.contextmanager
def altered(name: str):
    """``ops.moe.<name>`` replaced by the altered one for the duration."""
    import jax.numpy as jnp

    from video_features_tpu.ops import moe

    real = getattr(moe, name)

    def combine(expert_out, weights, d):
        first = jnp.arange(expert_out.shape[0])[:, None] < d.group_sizes[0]
        return real(jnp.where(first, expert_out * FACTOR, expert_out).astype(expert_out.dtype),
                    weights, d)

    def route(h, w_router, top_k, scale):
        return real(h, w_router, top_k, 1.0)

    setattr(moe, name, {"combine": combine, "route": route}[name])
    try:
        yield
    finally:
        setattr(moe, name, real)


def fault(cell_name: str, seed: int, patched: str = "route") -> int:
    import run as bench_run
    from conftest import ROOT

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    devices = bench_run.require_chips(int(cell["chips"]))
    with altered(patched):
        result = bench_run.run_cell(bench, cell, seed, float(bench["run_seconds"]), False,
                                    devices=devices)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] is False else 1


def float8(cell_name: str, seed: int) -> int:
    import os
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from check import row_gaps
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen
    from reference import laguna as ref
    from weights import make_weights, unflatten

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    bench_run.require_chips(int(cell["chips"]))
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    scratch = os.path.join(ROOT, "output", "benchmark", "laguna_float8")
    docs = gen.write_corpus(traffic, seed, os.path.join(scratch, "corpus"))
    # the sample `correct` draws: the longest document and check_videos - 1 others
    ctx = types.SimpleNamespace(seed=seed, conf=conf)
    window = {"finished": [os.path.join(scratch, f"w{i:05d}_{os.path.basename(d)}")
                           for i, d in enumerate(docs)]}
    sample = [docs[int(os.path.basename(p)[1:6])] for p in gen.check_sample(ctx, window)]
    spec = ref.weight_specs()["laguna"]
    tree = unflatten(make_weights(spec, seed, "laguna"))

    def rounded(float8_too: bool):
        def leaf(path, a):
            a = jnp.asarray(a)
            name = getattr(path[-1], "key", "")
            if float8_too and name in FLOAT8_LEAVES:
                a = a.astype(jnp.float8_e4m3fn)
            return a.astype(jnp.bfloat16)
        return jax.tree_util.tree_map_with_path(leaf, tree)

    def run(float8_too: bool, precision: str):
        features = ref.make_forward(rounded(float8_too), precision=precision, choices=True)
        out = []
        for path in sample:
            with np.load(path) as z:
                out.append(features(z["ids"], z["segment_ends"]))
        return out

    def against(want, low):
        gaps = [row_gaps(l[0], w[0]) for w, l in zip(want, low)]
        # a choice changed: an expert among a token's ten that the other run did not choose
        changed = [float(np.mean([(lc[:, :, None] != wc[:, None, :]).all(-1).mean()
                                  for wc, lc in zip(w[1], l[1])])) for w, l in zip(want, low)]
        return {"gap": max(float(g.max()) for g in gaps),
                "median": [float(np.median(g)) for g in gaps],
                "choices_changed_share": changed}

    want = run(False, "highest")
    readings = {"float8": against(want, run(True, "highest")),
                "bfloat16_products": against(want, run(False, "default"))}
    limit = conf["limits"]["gap.laguna"]
    print(json.dumps({"limit": limit, "documents": [os.path.basename(p) for p in sample],
                      **readings}), flush=True)
    return 0 if readings["float8"]["gap"] > limit else 1


def gmm(cell_name: str, seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import run as bench_run
    from conftest import ROOT
    from reference.laguna import EXPERTS, PUBLISHED as P
    from video_features_tpu.ops import moe

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    bench_run.require_chips(int(bench_run.find_cell(bench, cell_name)["chips"]))
    tokens, hid, width, held = GMM_TOKENS, P["hidden_size"], P["moe_intermediate_size"], len(EXPERTS)
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    h = jax.random.normal(keys[0], (tokens, hid), jnp.bfloat16)

    def kernel(key, shape):  # He normals by the matrix's own fan-in, as weights.make_leaf draws them
        return (jax.random.normal(key, shape, jnp.float32) * (2.0 / shape[-2]) ** 0.5).astype(jnp.bfloat16)

    router = kernel(keys[1], (hid, P["num_experts"]))
    w_gate_up, w_down = kernel(keys[2], (held, hid, 2 * width)), kernel(keys[3], (held, width, hid))
    slot_of = np.full((P["num_experts"],), -1, np.int32)
    slot_of[list(EXPERTS)] = np.arange(held)

    @jax.jit
    def both(h, router, w_gate_up, w_down):
        _w, experts = moe.route(h, router, P["num_experts_per_tok"], P["moe_routed_scaling_factor"])
        d = moe.dispatch(experts, jnp.ones((tokens,), bool), jnp.asarray(slot_of), held)
        rows = h[d.token_of_row]
        up = moe.grouped_matmul(rows, w_gate_up, d.group_sizes)
        gate, lin = jnp.split(up, 2, axis=-1)
        act = (jax.nn.silu(gate.astype(jnp.float32)) * lin.astype(jnp.float32)).astype(jnp.bfloat16)
        down = moe.grouped_matmul(act, w_down, d.group_sizes)
        ragged = [lax.ragged_dot(a, w, d.group_sizes, preferred_element_type=jnp.float32)
                  for a, w in ((rows, w_gate_up), (act, w_down))]
        return d.group_sizes, rows, act, (up, down), ragged

    sizes, rows, act, kernels, ragged = both(h, router, w_gate_up, w_down)
    sizes = np.asarray(sizes)
    covered, bounds = int(sizes.sum()), np.concatenate([[0], np.cumsum(sizes)])

    def row_gap(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)

    readings, bound = {}, 2.0 ** -8
    for name, lhs, w, got, want in (("gate_up", rows, w_gate_up, kernels[0], ragged[0]),
                                    ("down", act, w_down, kernels[1], ragged[1])):
        gaps = row_gap(got[:covered], want[:covered])
        differ = float(np.mean(np.asarray(got[:covered]) != np.asarray(want[:covered].astype(jnp.bfloat16))))
        exact = {}
        for g in (0, held // 2, held - 1):
            lo, hi = int(bounds[g]), int(bounds[g + 1])
            with jax.default_matmul_precision("highest"):
                ref = jnp.dot(lhs[lo:hi].astype(jnp.float32), w[g].astype(jnp.float32))
            exact[g] = {"rows": hi - lo, "gmm": float(row_gap(got[lo:hi], ref).max()),
                        "ragged_dot": float(row_gap(want[lo:hi], ref).max())}
        readings[name] = {"worst_row": float(gaps.max()), "median_row": float(np.median(gaps)),
                          "elements_that_differ_after_rounding": differ, "against_float32_highest": exact}
    ok = all(r["worst_row"] <= bound and all(e["gmm"] <= bound for e in r["against_float32_highest"].values())
             for r in readings.values())
    print(json.dumps({"tokens": tokens, "rows_held": covered, "rows_an_expert": [int(sizes.min()), int(sizes.max())],
                      "bound": bound, "inside": ok, **readings}), flush=True)
    return 0 if ok else 1


def attention(cell_name: str, seed: int) -> int:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import run as bench_run
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen
    from reference.laguna import PUBLISHED as P
    from video_features_tpu.ops.segment_attention import segment_attention

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    bench_run.require_chips(int(cell["chips"]))
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    tokens, block = ATTENTION_TOKENS or int(traffic["equal_work"]["page_tokens"]), ATTENTION_BLOCK
    kv, d = P["num_key_value_heads"], P["head_dim"]
    pages = gen.pack_pass(gen.document_plan(traffic, seed), int(traffic["equal_work"]["page_tokens"]))
    mixed = max(pages, key=lambda page: (len(page), sum(page)))
    if ATTENTION_TOKENS:  # a dry run off the chip: the same make-up in small
        pages, mixed = [[tokens]], [tokens // 4, tokens // 8, tokens // 2 - 3]

    @functools.partial(jax.jit, static_argnames=("window",))
    def plain(q, k, v, doc, window):
        group, step = q.shape[1] // (kv * d), min(block, 128)  # scores of a step: 72 heads x 128 x 16,384 x 4 B = 0.6 GB
        qh = q.astype(jnp.float32).reshape(tokens // step, step, kv, group, d)
        kh, vh = (a.astype(jnp.float32).reshape(tokens, kv, d) for a in (k, v))
        cols = jnp.arange(tokens)

        def one(args):
            qb, start = args
            rows = start + jnp.arange(step)
            mask = (doc[rows][:, None] == doc[None, :]) & (cols[None, :] <= rows[:, None])
            if window is not None:
                mask &= rows[:, None] - cols[None, :] < window
            with jax.default_matmul_precision("highest"):
                s = jnp.einsum("qhgd,khd->hgqk", qb, kh)
                w = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
                return jnp.einsum("hgqk,khd->qhgd", w, vh)

        out = lax.map(one, (qh, jnp.arange(0, tokens, step)))
        return out.reshape(tokens, q.shape[1])

    readings, bound = {}, 2.0 ** -7
    key = jax.random.PRNGKey(seed % (2 ** 31))
    for page_name, page in (("mixed", mixed), ("one_document", max(pages, key=sum))):
        doc = np.full(tokens, -1, np.int32)
        doc[:sum(page)] = np.repeat(np.arange(len(page)), page)
        for layer, heads, window in (("full", P["heads_full"], None),
                                     ("sliding", P["heads_sliding"], P["sliding_window"])):
            key, kq, kk, kvv = jax.random.split(key, 4)
            q = (jax.random.normal(kq, (tokens, heads * d), jnp.float32) * d ** -0.5).astype(jnp.bfloat16)
            k, v = (jax.random.normal(x, (tokens, kv * d), jnp.bfloat16) for x in (kk, kvv))
            got = segment_attention(q, k, v, jnp.asarray(doc), kv_heads=kv, head_dim=d, window=window,
                                    block=block, interpret=ATTENTION_TOKENS is not None)
            want = np.asarray(plain(q, k, v, jnp.asarray(doc), window))[doc >= 0]
            got = np.asarray(got.astype(jnp.float32))[doc >= 0]
            gaps = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
            readings[f"{page_name}.{layer}"] = {"documents": list(map(int, page)), "heads": heads,
                                                "worst_row": float(gaps.max()),
                                                "median_row": float(np.median(gaps))}
    ok = all(r["worst_row"] <= bound for r in readings.values())
    print(json.dumps({"tokens": tokens, "bound": bound, "inside": ok, **readings}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    import conftest  # noqa: F401 — puts the checkout and benchmark/ on the path

    kind, seed = sys.argv[1], int(sys.argv[2])
    cell = sys.argv[3] if len(sys.argv) > 3 else "laguna_s21_bf16.corpus_transcripts"
    sys.exit({"fault": fault, "float8": float8, "gmm": gmm, "attention": attention,
              "expert": lambda c, s: fault(c, s, "combine")}[kind](cell, seed))

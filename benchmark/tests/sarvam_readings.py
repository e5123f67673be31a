"""The readings that ``gap.sarvam``'s limit lies between, and the latent
attention kernel alone, taken on the chip at the cell's own size
(``sarvam_105b_bf16``); none is a flag of the program.

    chiprun -- python3 benchmark/tests/sarvam_readings.py fault <seed>
    chiprun -- python3 benchmark/tests/sarvam_readings.py float8 <seed>
    chiprun -- python3 benchmark/tests/sarvam_readings.py attention <seed>

``fault``: the cell's own run with MLA's own fault planted where the scores
are made, so that everything after it is the program's: the shared rope term
``qR·kR`` left out (``models/sarvam.py``'s call of the attention kernel given
rotated queries of zeros). Exit 0 when the run is NOT correct.

``float8``: the nearest precision below the one the configuration states.
The reference against itself: once as ``correct`` takes it (weights rounded to
bfloat16) and once with every projection and expert matrix (attention's four,
the dense unit, the shared and the routed experts; not the embedding, the
norms, the router or its bias) rounded to ``float8_e4m3fn`` first, on the
documents ``correct`` would sample. No program runs. Prints ``gap.sarvam`` as
``check.row_gaps`` computes it; exit 0 when it is over the cell's limit.
Beside it: the same reference with its products at the default precision
(operands rounded to bfloat16 per product, float32 sums: the arithmetic the
configuration states, without the program), and for both how many of the
router's choices changed.

``attention``: ``ops/segment_attention`` with its shared-key term, compiled
by Mosaic (tier-1 runs the same kernel, but in the interpreter), against
scores materialised in float32 at ``highest`` per block of queries, on two
pages of the seed's own corpus (the fullest mixed page with its pads, and the
page that is one 16,384-token document) at the published shape: 64 heads, 128
+ 64 wide scores, 128-wide values. The kernel rounds the softmax's weights to
bfloat16 for their product with the values and its result to bfloat16: a real
token's row may differ by two such roundings, 2**-7 of its norm. Exit 0 when
every real token's row is inside that. Prints the kernel's time a page too.
"""

from __future__ import annotations

import contextlib
import json
import sys

ATTENTION_TOKENS, ATTENTION_BLOCK = None, 512  # the page's own size, the extractor's block
FLOAT8_LEAVES = ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj", "gate_proj", "up_proj",
                 "down_proj")
CELL = "sarvam_105b_bf16.corpus_transcripts"


@contextlib.contextmanager
def without_the_rope_term():
    """``models.sarvam``'s attention kernel given rotated queries of zeros
    for the duration: ``qR·kR`` adds nothing to any score."""
    import jax.numpy as jnp

    from video_features_tpu.models import sarvam as model

    real = model.segment_attention

    def altered(q, k, v, doc, *, q_shared, k_shared, **kw):
        return real(q, k, v, doc, q_shared=jnp.zeros_like(q_shared), k_shared=k_shared, **kw)

    model.segment_attention = altered
    try:
        yield
    finally:
        model.segment_attention = real


def fault(cell_name: str, seed: int) -> int:
    import run as bench_run
    from conftest import ROOT

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    devices = bench_run.require_chips(int(cell["chips"]))
    with without_the_rope_term():
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
    from reference import sarvam as ref
    from weights import make_weights, unflatten

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    bench_run.require_chips(int(cell["chips"]))
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    scratch = os.path.join(ROOT, "output", "benchmark", "sarvam_float8")
    docs = gen.write_corpus(traffic, seed, os.path.join(scratch, "corpus"))
    # the sample `correct` draws: the longest document and check_videos - 1 others
    ctx = types.SimpleNamespace(seed=seed, conf=conf)
    window = {"finished": [os.path.join(scratch, f"w{i:05d}_{os.path.basename(d)}")
                           for i, d in enumerate(docs)]}
    sample = [docs[int(os.path.basename(p)[1:6])] for p in gen.check_sample(ctx, window)]
    tree = unflatten(make_weights(ref.weight_specs()["sarvam"], seed, "sarvam"))

    def rounded(float8_too: bool):
        def leaf(path, a):
            name = getattr(path[-1], "key", "")
            if name == "bias":
                return jnp.asarray(a, jnp.float32)
            a = jnp.asarray(a)
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
        # a choice changed: an expert among a token's eight that the other run did not choose
        changed = [float(np.mean([(lc[:, :, None] != wc[:, None, :]).all(-1).mean()
                                  for wc, lc in zip(w[1], l[1])])) for w, l in zip(want, low)]
        return {"gap": max(float(g.max()) for g in gaps),
                "median": [float(np.median(g)) for g in gaps],
                "choices_changed_share": changed}

    want = run(False, "highest")
    readings = {"float8": against(want, run(True, "highest")),
                "bfloat16_products": against(want, run(False, "default"))}
    limit = conf["limits"]["gap.sarvam"]
    print(json.dumps({"limit": limit, "documents": [os.path.basename(p) for p in sample],
                      **readings}), flush=True)
    return 0 if readings["float8"]["gap"] > limit else 1


def attention(cell_name: str, seed: int) -> int:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import run as bench_run
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen
    from reference.sarvam import PUBLISHED as P
    from video_features_tpu.ops.segment_attention import segment_attention

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    dry = ATTENTION_TOKENS is not None  # a dry run off the chip: the same make-up in small
    if not dry:
        bench_run.require_chips(int(cell["chips"]))
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    tokens, block = ATTENTION_TOKENS or int(traffic["equal_work"]["page_tokens"]), ATTENTION_BLOCK
    heads, d, r = P["num_attention_heads"], P["qk_nope_head_dim"], P["qk_rope_head_dim"]
    pages = gen.pack_pass(gen.document_plan(traffic, seed), int(traffic["equal_work"]["page_tokens"]))
    mixed = max(pages, key=lambda page: (len(page), sum(page)))
    if dry:
        pages, mixed, block = [[tokens]], [tokens // 4, tokens // 8, tokens // 2 - 3], 128

    @jax.jit
    def plain(q, k, v, qs, ks, doc):
        step = min(block, 128)  # scores of a step: 64 heads x 128 x 16,384 x 4 B = 0.5 GB
        qh = q.astype(jnp.float32).reshape(tokens // step, step, heads, d)
        qsh = qs.astype(jnp.float32).reshape(tokens // step, step, heads, r)
        kh, vh = (a.astype(jnp.float32).reshape(tokens, heads, d) for a in (k, v))
        cols = jnp.arange(tokens)

        def one(args):
            qb, qsb, start = args
            rows = start + jnp.arange(step)
            mask = (doc[rows][:, None] == doc[None, :]) & (cols[None, :] <= rows[:, None])
            with jax.default_matmul_precision("highest"):
                s = (jnp.einsum("qhd,khd->hqk", qb, kh)
                     + jnp.einsum("qhr,kr->hqk", qsb, ks.astype(jnp.float32)))
                w = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
                return jnp.einsum("hqk,khd->qhd", w, vh)

        out = lax.map(one, (qh, qsh, jnp.arange(0, tokens, step)))
        return out.reshape(tokens, heads * d)

    readings, bound = {}, 2.0 ** -7
    key = jax.random.PRNGKey(seed % (2 ** 31))
    for page_name, page in (("mixed", mixed), ("one_document", max(pages, key=sum))):
        doc = np.full(tokens, -1, np.int32)
        doc[:sum(page)] = np.repeat(np.arange(len(page)), page)
        key, kq, kk, kv, kqs, kks = jax.random.split(key, 6)
        scale = (d + r) ** -0.5
        q = (jax.random.normal(kq, (tokens, heads * d), jnp.float32) * scale).astype(jnp.bfloat16)
        qs = (jax.random.normal(kqs, (tokens, heads * r), jnp.float32) * scale).astype(jnp.bfloat16)
        k, v = (jax.random.normal(x, (tokens, heads * d), jnp.bfloat16) for x in (kk, kv))
        ks = jax.random.normal(kks, (tokens, r), jnp.bfloat16)
        run = lambda: segment_attention(  # noqa: E731
            q, k, v, jnp.asarray(doc), kv_heads=heads, head_dim=d, block=block,
            interpret=dry, q_shared=qs, k_shared=ks)
        got = run().block_until_ready()
        times = []
        for _ in range(1 if dry else 5):
            t0 = time.perf_counter()
            run().block_until_ready()
            times.append(time.perf_counter() - t0)
        want = np.asarray(plain(q, k, v, qs, ks, jnp.asarray(doc)))[doc >= 0]
        got = np.asarray(got.astype(jnp.float32))[doc >= 0]
        gaps = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
        readings[page_name] = {"documents": list(map(int, page)), "heads": heads,
                               "worst_row": float(gaps.max()),
                               "median_row": float(np.median(gaps)),
                               "kernel_ms_host_clock": round(1e3 * min(times), 3)}
    ok = all(x["worst_row"] <= bound for x in readings.values())
    print(json.dumps({"tokens": tokens, "bound": bound, "inside": ok, **readings}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    import conftest  # noqa: F401 — puts the checkout and benchmark/ on the path

    kind, seed = sys.argv[1], int(sys.argv[2])
    cell = sys.argv[3] if len(sys.argv) > 3 else CELL
    sys.exit({"fault": fault, "float8": float8, "attention": attention}[kind](cell, seed))

"""The readings that ``gap.qwen3_next``'s limit lies between, the planted
faults, and the delta rule's kernel alone, taken on the chip at the cell's own
size (``qwen3_next_80b_bf16``); none is a flag of the program.

    chiprun -- python3 benchmark/tests/qwen3_next_readings.py fault <seed> [carry|delta]
    chiprun -- python3 benchmark/tests/qwen3_next_readings.py float8 <seed>
    chiprun -- python3 benchmark/tests/qwen3_next_readings.py core <seed>

``fault``: the cell's own run with one of the delta rule's own faults planted
in the program, so that everything after it is the program's. ``carry``: the
state dropped between chunks (``ops/gated_delta.chunk_edges`` says that no
chunk's tokens continue the document before it). ``delta``: the correction
left out, ``δ = βv`` (the model's call of the kernel answered by decayed linear
attention, written here in plain ``jax.numpy``). Exit 0 when the run is NOT
correct.

``float8``: the nearest precision below the one the configuration states.
The reference against itself: once as ``correct`` takes it (weights rounded to
bfloat16) and once with every projection and expert matrix (``FLOAT8_LEAVES``;
not the embedding, the norms, the convolution, ``b``/``a``, the router or the
shared expert's gate) rounded to ``float8_e4m3fn`` first, on the documents
``correct`` would sample. No program runs. Prints ``gap.qwen3_next`` as
``check.row_gaps`` computes it; exit 0 when it is over the cell's limit.
Beside it: the same reference with its products at the default precision, how
many of the router's choices changed, and the distribution of the decay ``α``
over the sampled documents' tokens, heads and linear layers.

``core``: ``ops/gated_delta`` compiled by Mosaic (tier-1 runs the same kernel,
but in the interpreter) against step 5 run token by token in float32 at
``highest`` on each document alone, on two pages of the seed's own corpus (the
fullest mixed page with its pads, and the page that is one 16,384-token
document) at the published shape: 16 key heads, 32 value heads, 128 wide.
Both sides get the same bfloat16 ``q, k, v`` and make the unit rows in float32;
the kernel rounds the unit rows, the inverse, the deltas and the state to
bfloat16 for their products: a real token's row may differ by two such
roundings, 2**-7 of its norm. Exit 0 when every real
token's row is inside that. Prints the kernel's time a page too.
"""

from __future__ import annotations

import contextlib
import json
import sys

CORE_TOKENS = None  # the page's own size; a dry run off the chip sets a small one
FLOAT8_LEAVES = ("q_proj", "k_proj", "v_proj", "z_proj", "out_proj", "o_proj", "gate_proj",
                 "up_proj", "down_proj")
CELL = "qwen3_next_80b_bf16.corpus_transcripts"


def decayed_linear_attention(qkv, g, beta, doc, *, key_heads, interpret=False, chunk=64):
    """The delta rule WITHOUT its correction, ``δ_t = β_t v_t``: ``S ← α_t S +
    k_t (β_t v_t)ᵀ``, ``o_t = Sᵀ q_t`` over unit rows, restarting at every
    document; chunked, float32, plain ``jax.numpy``; the kernel's arguments."""
    import jax.numpy as jnp
    from jax import lax

    del interpret
    tokens, heads = g.shape
    width = qkv.shape[1] // (2 * key_heads + heads)
    per, n, f32 = heads // key_heads, tokens // chunk, jnp.float32
    q, k, v = jnp.split(qkv, [key_heads * width, 2 * key_heads * width], axis=1)

    def to_heads(a, scale):
        a = a.astype(f32).reshape(n, chunk, key_heads, width)
        a = a * (lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6) * scale)
        return jnp.repeat(a, per, 2)

    total = jnp.cumsum(g.astype(f32).reshape(n, chunk, heads), axis=1)
    docs = doc.reshape(n, chunk)
    before = jnp.concatenate([jnp.full((1,), -2, doc.dtype), docs[:-1, -1]])
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, xs):
        qc, kc, vc, gc, bc, dc, carried = xs
        together = (dc[:, None] == dc[None, :]) & lower
        decay = jnp.where(together[None], jnp.exp(jnp.minimum(
            gc.T[:, :, None] - gc.T[:, None, :], 0.0)), 0.0)          # (heads, chunk, chunk)
        delta = bc[:, :, None] * vc                                      # (chunk, heads, width)
        from_start = jnp.exp(gc) * (dc == carried)[:, None]             # (chunk, heads)
        o = (jnp.einsum("chk,hkv->chv", qc * from_start[:, :, None], state)
             + jnp.einsum("hce,ehv->chv", jnp.einsum("chk,ehk->hce", qc, kc) * decay, delta))
        to_end = jnp.exp(gc[-1] - gc) * (dc == dc[-1])[:, None]
        state = (state * (jnp.exp(gc[-1]) * (carried == dc[-1]))[:, None, None]
                 + jnp.einsum("chk,chv->hkv", kc * to_end[:, :, None], delta))
        return state, o

    xs = (to_heads(q, width ** -0.5), to_heads(k, 1.0),
          v.astype(f32).reshape(n, chunk, heads, width), total,
          beta.astype(f32).reshape(n, chunk, heads), docs, before)
    _, out = lax.scan(step, jnp.zeros((heads, width, width), f32), xs)
    return out.reshape(tokens, heads * width).astype(qkv.dtype)


@contextlib.contextmanager
def planted(kind: str):
    """``carry``: no chunk continues the document before it. ``delta``: the
    model's kernel call answered without the correction."""
    import jax.numpy as jnp

    from video_features_tpu.models import qwen3_next as model
    from video_features_tpu.ops import gated_delta as op

    if kind == "carry":
        owner, name, real = op, "chunk_edges", op.chunk_edges

        def altered(doc, chunk):
            edges = real(doc, chunk)
            return edges.at[0].set(jnp.full_like(edges[0], -2))
    elif kind == "delta":
        owner, name, altered = model, "gated_delta", decayed_linear_attention
    else:
        raise SystemExit(f"no fault {kind!r}: carry or delta")
    real_value = getattr(owner, name)
    setattr(owner, name, altered)
    try:
        yield
    finally:
        setattr(owner, name, real_value)


def fault(cell_name: str, seed: int, kind: str = "carry") -> int:
    import run as bench_run
    from conftest import ROOT

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    devices = bench_run.require_chips(int(cell["chips"]))
    with planted(kind):
        result = bench_run.run_cell(bench, cell, seed, float(bench["run_seconds"]), False,
                                    devices=devices)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(dict(result, fault=kind)), flush=True)
    return 0 if result["correct"] is False else 1


def sampled_documents(cell_name: str, seed: int, scratch_name: str):
    """The documents ``correct`` would sample for ``seed`` (the longest and
    ``check_videos`` - 1 others), written under ``output/``."""
    import os
    import types

    import run as bench_run
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    scratch = os.path.join(ROOT, "output", "benchmark", scratch_name)
    docs = gen.write_corpus(traffic, seed, os.path.join(scratch, "corpus"))
    ctx = types.SimpleNamespace(seed=seed, conf=conf)
    window = {"finished": [os.path.join(scratch, f"w{i:05d}_{os.path.basename(d)}")
                           for i, d in enumerate(docs)]}
    return cell, conf, [docs[int(os.path.basename(p)[1:6])] for p in gen.check_sample(ctx, window)]


def float8(cell_name: str, seed: int) -> int:
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from check import row_gaps
    from reference import qwen3_next as ref
    from weights import make_weights, unflatten

    cell, conf, sample = sampled_documents(cell_name, seed, "qwen3_next_float8")
    bench_run.require_chips(int(cell["chips"]))
    tree = unflatten(make_weights(ref.weight_specs()["qwen3_next"], seed, "qwen3_next"))

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
        # a choice changed: an expert among a token's ten that the other run did not choose
        changed = [float(np.mean([(lc[:, :, None] != wc[:, None, :]).all(-1).mean()
                                  for wc, lc in zip(w[1], l[1])])) for w, l in zip(want, low)]
        return {"gap": max(float(g.max()) for g in gaps),
                "median": [float(np.median(g)) for g in gaps],
                "choices_changed_share": changed}

    want = run(False, "highest")
    alpha = np.concatenate([a.reshape(-1) for w in want for a in w[2]])
    readings = {"float8": against(want, run(True, "highest")),
                "bfloat16_products": against(want, run(False, "default")),
                "alpha": {"quantiles_1_10_50_90_99": [float(x) for x in np.quantile(
                    alpha, [0.01, 0.1, 0.5, 0.9, 0.99])], "mean": float(alpha.mean()),
                    "tokens_to_forget_to_1pct_at_median": float(np.log(0.01) / np.log(np.median(alpha)))}}
    limit = conf["limits"]["gap.qwen3_next"]
    print(json.dumps({"limit": limit, "documents": [os.path.basename(p) for p in sample],
                      **readings}), flush=True)
    return 0 if readings["float8"]["gap"] > limit else 1


def core(cell_name: str, seed: int) -> int:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen
    from reference import qwen3_next as ref
    from video_features_tpu.ops.gated_delta import gated_delta

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    dry = CORE_TOKENS is not None  # a dry run off the chip: the same make-up in small
    if not dry:
        bench_run.require_chips(int(cell["chips"]))
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    tokens = CORE_TOKENS or int(traffic["equal_work"]["page_tokens"])
    P = ref.PUBLISHED
    kh, vh, d = P["linear_num_key_heads"], P["linear_num_value_heads"], P["linear_key_head_dim"]
    pages = gen.pack_pass(gen.document_plan(traffic, seed), int(traffic["equal_work"]["page_tokens"]))
    mixed = max(pages, key=lambda page: (len(page), sum(page)))
    if dry:
        pages, mixed = [[tokens]], [tokens // 4, 1, tokens // 8, tokens // 2 - 3]
    recurrence = jax.jit(ref.delta_rule)

    def unit_rows(x, scale):
        x = x.astype(jnp.float32).reshape(tokens, kh, d)
        return x * (scale / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6))

    readings, bound = {}, 2.0 ** -7
    key = jax.random.PRNGKey(seed % (2 ** 31))
    for page_name, page in (("mixed", mixed), ("one_document", max(pages, key=sum))):
        doc = np.full(tokens, -1, np.int32)
        doc[:sum(page)] = np.repeat(np.arange(len(page)), page)
        key, kq, kk, kv, kg, kb = jax.random.split(key, 6)
        q, k = (1.5 * jax.random.normal(x, (tokens, kh * d), jnp.float32) for x in (kq, kk))
        v = jax.random.normal(kv, (tokens, vh * d), jnp.float32)
        qkv = jnp.concatenate([q, k, v], axis=1).astype(jnp.bfloat16)
        g = -jax.nn.softplus(1.41 * jax.random.normal(kg, (tokens, vh), jnp.float32))
        beta = jax.nn.sigmoid(1.41 * jax.random.normal(kb, (tokens, vh), jnp.float32))
        run = lambda: gated_delta(qkv, g, beta, jnp.asarray(doc), key_heads=kh,  # noqa: E731
                                  interpret=dry)
        got = run().block_until_ready()
        times = []
        for _ in range(1 if dry else 5):
            t0 = time.perf_counter()
            run().block_until_ready()
            times.append(time.perf_counter() - t0)
        got = np.asarray(got.astype(jnp.float32)).reshape(tokens, vh, d)
        assert np.isfinite(got).all()  # the pads too
        gaps, at = [], 0
        qb, kb, vb = jnp.split(qkv, [kh * d, 2 * kh * d], axis=1)
        qh = jnp.repeat(unit_rows(qb, d ** -0.5), vh // kh, axis=1)
        kh_ = jnp.repeat(unit_rows(kb, 1.0), vh // kh, axis=1)
        vh_ = vb.astype(jnp.float32).reshape(tokens, vh, d)
        with jax.default_matmul_precision("highest"):
            for n in page:
                sl = slice(at, at + n)
                at += n
                want = np.asarray(recurrence(qh[sl], kh_[sl], vh_[sl], g[sl], beta[sl]))
                gaps.append(np.linalg.norm((got[sl] - want).reshape(n, -1), axis=1)
                            / np.linalg.norm(want.reshape(n, -1), axis=1))
        gaps = np.concatenate(gaps)
        readings[page_name] = {"documents": list(map(int, page)), "value_heads": vh,
                               "worst_row": float(gaps.max()),
                               "median_row": float(np.median(gaps)),
                               "kernel_ms_host_clock": round(1e3 * min(times), 3)}
    ok = all(x["worst_row"] <= bound for x in readings.values())
    print(json.dumps({"tokens": tokens, "bound": bound, "inside": ok, **readings}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    import conftest  # noqa: F401 — puts the checkout and benchmark/ on the path

    kind, seed = sys.argv[1], int(sys.argv[2])
    if kind == "fault":
        sys.exit(fault(CELL, seed, *sys.argv[3:4]))
    sys.exit({"float8": float8, "core": core}[kind](CELL, seed))

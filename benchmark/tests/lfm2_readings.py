"""The readings that ``gap.lfm2``'s limit lies between and the planted faults,
taken on a TPU at the cell's own size (``lfm2_24b_a2b_bf16``); none is a flag
of the program.

    python3 benchmark/tests/lfm2_readings.py sweep <seed> [<seed> ...]
    python3 benchmark/tests/lfm2_readings.py float8 <seed>
    python3 benchmark/tests/lfm2_readings.py fault <seed> restart|bias

``sweep``: the program's page against the reference on many seeds in one
process, with no checkpoint written: for each seed the weights ``correct``
draws, two pages of the seed's first pass packed as the traffic packs them
(the longest document alone, and the page of several documents), the page
program run on them as the cell runs it, and every document of the two pages
against the reference, ``gap.lfm2`` as ``check.row_gaps`` computes it. Beside
it, on the same pages: both faults planted in the program (``restart``: the
short convolution not restarted at a document inside a page; ``bias``: the
expert bias in the router's weights as well as its choice), all-zero
features, and the page program's time on the chip (the mixed page, the least
of five calls on the host's clock). One JSON line a seed; exit 0 when every
sound reading is inside the cell's limit and every planted one over it.

``float8``: the nearest precision below the one the configuration states.
The reference against itself: once as ``correct`` takes it (weights rounded to
bfloat16) and once with every projection and expert matrix
(``FLOAT8_LEAVES``; not the embedding, the norms, the convolution's taps, the
router or the bias) rounded to ``float8_e4m3fn`` first, on the documents
``correct`` would sample. No program runs. Prints ``gap.lfm2`` as
``check.row_gaps`` computes it; exit 0 when it is over the cell's limit.
Beside it: the same reference with its activations float32.

``fault``: the cell's own run with one fault planted in the program. Exit 0
when the run is NOT correct.
"""

from __future__ import annotations

import contextlib
import json
import sys

SWEEP = None  # (model config, reference config, page tokens, documents) of a dry run off the chip
FLOAT8_LEAVES = ("in_proj", "out_proj", "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                 "up_proj", "down_proj")
FAULTS = ("restart", "bias")
CELL = "lfm2_24b_a2b_bf16.corpus_transcripts_64k"


def unrestarted(pos):
    """The ``pos`` plane the short convolution is given when it does not
    restart at a document inside a page: no token but the page's first is a
    document's first or second."""
    import jax.numpy as jnp

    from video_features_tpu.models import lfm2 as model

    at = jnp.arange(pos.shape[0])
    return jnp.where(at == 0, 0, jnp.maximum(pos, model.PUBLISHED.conv_L_cache - 1))


def biased_route(cfg, p, h):
    """The router with the expert bias in the weights as well as the choice."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.dot(h, p["router"], preferred_element_type=jnp.float32))
    top, experts = jax.lax.top_k(scores + p["router_bias"], cfg.num_experts_per_tok)
    return (top / (top.sum(-1, keepdims=True) + cfg.renorm_eps) * cfg.routed_scaling_factor,
            experts.astype(jnp.int32))


@contextlib.contextmanager
def planted(kind: str):
    """The model's conv mixer given :func:`unrestarted`'s ``pos``
    (``restart``), or its router :func:`biased_route` (``bias``)."""
    from video_features_tpu.models import lfm2 as model

    if kind not in FAULTS:
        raise SystemExit(f"no fault {kind!r}: {' or '.join(FAULTS)}")
    name = {"restart": "short_conv", "bias": "route"}[kind]
    real = getattr(model, name)
    altered = ((lambda cfg, p, x, pos: real(cfg, p, x, unrestarted(pos))) if kind == "restart"
               else biased_route)
    setattr(model, name, altered)
    try:
        yield
    finally:
        setattr(model, name, real)


def fault(cell_name: str, seed: int, kind: str) -> int:
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
    from reference import lfm2 as ref
    from weights import make_weights, unflatten

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    bench_run.require_chips(int(cell["chips"]))
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    scratch = os.path.join(ROOT, "output", "benchmark", "lfm2_float8")
    docs = gen.write_corpus(traffic, seed, os.path.join(scratch, "corpus"))
    ctx = types.SimpleNamespace(seed=seed, conf=conf)
    window = {"finished": [os.path.join(scratch, f"w{i:05d}_{os.path.basename(d)}")
                           for i, d in enumerate(docs)]}
    sample = [docs[int(os.path.basename(p)[1:6])] for p in gen.check_sample(ctx, window)]
    tree = unflatten(make_weights(ref.weight_specs()["lfm2"], seed, "lfm2"))

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

    def run(float8_too: bool, activations: str = "bfloat16"):
        features = ref.make_forward(rounded(float8_too), activations=activations)
        out = []
        for path in sample:
            with np.load(path) as z:
                out.append(features(z["ids"], z["segment_ends"]))
        return out

    def against(want, low):
        gaps = [row_gaps(l, w) for w, l in zip(want, low)]
        return {"gap": max(float(g.max()) for g in gaps),
                "median": [float(np.median(g)) for g in gaps]}

    want = run(False)
    readings = {"float8": against(want, run(True)),
                "float32_activations": against(want, run(False, "float32"))}
    limit = conf["limits"]["gap.lfm2"]
    print(json.dumps({"seed": seed, "limit": limit,
                      "documents": [os.path.basename(p) for p in sample], **readings}), flush=True)
    return 0 if readings["float8"]["gap"] > limit else 1


def sweep_pages(traffic: dict, seed: int, scratch: str):
    """The two pages a seed's sweep reads: the longest document alone, and
    the page of its first pass with the most documents. → [[(ids,
    segment_ends), …], …]."""
    import os

    import numpy as np

    from generators import corpus_tokens as gen

    tokens = int(traffic["equal_work"]["page_tokens"])
    plan = gen.document_plan(traffic, seed)
    paths = gen.write_corpus(traffic, seed, os.path.join(scratch, "corpus"))
    by_length = dict(zip(plan, paths))  # a pass's lengths are distinct
    pages = gen.pack_pass(plan, tokens)
    chosen = [max(pages, key=sum), max(pages, key=lambda page: (len(page), sum(page)))]

    def read(path):
        with np.load(path) as z:
            return z["ids"], z["segment_ends"]

    return tokens, [[read(by_length[n]) for n in page] for page in chosen]


def sweep(cell_name: str, seeds) -> int:
    import functools
    import gc
    import os
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from check import row_gaps
    from conftest import BENCH, ROOT
    from reference import lfm2 as ref
    from video_features_tpu.extractors.token_pages import ATTENTION_BLOCK, SEGMENT_TOKENS_MIN
    from video_features_tpu.models import lfm2 as model
    from video_features_tpu.parallel.pages import build_token_page
    from weights import make_weights, unflatten

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    limit = conf["limits"]["gap.lfm2"]
    if SWEEP is None:
        bench_run.require_chips(int(cell["chips"]))
        traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
        cfg, ref_cfg, interpret = model.PUBLISHED, ref.PUBLISHED, False
        layers, experts = ref.LAYERS, ref.EXPERTS
    else:
        cfg, ref_cfg, tokens, dry_pages = SWEEP
        interpret = True
        layers, experts = tuple(range(cfg.num_hidden_layers)), tuple(range(cfg.num_experts))
    scratch = os.path.join(ROOT, "output", "benchmark", "lfm2_sweep")
    compiled = {}
    ok = True
    for seed in seeds:
        t0 = time.perf_counter()
        if SWEEP is None:
            tokens, pages = sweep_pages(traffic, seed, scratch)
        else:
            pages = dry_pages
        block, rows_a_page = min(ATTENTION_BLOCK, tokens), tokens // SEGMENT_TOKENS_MIN  # as the extractor's
        built = []
        for docs in pages:
            page = np.zeros((4, tokens), np.int32)
            table = np.zeros((rows_a_page, 3), np.int32)
            slices = build_token_page([(0, ids, ends) for ids, ends in docs], page, table)
            built.append((jnp.asarray(page), slices))
        flat = make_weights(ref.weight_specs(ref_cfg, layers, experts)["lfm2"], seed, "lfm2")
        params, share = model.stack_checkpoint(cfg, list(flat), flat.__getitem__)
        t_weights = time.perf_counter() - t0

        def program(fault: str = ""):
            if fault not in compiled:  # one compile for every seed
                compiled[fault] = jax.jit(functools.partial(
                    model.forward, cfg, share, rows_a_page, block, interpret=interpret))
            forward = compiled[fault]
            with planted(fault) if fault else contextlib.nullcontext():
                out = [np.asarray(forward(params, page)[0]) for page, _slices in built]
            return [[rows[s] for s in slices] for rows, (_page, slices) in zip(out, built)]

        got = program()
        faults = {kind: program(kind) for kind in FAULTS}
        times = []
        for _ in range(1 if interpret else 5):
            t1 = time.perf_counter()
            compiled[""](params, built[-1][0])[0].block_until_ready()
            times.append(time.perf_counter() - t1)
        t_program = time.perf_counter() - t0 - t_weights
        del params
        gc.collect()
        tree = ref.round_weights(unflatten(flat))
        del flat
        features = ref.make_forward(tree, ref_cfg)
        want = [[features(ids, ends) for ids, ends in docs] for docs in pages]
        del tree, features
        gc.collect()

        def reading(have):
            gaps = [row_gaps(h, w) for hp, wp in zip(have, want) for h, w in zip(hp, wp)]
            return {"gap": max(float(g.max()) for g in gaps),
                    "medians": [round(float(np.median(g)), 4) for g in gaps]}

        line = {"seed": seed, "limit": limit,
                "pages": [[len(ids) for ids, _ in docs] for docs in pages],
                "program": reading(got), **{kind: reading(f) for kind, f in faults.items()},
                "zeros": reading([[np.zeros_like(h) for h in hp] for hp in got]),
                "page_ms_host_clock": round(1e3 * min(times), 2),
                "weights_s": round(t_weights, 1), "program_s": round(t_program, 1),
                "seed_s": round(time.perf_counter() - t0, 1)}
        ok &= (line["program"]["gap"] <= limit and line["zeros"]["gap"] > limit
               and all(line[kind]["gap"] > limit for kind in FAULTS))
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    import conftest  # noqa: F401 — puts the checkout and benchmark/ on the path

    kind, seed = sys.argv[1], int(sys.argv[2])
    if kind == "sweep":
        sys.exit(sweep(CELL, [int(a) for a in sys.argv[2:]]))
    if kind == "fault":
        sys.exit(fault(CELL, seed, sys.argv[3]))
    sys.exit(float8(CELL, seed))

"""The readings that ``gap.jamba``'s limit lies between, the planted faults, and
the selective scan's kernel alone, taken on a TPU at the cell's own size
(``jamba2_3b_bf16``); none is a flag of the program.

    python3 benchmark/tests/jamba_readings.py fault <seed> [reset|carry]
    python3 benchmark/tests/jamba_readings.py float8 <seed>
    python3 benchmark/tests/jamba_readings.py scan <seed>
    python3 benchmark/tests/jamba_readings.py sweep <seed> [<seed> ...]

``fault``: the cell's own run with one of the scan's faults planted in the
program, so that everything after it is the program's: the model's call of
``selective_scan`` is given another ``pos`` plane (the convolution keeps the
page's). ``reset``: no token but the page's first restarts the state, so a
document that follows another in a page starts from the state its neighbour
left. ``carry``: the state is dropped every ``FAULT_CHUNK`` tokens of the page as
well. Exit 0 when the run is NOT correct.

``float8``: the nearest precision below the one the configuration states.
The reference against itself: once as ``correct`` takes it (weights rounded to
bfloat16) and once with every projection matrix (``FLOAT8_LEAVES``; not the
embedding, the norms, ``D``, the convolution, ``A_log`` or the biases) rounded
to ``float8_e4m3fn`` first, on the documents ``correct`` would sample. No
program runs. Prints ``gap.jamba`` as ``check.row_gaps`` computes it; exit 0
when it is over the cell's limit. Beside it: the same reference with its
activations float32 (nothing rounded between the products).

``sweep``: the program's page against the reference on many seeds in one
process, with no checkpoint written: for each seed the weights ``correct``
draws, two pages of the seed's first pass packed as the traffic packs them
(the longest document alone, and the fullest page of several documents), the
page program run on them as the cell runs it, and every document of the two
pages against the reference, ``gap.jamba`` as ``check.row_gaps`` computes it.
Beside it, on the same pages: the ``reset`` fault planted, all-zero features,
and on the first seed the program and the reference cut after ``DEPTHS``
layers (where the gap grows). One JSON line a seed; exit 0 when every sound
reading is inside the cell's limit and every planted one over it.

``scan``: ``ops/selective_scan`` compiled by Mosaic (tier-1 runs the same
kernel, but in the interpreter) against the reference's scan run token by
token in float32 on each document alone, on two pages of the seed's own
corpus (the fullest mixed page with its pads, and the page that is one
16,384-token document) at the published shape: 5,120 channels, 16 states. Both
sides get the same bfloat16 ``u`` and ``z`` and float32 ``Δ``, ``B``, ``C``; the
kernel writes its output in bfloat16: a real token's row may differ by that
rounding, 2**-8 of its norm. Exit 0 when every real token's row is inside
2**-7. Prints the kernel's time a page too.
"""

from __future__ import annotations

import contextlib
import json
import sys

SCAN_TOKENS = None  # the page's own size; a dry run off the chip sets a small one
SCAN_WIDTH = None   # the published 5,120 channels; a dry run sets fewer
FAULT_CHUNK = 256
DEPTHS = (1, 2, 7, 14)
SWEEP = None  # (model config, reference config, page tokens, documents) of a dry run off the chip
FLOAT8_LEAVES = ("in_proj", "x_proj", "kernel", "out_proj", "q_proj", "k_proj", "v_proj",
                 "o_proj", "gate_proj", "up_proj", "down_proj")
CELL = "jamba2_3b_bf16.corpus_transcripts_64k"


def fault_pos(kind: str, pos):
    """The ``pos`` plane the scan is given under a planted fault."""
    import jax.numpy as jnp

    at = jnp.arange(pos.shape[0])
    if kind == "reset":
        return jnp.where(at == 0, 0, jnp.maximum(pos, 1))
    return jnp.where(at % FAULT_CHUNK == 0, 0, pos)


@contextlib.contextmanager
def planted(kind: str):
    """The model's call of the scan answered with the altered ``pos``."""
    from video_features_tpu.models import jamba as model

    if kind not in ("reset", "carry"):
        raise SystemExit(f"no fault {kind!r}: reset or carry")
    real = model.selective_scan

    def altered(u, dt, b, c, a, d, gate, pos, **kw):
        return real(u, dt, b, c, a, d, gate, fault_pos(kind, pos), **kw)

    model.selective_scan = altered
    try:
        yield
    finally:
        model.selective_scan = real


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
    from reference import jamba as ref
    from weights import make_weights, unflatten

    cell, conf, sample = sampled_documents(cell_name, seed, "jamba_float8")
    bench_run.require_chips(int(cell["chips"]))
    tree = unflatten(make_weights(ref.weight_specs()["jamba"], seed, "jamba"))

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
    limit = conf["limits"]["gap.jamba"]
    print(json.dumps({"limit": limit, "documents": [os.path.basename(p) for p in sample],
                      **readings}), flush=True)
    return 0 if readings["float8"]["gap"] > limit else 1


def scan(cell_name: str, seed: int) -> int:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from conftest import BENCH, ROOT
    from generators import corpus_tokens as gen
    from reference import jamba as ref
    from video_features_tpu.ops.selective_scan import selective_scan

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    dry = SCAN_TOKENS is not None  # a dry run off the chip: the same make-up in small
    if not dry:
        bench_run.require_chips(int(cell["chips"]))
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    tokens = SCAN_TOKENS or int(traffic["equal_work"]["page_tokens"])
    P = ref.PUBLISHED
    width, state = SCAN_WIDTH or P["mamba_expand"] * P["hidden_size"], P["mamba_d_state"]
    pages = gen.pack_pass(gen.document_plan(traffic, seed), int(traffic["equal_work"]["page_tokens"]))
    mixed = max(pages, key=lambda page: (len(page), sum(page)))
    if dry:
        pages, mixed = [[tokens]], [tokens // 4, 1, tokens // 8, tokens // 2 - 3]
    recurrence = jax.jit(ref.selective_scan)

    readings, bound = {}, 2.0 ** -7
    key = jax.random.PRNGKey(seed % (2 ** 31))
    for page_name, page in (("mixed", mixed), ("one_document", max(pages, key=sum))):
        pos = np.zeros(tokens, np.int32)
        at = 0
        for n in page:
            pos[at:at + n] = np.arange(n)
            at += n
        key, ku, kd, kb, kc, ka, kz = jax.random.split(key, 7)
        u = jax.random.normal(ku, (tokens, width), jnp.float32).astype(jnp.bfloat16)
        dt = jax.nn.softplus(1.41 * jax.random.normal(kd, (tokens, width), jnp.float32))
        b, c = (jax.random.normal(k, (tokens, state), jnp.float32) for k in (kb, kc))
        a = -jnp.exp(0.05 * jax.random.normal(ka, (state, width), jnp.float32))
        d = jnp.ones((width,), jnp.float32)
        z = jax.random.normal(kz, (tokens, width), jnp.float32).astype(jnp.bfloat16)
        run = lambda: selective_scan(u, dt, b, c, a, d, z, jnp.asarray(pos),  # noqa: E731
                                     interpret=dry)
        got = run().block_until_ready()
        times = []
        for _ in range(1 if dry else 5):
            t0 = time.perf_counter()
            run().block_until_ready()
            times.append(time.perf_counter() - t0)
        got = np.asarray(got.astype(jnp.float32))
        assert np.isfinite(got).all()  # the pads too
        gaps, at = [], 0
        uf, zf = u.astype(jnp.float32), z.astype(jnp.float32)
        zero = jnp.zeros((width, state), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for n in page:
                sl = slice(at, at + n)
                at += n
                y, _last = recurrence(uf[sl], dt[sl], b[sl], c[sl], a.T, d, zero)
                want = np.asarray(y * jax.nn.silu(zf[sl]))
                gaps.append(np.linalg.norm(got[sl] - want, axis=1) / np.linalg.norm(want, axis=1))
        gaps = np.concatenate(gaps)
        readings[page_name] = {"documents": list(map(int, page)),
                               "worst_row": float(gaps.max()),
                               "median_row": float(np.median(gaps)),
                               "kernel_ms_host_clock": round(1e3 * min(times), 3)}
    ok = all(x["worst_row"] <= bound for x in readings.values())
    print(json.dumps({"tokens": tokens, "width": width, "bound": bound, "inside": ok, **readings}),
          flush=True)
    return 0 if ok else 1


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
    from reference import jamba as ref
    from video_features_tpu.extractors.token_pages import ATTENTION_BLOCK, SEGMENT_TOKENS_MIN
    from video_features_tpu.models import jamba as model
    from video_features_tpu.parallel.pages import build_token_page
    from weights import make_weights, unflatten

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, cell_name)
    conf = bench_run.load_json(BENCH, "configs", cell["config"] + ".json")
    limit = conf["limits"]["gap.jamba"]
    if SWEEP is None:
        bench_run.require_chips(int(cell["chips"]))
        traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
        cfg, ref_cfg, interpret = model.PUBLISHED, ref.PUBLISHED, False
    else:
        cfg, ref_cfg, tokens, dry_pages = SWEEP
        interpret = True
    scratch = os.path.join(ROOT, "output", "benchmark", "jamba_sweep")
    compiled = {}
    ok = True
    for index, seed in enumerate(seeds):
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
        flat = make_weights(ref.weight_specs(ref_cfg, range(cfg.num_hidden_layers))["jamba"],
                            seed, "jamba")
        params, _share = model.stack_checkpoint(cfg, list(flat), flat.__getitem__)
        depths = (cfg.num_hidden_layers,) + (DEPTHS if index == 0 else ())

        def program(depth: int, fault: str = ""):
            cut = dict(params, layers=params["layers"][:depth])
            if (depth, fault) not in compiled:  # one compile for every seed
                share = model.Share(tuple(range(depth)), ())
                compiled[depth, fault] = jax.jit(functools.partial(
                    model.forward, cfg, share, rows_a_page, block, interpret=interpret))
            forward = compiled[depth, fault]
            with planted(fault) if fault else contextlib.nullcontext():
                out = [np.asarray(forward(cut, page)[0]) for page, _slices in built]
            return [[rows[s] for s in slices] for rows, (_page, slices) in zip(out, built)]

        got = {d: program(d) for d in depths}
        reset = program(cfg.num_hidden_layers, "reset")
        t_program = time.perf_counter() - t0
        del params
        gc.collect()
        tree = ref.round_weights(unflatten(flat))
        del flat
        want = {}
        for d in depths:
            cut = dict(tree, layers={k: v for k, v in tree["layers"].items() if int(k) < d})
            features = ref.make_forward(cut, ref_cfg)
            want[d] = [[features(ids, ends) for ids, ends in docs] for docs in pages]
        del tree
        gc.collect()

        def reading(have, d):
            gaps = [row_gaps(h, w) for hp, wp in zip(have, want[d]) for h, w in zip(hp, wp)]
            return {"gap": max(float(g.max()) for g in gaps),
                    "medians": [round(float(np.median(g)), 4) for g in gaps]}

        full = cfg.num_hidden_layers
        line = {"seed": seed, "limit": limit,
                "pages": [[len(ids) for ids, _ in docs] for docs in pages],
                "program": reading(got[full], full), "reset": reading(reset, full),
                "zeros": reading([[np.zeros_like(h) for h in hp] for hp in got[full]], full),
                "depths": {d: reading(got[d], d)["gap"] for d in depths if d != full},
                "program_s": round(t_program, 1), "seed_s": round(time.perf_counter() - t0, 1)}
        ok &= (line["program"]["gap"] <= limit and line["reset"]["gap"] > limit
               and line["zeros"]["gap"] > limit)
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    import conftest  # noqa: F401 — puts the checkout and benchmark/ on the path

    kind, seed = sys.argv[1], int(sys.argv[2])
    if kind == "sweep":
        sys.exit(sweep(CELL, [int(a) for a in sys.argv[2:]]))
    if kind == "fault":
        sys.exit(fault(CELL, seed, *sys.argv[3:4]))
    sys.exit({"float8": float8, "scan": scan}[kind](CELL, seed))

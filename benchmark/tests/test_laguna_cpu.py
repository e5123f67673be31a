"""The text stream's benchmark pieces on the CPU: the generator's plan, the
operation counter against a count made another way, and each new reader on a
hand-built reduction (planes, span records and counters as the program and
the profiler give them)."""

import numpy as np
import pytest

import trace_reduce as tr
from flops import laguna as counter
from generators import corpus_tokens as gen
from layer_metrics import (_spans, attention_pct, attn_core_roofline, expert_load_max_over_mean,
                           moe_dispatch_pct, moe_experts_roofline)
from run import load_json
from conftest import BENCH

TRAFFIC = load_json(BENCH, "traffic", "corpus_transcripts.json")
PEAKS = load_json(BENCH, "peaks.json")
T0 = 1_790_000_000_000_000_000
MS = 1_000_000


def pages_of(order, entries, page_tokens=16384):
    """The packer's rule by hand: a page goes when the queued documents fill
    or overflow it, first-fit over the queue."""
    from video_features_tpu.parallel.pages import fit_documents

    queue, pages = [], []
    for i in range(entries):
        queue.append(order[i % len(order)])
        while sum(queue) >= page_tokens:
            take = fit_documents([(n, 1) for n in queue], page_tokens, 2048)
            pages.append([queue[j] for j in take])
            queue = [n for j, n in enumerate(queue) if j not in set(take)]
    return pages + ([queue] if queue else [])


def kernel_tiles(page, page_tokens, tile):
    """Key tiles a full layer walks over one page, by the kernel's own
    ``first_key_block``."""
    import jax.numpy as jnp
    from video_features_tpu.ops.segment_attention import first_key_block

    doc = np.full(page_tokens, -1, np.int32)
    doc[:sum(page)] = np.repeat(np.arange(len(page)), page)
    lo = np.asarray(first_key_block(jnp.asarray(doc), tile, None))
    return int(np.sum(np.arange(page_tokens // tile) - lo + 1))


SEEDS = (0, 3, 2_400_000_011, 2_147_483_999, 3_400_000_109)


def test_plan_same_multiset_longest_first_and_the_seed_orders_the_rest(tmp_path):
    work = TRAFFIC["equal_work"]
    conf = load_json(BENCH, "configs", "laguna_s21_bf16.json")
    assert work["page_tokens"] == conf["extraction"]["page_tokens"]
    plans = [gen.document_plan(TRAFFIC, seed) for seed in SEEDS]
    assert len({tuple(p) for p in plans}) == len(SEEDS)  # the seed orders the corpus
    assert gen.document_plan(TRAFFIC, SEEDS[2]) == plans[2]
    for plan in plans:
        assert sorted(plan) == gen.document_lengths(TRAFFIC) == counter.document_lengths()
        assert plan[0] == 16384 == max(plan) and sum(plan) == 92040 and min(plan) == 1024
        # the work of a window, whatever the seed: the pages its 224 transcripts
        # make by the program's own first-fit, each pass the pages of pack_pass,
        # and the key tiles the attention kernel's own rule walks over them
        pages = pages_of(plan, conf["window_videos"])
        per_pass = gen.pack_pass(plan, 16384)
        assert pages == per_pass * 14 and len(pages) == 98 and pages[0] == [16384]
        assert len(per_pass) == work["pages_per_pass"]
        assert gen.attention_tiles(per_pass, 16384, 512) == work["attention_tiles_per_pass"] \
            == sum(kernel_tiles(page, 16384, 512) for page in per_pass)
    small = dict(TRAFFIC, min_tokens=64, max_tokens=512, documents=4,
                 equal_work=dict(work, page_tokens=512, attention_tile=128,
                                 pages_per_pass=2, attention_tiles_per_pass=18))
    assert sorted(gen.document_plan(small, 3)) == [64, 128, 256, 512]
    paths = gen.write_corpus(small, 2_400_000_011, str(tmp_path / "c"))
    again = gen.write_corpus(small, 2_400_000_011, str(tmp_path / "d"))
    other = gen.write_corpus(small, 3, str(tmp_path / "e"))
    with np.load(paths[0]) as x, np.load(other[0]) as y:  # another seed: other ids, the longest first
        assert len(x["ids"]) == len(y["ids"]) == 512 and (x["ids"] != y["ids"]).any()
    for p, q in zip(paths, again):
        with np.load(p) as x, np.load(q) as y:
            assert all((x[k] == y[k]).all() for k in x.files)  # the same seed, the same bytes
            sizes = np.diff(x["segment_ends"], prepend=0)
            assert x["segment_ends"][-1] == len(x["ids"]) and x["ids"].dtype == np.int32
            assert sizes.min() >= 16 and (sizes[:-1] <= 64).all() and sizes[-1] < 64 + 16
            assert 0 <= x["ids"].min() and x["ids"].max() < TRAFFIC["vocab_size"]
            assert (x["end_ms"] - x["start_ms"] == sizes * TRAFFIC["ms_per_token"]).all()
    assert gen.stem_of(paths[0]) == "doc0"


def test_an_order_left_free_changes_the_work():
    """Why the plan redraws: the pages and the attention tiles of a pass over
    200 seeded permutations with the longest first and nothing else held
    (``equal_work.why`` of the traffic file, PERF.md section 6, PR 34)."""
    lengths = gen.document_lengths(TRAFFIC)
    pages, tiles = {}, {}
    for seed in range(200):
        rng = np.random.default_rng([seed, 0xD0C5])
        order = [lengths[-1]] + [lengths[int(r)] for r in rng.permutation(15)]
        made = gen.pack_pass(order, 16384)
        pages[len(made)] = pages.get(len(made), 0) + 1
        tiles.setdefault(len(made), []).append(gen.attention_tiles(made, 16384, 512))
    assert pages == {7: 145, 8: 46, 6: 9}  # 98, 112 and 84 pages of the window's 224 transcripts
    assert (min(tiles[7]), max(tiles[7])) == (2056, 2372)  # 15 % more keys met at one page count
    with pytest.raises(RuntimeError, match="no permutation"):
        gen.document_plan(dict(TRAFFIC, equal_work=dict(TRAFFIC["equal_work"], pages_per_pass=5)), 1)


def test_every_traffic_counted_by_this_counter_has_its_lengths():
    """``flops/laguna.flops_per_row`` reads ONE traffic file (``step_mfu``'s
    reader passes none): a second traffic on a configuration that counts with
    it must hold the same multiset of lengths, and the cut is the reference's."""
    import glob
    import json
    import os
    from reference import laguna as ref

    assert counter.LAYERS == ref.LAYERS and counter.EXPERTS_HELD == len(ref.EXPERTS) == 64
    bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    configs = {os.path.basename(f)[:-5] for f in glob.glob(os.path.join(BENCH, "configs", "*.json"))
               if json.load(open(f)).get("flops") == "laguna"}
    cells = [w for w in bench["workloads"] if w["config"] in configs]
    assert [w["name"] for w in cells] == ["laguna_s21_bf16.corpus_transcripts"]
    for w in cells:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert sorted(counter.document_lengths(path)) == sorted(counter.document_lengths())


def test_counter_against_a_count_made_another_way():
    """Attention pairs by enumeration at a small size, and the products per
    token written out as one sum over the published matrices."""
    for layer in (0, 1):
        for n in (1, 7, 511, 512, 513, 600):
            window = None if counter.is_full(layer) else counter.WINDOW
            pairs = sum(min(i + 1, window or i + 1) for i in range(n))
            assert counter.attention_pairs(n, layer) == pairs
    assert counter.attention_core_flops([3, 5], 0) == 4 * 128 * 48 * (6 + 15)
    matrices = 0
    for layer, h in zip(range(5), (48, 72, 72, 72, 48)):
        matrices += 3072 * h * 128 * 2 + 3072 * 1024 * 2 + 3072 * h   # q and out, k and v, gate
        if layer == 0:
            matrices += 3 * 3072 * 12288
        else:
            matrices += 3072 * 256 + 3 * 3072 * 1024 + 2.5 * 3 * 3072 * 1024  # router, shared, 2.5 routed
    assert counter.product_flops_per_token() == pytest.approx(2 * matrices)
    assert counter.flops_per_row() == pytest.approx(1.05259e9 + 0.28544e9, rel=1e-4)
    assert counter.expert_flops(640) == 640 * 3 * 2 * 3072 * 1024


# --- the readers ----------------------------------------------------------------

SCOPES = {1: "jit(paged)/paged/laguna/L1/attn/core/segment_attention_window",
          2: "jit(paged)/paged/laguna/L1/attn/qkv/dot_general",
          3: "jit(paged)/paged/laguna/L1/moe/experts/gmm",
          4: "jit(paged)/paged/laguna/L1/moe/dispatch/sort",
          5: "jit(paged)/paged/laguna/L1/moe/combine/gather",
          6: "jit(paged)/paged/laguna/pool/dot_general",
          9: ""}


def rec(name, start_ms, end_ms, parent=None, **ids):
    return {"name": name, "thread": "MainThread", "start": T0 + int(start_ms * MS),
            "end": T0 + int(end_ms * MS), "parent": parent, "ids": ids}


def build(pages=4, page_ms=100.0):
    """``pages`` executions back to back, the first and last cut by the
    slice; in each: core 30 ms, qkv 10, experts 20, dispatch 5, combine 5,
    pool 10, the rest unscoped."""
    ops, modules, records = [], [], [rec("run", -500, 1000)]
    for k in range(pages):
        t = k * page_ms
        modules.append((9, int(t * MS), int(page_ms * MS)))
        for meta, start, dur in ((1, 0, 30), (2, 30, 10), (3, 40, 20), (4, 60, 5), (5, 65, 5),
                                 (6, 70, 10), (9, 80, 20)):
            ops.append((meta, int((t + start) * MS), int(dur * MS)))
        page = 10 + k
        records += [rec("stage", t - 120, t - 110, parent=0, page=page,
                        documents=[4096, 1024] if k % 2 else [8192]),
                    rec("launch", t - 105, t - 100, parent=0, page=page),
                    rec("device", t + 90, t + page_ms + 1, parent=0, page=page)]
    plane = {"lines": {tr.OPS_LINE: ops, _spans.MODULES_LINE: modules},
             "metadata": {m: (f"%op.{m}", s) for m, s in SCOPES.items()}}
    space = {"profile_start_ns": T0, "devices": {"/device:TPU:0": plane}}
    trace = tr.reduce_planes({"/device:TPU:0": {tr.OPS_LINE: [(f"op{m}", s, d) for m, s, d in ops],
                                                tr.MODULES_LINE: [("jit_paged", s, d) for _m, s, d in modules]}})
    stats = {"spans": {"clock": "time_ns", "records": records}, "real_slots": 100_000,
             "routed_held": 250_000, "routed_total": 1_000_000,
             "expert_rows": [[10, 30, 20, 20], [25, 25, 25, 25]]}
    return space, dict(trace, path="built"), stats


@pytest.fixture
def built(monkeypatch):
    space, trace, stats = build()
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    from layer_metrics import _laguna
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    return trace, stats, {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}


def test_rooflines_read_whole_pages_only(built):
    trace, stats, facts = built
    assert trace["slice_pages"] == 2
    peak = PEAKS["TPU v5 lite"]["bf16_flops_per_s"]
    # whole executions are the 2nd and 3rd: pages 11 ([4096, 1024]) and 12 ([8192])
    ops = sum(counter.attention_core_flops(d, l) for d in ([4096, 1024], [8192]) for l in counter.LAYERS)
    assert attn_core_roofline.read(trace, stats, facts) == pytest.approx(100 * ops / peak / 0.060)
    # so few rows a page that the experts' weights bound it: 4 layers x 64 x
    # three 3072 x 1024 matrices at 2 bytes, and the rows in and out
    least = 0.0
    for tokens in (5120, 8192):
        rows = tokens * 2.5
        nbytes = 4 * 64 * 3 * 3072 * 1024 * 2 + rows * 2 * (2 * 3072 + 3 * 1024)
        least += max(counter.expert_flops(rows) / peak,
                     nbytes / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"])
    assert moe_experts_roofline.read(trace, stats, facts) == pytest.approx(100 * least / 0.040)
    stats["routed_held"] = 100 * stats["real_slots"]  # many rows: the products bound it
    assert moe_experts_roofline.read(trace, stats, facts) == pytest.approx(
        100 * counter.expert_flops((5120 + 8192) * 100) / peak / 0.040)


def test_busy_shares_and_load(built):
    trace, stats, facts = built
    assert attention_pct.read(trace, stats, facts) == pytest.approx(40.0)
    assert moe_dispatch_pct.read(trace, stats, facts) == pytest.approx(10.0)
    assert expert_load_max_over_mean.read(trace, stats, facts) == pytest.approx(1.5)


def test_a_program_without_the_scopes_or_counters_reads_nothing(monkeypatch):
    """The parent commit: no ``laguna/`` scope, no ``documents`` on a stage
    span, no routing counters. Every reader returns None and raises nothing."""
    space, trace, stats = build()
    plane = space["devices"]["/device:TPU:0"]
    plane["metadata"] = {m: (n, "jit(paged)/i3d/page/x" if s else "") for m, (n, s) in plane["metadata"].items()}
    for r in stats["spans"]["records"]:
        r["ids"].pop("documents", None)
    for key in ("routed_held", "routed_total", "expert_rows"):
        stats.pop(key)
    monkeypatch.setattr(_spans, "load", lambda path=None: space)
    from layer_metrics import _laguna
    monkeypatch.setattr(_laguna, "load", lambda path=None: space)
    facts = {"device_kind": "TPU v5 lite", "peaks": PEAKS, "chips": 1}
    for reader in (attn_core_roofline, moe_experts_roofline, moe_dispatch_pct, attention_pct,
                   expert_load_max_over_mean):
        assert reader.read(trace, stats, facts) is None
        assert reader.read(dict(trace, path=None), {}, facts) is None

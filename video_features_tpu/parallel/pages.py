"""Ragged paged batching: fixed-size device pages + int32 row tables.

The bucketed packer (:mod:`.packer`) fills fixed ``(batch_size, …)`` batches
per ``(model, slot-shape)`` bucket and keeps ONE batch in flight per bucket —
every corpus-flush or anti-starvation tail pays up to ``batch_size - 1``
padding rows, and the host round trip per dispatched batch is the
serialization point. The Ragged Paged Attention kernel work (PAPERS.md,
arXiv:2604.15464) shows the TPU-native fix: pack variable-length work into
fixed-size **pages** with a **row table** indexing the real rows, so one
compiled program per bucket *family* serves clips from any number of videos
(and any source geometry the host path normalizes into the family), with pad
waste bounded by one partial page instead of one partial batch.

Three pieces live here; the dispatch mechanics stay in
:class:`.packer.CorpusPacker` (its paged mode):

- **page geometry** — :func:`page_rows_for` sizes the page per family from
  the model's batch budget and the in-flight depth: ``depth`` pages of
  ``ceil(batch_size / depth)`` rows (rounded up to the mesh multiple) keep
  the same total rows in flight as one bucketed batch while the flush tail
  wastes at most ``page_rows - 1`` rows.
- **row tables** — :func:`build_row_table` maps each page row to
  ``(video, clip, valid)``: monotonically-assigned int32 video ids (host
  side, observability + device mask), the clip's index within its video, and
  a validity bit; padding rows are ``(-1, -1, 0)``. The table ships with the
  page and the jitted program masks by it.
- **the paged program** — :func:`paged_program` wraps a model's pure forward
  ``fn(params, page) -> rows`` into ``(params, page, table) ->
  (masked_rows, table)``. Masking multiplies every leading-axis output leaf
  by the validity column (×1.0 for real rows — exact, byte-preserving;
  ×0.0 zeroes padding rows on device). Passing the table through unchanged
  is what makes **buffer donation legal**: int32 ``(page_rows, 3)`` in and
  out, so :meth:`..parallel.mesh.MeshRunner.jit_paged` donates it and XLA
  aliases the buffer in place — the one dispatch-path donation the uint8
  wire format admits (``mesh.py::sharded_apply``'s documented seam).

**Token pages** (the ``laguna`` text stream) are the same idea one level
down: the page is ``page_tokens`` token slots filled with WHOLE documents of
different lengths, each token carrying its document, its position in it and
the table row of its segment, and the row table names the page's output rows:
``(video id, segment idx, valid)``. A page goes once ``PAGES_QUEUED`` pages'
worth of documents wait (:meth:`.packer.CorpusPacker._full`);
:func:`fit_documents` takes the oldest of them and the subset of the others
that fills the page best, :func:`build_token_page` fills page and table, and
the model's own forward masks pads (no epilogue multiply: a pad token must not
be attended to, routed or averaged, which only the model can see to).

Host scatter never reads the table (slots carry their assembly references —
slot-level fault attribution is unchanged); the table is the device-side
contract plus the journal's and the benchmark's occupancy ground truth.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence, Tuple

import numpy as np

# row-table columns: page row -> (video id, clip idx, valid bit)
TABLE_COLS = 3
PAD_ROW = (-1, -1, 0)


def page_rows_for(batch_size: int, depth: int,
                  device_batch: Callable[[int], int] = lambda n: n) -> int:
    """Rows per page for a family with ``batch_size`` total rows budgeted
    across ``depth`` in-flight pages, rounded up to the mesh multiple via
    ``device_batch`` (:meth:`..parallel.mesh.MeshRunner.device_batch`)."""
    if depth < 1:
        raise ValueError("pages_in_flight depth must be >= 1")
    return device_batch(max(1, -(-batch_size // depth)))


def build_row_table(entries: Sequence[Tuple[int, int]], page_rows: int,
                    out: np.ndarray = None) -> np.ndarray:
    """int32 ``(page_rows, 3)`` row table for one page.

    ``entries`` are the occupied rows' ``(video_id, clip_idx)`` pairs in page
    order; rows past ``len(entries)`` are padding (``(-1, -1, 0)``). ``out``
    reuses a staging-ring buffer when given (the host's per-page work is a
    fill, not an allocation)."""
    n = len(entries)
    if n > page_rows:
        raise ValueError(f"{n} entries exceed the {page_rows}-row page")
    table = np.empty((page_rows, TABLE_COLS), np.int32) if out is None else out
    for i, (vid, idx) in enumerate(entries):
        table[i, 0] = vid
        table[i, 1] = idx
        table[i, 2] = 1
    table[n:] = PAD_ROW
    return table


# token-page planes: page[plane, slot], int32
TOKEN_PLANES = 4
IDS, DOC, POS, SEG = range(TOKEN_PLANES)
# a token page goes once this many pages' worth of documents (tokens or table
# rows) are queued: what fit_documents has to choose from. The ring's default
# depth (PackSpec.pages_in_flight): in a device-bound loop the device holds
# that much work already, so the wait is the host's to spend
PAGES_QUEUED = 2


def fit_documents(sizes: Sequence[Tuple[int, int]], page_tokens: int,
                  page_rows: int) -> list:
    """Indices (in queue order) of the ``(tokens, segments)`` documents that
    go into the next page: always the first (the oldest never waits for a
    better page; ``[]`` when it fits no empty page), then the subset of the
    others that leaves the fewest free token slots within both bounds, tokens
    and table rows; among equal fills the one of the earliest documents (its
    latest document is the earliest, then its latest but one, …: the newest
    wait).

    An exact subset sum over the fills that can be reached, one pass over the
    free slots a queued document: ``least[j][t]`` is the fewest table rows
    with which the first ``j`` candidates fill exactly ``t`` slots (fewer
    rows at one fill never shut out a later document, so the least is all a
    fill has to remember), and the pass stops at a candidate that can
    complete a full page. On the chip machine's host 0.04–0.06 ms a page in
    the mean and 0.43 at most for the five to nine documents a 16,384-token
    page of the transcript corpus chooses from (PERF.md §6, PR 41), inside
    the page's ``stage`` span; the cost grows with candidates × free slots (a
    page of a thousand 15-token documents: 160 ms and 70 MB of tables on a
    sandbox CPU, a seventh of what reading and writing that many files
    takes)."""
    if not sizes or sizes[0][0] > page_tokens or sizes[0][1] > page_rows:
        return []
    free, rows = page_tokens - sizes[0][0], page_rows - sizes[0][1]
    rest = [i for i in range(1, len(sizes))
            if sizes[i][0] <= free and sizes[i][1] <= rows]
    least = [np.full(free + 1, rows + 1, np.int32)]  # rows + 1: no such fill
    least[0][0] = 0
    for i in rest:
        n, s = sizes[i]
        with_i = least[-1].copy()
        np.minimum(with_i[n:], least[-1][:free + 1 - n] + s, out=with_i[n:])
        least.append(with_i)
        if with_i[free] <= rows:
            break  # a full page: no later document betters it
    fill = int(np.flatnonzero(least[-1] <= rows)[-1])
    take = []
    for j in reversed(range(len(least) - 1)):
        if least[j][fill] > rows:  # no such fill without candidate j
            n, s = sizes[rest[j]]
            take.append(rest[j])
            fill -= n
            rows -= s
    return [0] + take[::-1]


def build_token_page(docs: Sequence[Tuple[int, np.ndarray, np.ndarray]],
                     page: np.ndarray, table: np.ndarray) -> list:
    """Fill one token page and its row table in place (staging-ring buffers:
    ``page`` int32 ``(4, page_tokens)``, ``table`` int32 ``(page_rows, 3)``).

    ``docs``: ``(video id, ids, segment_ends)`` per document, ``segment_ends``
    cumulative token counts. Planes: token id; document index in the page;
    position in the document (restarting at 0); table row of the token's
    segment. Pads are ``(0, -1, 0, -1)``, pad rows ``(-1, -1, 0)``. Returns
    each document's slice of the table's rows."""
    t = r = 0
    row_slices = []
    for d, (vid, ids, ends) in enumerate(docs):
        n, s = len(ids), len(ends)
        page[IDS, t:t + n] = ids
        page[DOC, t:t + n] = d
        page[POS, t:t + n] = np.arange(n, dtype=np.int32)
        page[SEG, t:t + n] = r + np.repeat(
            np.arange(s, dtype=np.int32), np.diff(ends, prepend=0))
        table[r:r + s, 0] = vid
        table[r:r + s, 1] = np.arange(s, dtype=np.int32)
        table[r:r + s, 2] = 1
        row_slices.append(slice(r, r + s))
        t += n
        r += s
    page[IDS, t:] = 0
    page[DOC, t:] = -1
    page[POS, t:] = 0
    page[SEG, t:] = -1
    table[r:] = PAD_ROW
    return row_slices


def token_paged_program(forward: Callable[[Any, Any], Any]) -> Callable:
    """Wrap a token model's ``forward(params, page) -> out`` into the paged
    step ``(params, page, table) -> (out, table)``: the table rides through
    for the same donation as :func:`paged_program`'s; masking by it is the
    forward's own business (pads are in the page's planes)."""

    def paged(params, page, table):
        return forward(params, page), table

    return paged


def mask_rows(rows: Any, valid) -> Any:
    """Multiply every leading-axis leaf of ``rows`` by the validity column.

    ``valid`` is the table's int32 valid bit; the multiply is ×1.0 for real
    rows (exact — packed outputs stay byte-identical to the bucketed loop)
    and ×0.0 for padding rows. Pytree-aware for multi-output forwards."""
    import jax

    def mask(leaf):
        v = valid.astype(leaf.dtype).reshape((-1,) + (1,) * (leaf.ndim - 1))
        return leaf * v

    return jax.tree_util.tree_map(mask, rows)


def paged_program(forward: Callable[[Any, Any], Any]) -> Callable:
    """Wrap a pure per-row ``forward(params, page)`` into the paged step
    ``(params, page, table) -> (masked_rows, table)``.

    The returned callable is what :meth:`..parallel.mesh.MeshRunner.jit_paged`
    compiles ONCE per family: the row table (not the trace signature) carries
    which rows are real, so every page of the family — whatever mix of videos
    and source geometries filled it — runs this single program."""

    def paged(params, page, table):
        rows = forward(params, page)
        return mask_rows(rows, table[:, 2]), table

    return paged

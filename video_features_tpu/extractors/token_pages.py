"""The text stream's extractor: a video's tokenised, timed transcript → one
contextual feature row per segment, whatever language model the type names
(``extractors/laguna.py``, ``extractors/sarvam.py``, ``extractors/qwen3_next.py``: a
model module, a checkpoint name, the share a random checkpoint holds).

A path is one video's transcript, ``<stem>.tokens.npz``
(:mod:`..io.transcript`); outputs are ``<stem>_<type>.npy`` (segments × hidden,
float32: the mean over each segment's tokens of the final-norm rows),
``<stem>_timestamps_ms.npy`` (segments × 2: start, end) and
``<stem>_tokens.npy`` (tokens per segment). Like ``vggish`` for the audio track,
it reads no frames; transcripts are read in the decode pool's place, under the
same ``decode``/``pull`` spans.

The only path is the packed one: a page of ``page_tokens`` token slots holds
whole transcripts, the oldest queued and the others that fill it best, chosen
from two pages' worth (``parallel/pages.py::fit_documents``), one compiled page program
(the model's ``forward``: embed → the layers the checkpoint holds → final
norm → segment mean) takes the weights as arguments, and a transcript longer
than a page is a permanent error of its video. The checkpoint's leaf names say
which layers and experts this chip holds (``models/text_layers.share_of``).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ..io.transcript import Transcript, read_transcript
from ..reliability.errors import DecodeError
from ..weights.store import load_weights, open_checkpoint
from .base import Extractor

# table rows (segments) per page, as a share of its token slots: a page of
# transcripts averaging under 8 tokens a segment closes on rows before tokens
SEGMENT_TOKENS_MIN = 8
# attention's block of queries and keys; a page is a whole number of them
ATTENTION_BLOCK = 512


class TokenPageExtractor(Extractor):
    uses_frame_stream = True  # transcripts are read by the decode pool's workers
    # a type states: its module under ``models`` (``PUBLISHED``, ``forward``,
    # ``stack_checkpoint``, ``random_checkpoint``), which is also its
    # checkpoint's name, and the layers and experts a random checkpoint holds
    # (the share its benchmark configuration states)
    model_name: str
    random_layers: int
    random_experts: int

    def __init__(self, cfg):
        super().__init__(cfg)
        # jax and the Pallas libraries the model needs load here, not with
        # the package: `import video_features_tpu` and the other types' paths
        # never see them
        import importlib

        self.model = model = importlib.import_module(f"..models.{self.model_name}", __package__)
        self.model_cfg = model.PUBLISHED
        # the attention and grouped-product kernels are Pallas and compile for
        # the TPU only; on another backend the same kernels run in the Pallas
        # interpreter (tests and smoke runs: a published-size page takes minutes)
        import jax

        self.interpret = jax.default_backend() != "tpu"
        if self.interpret:
            print(f"{self.model_name}: the default backend is {jax.default_backend()!r}: the page "
                  "program's Pallas kernels run in the interpreter")
        self.page_tokens = int(cfg.page_tokens)
        self.block = min(ATTENTION_BLOCK, self.page_tokens)
        if self.page_tokens % self.block:
            raise ValueError(f"page_tokens {self.page_tokens} is not a whole number of "
                             f"attention blocks of {self.block}")
        self.page_rows = max(1, self.page_tokens // SEGMENT_TOKENS_MIN)
        if self.runner.num_devices != 1:
            raise ValueError(f"{self.model_name} runs one page program on one chip (its share "
                             "of the experts is this chip's); use --num_devices 1")
        with (load_weights(self.model_name) as load,
              open_checkpoint(self.model_name, init_fn=self._random_checkpoint) as (names, read)):
            self.params, self.share = model.stack_checkpoint(self.model_cfg, names,
                                                             load.leaves(read))
            load.place(self.params)

    def _random_checkpoint(self) -> Dict[str, np.ndarray]:
        """VFT_ALLOW_RANDOM_WEIGHTS smoke runs: the type's first layers and
        experts."""
        c = self.model_cfg
        return self.model.random_checkpoint(c, range(self.random_layers),
                                            range(min(self.random_experts, c.num_experts)))

    # --- input: transcripts, through the decode pool ---

    def _open_inline(self, path: str):
        t = read_transcript(path, self.model_cfg.vocab_size)
        if len(t.ids) > self.page_tokens or len(t.segment_ends) > self.page_rows:
            raise DecodeError(
                f"{path}: {len(t.ids)} tokens in {len(t.segment_ends)} segments do not "
                f"fit a page of {self.page_tokens} tokens and {self.page_rows} rows "
                "(documents longer than a page are not split)")
        return {"tokens": len(t.ids)}, iter([(t.ids, t)])

    def _plan_inline(self, video_path: str, max_segments: int):
        return None  # a transcript is read whole

    # --- the page program ---

    def _forward(self, params, page):
        return self.model.forward(self.model_cfg, self.share, self.page_rows, self.block,
                                  params, page, self.interpret)

    def _page_program(self):
        """The one jitted page program, kept where the other types keep
        theirs (``_paged_programs``: ``pack_spec()`` runs once per run, a
        fresh ``jit`` each time would compile again, and a caller freeing an
        extractor's device state finds it there)."""
        from ..parallel.pages import token_paged_program

        cache = self.__dict__.setdefault("_paged_programs", {})
        key = (type(self)._forward, self.page_tokens, self.page_rows)
        jitted = cache.get(key)
        if jitted is None:
            jitted = self.runner.jit_paged(token_paged_program(self._forward))
            cache[key] = jitted
        return jitted

    def pack_spec(self):
        from ..parallel.packer import PackSpec

        program = self._page_program()
        self._moe_counters = None

        def open_clips(path):
            _meta, items = self._open_video(path)
            info: dict = {}

            def documents():
                for _ids, t in self._timed_frames(items):
                    info["transcript"] = t
                    yield t

            return info, documents()

        def paged_step(page, table):
            # the table's device value is DONATED into the call; the packer
            # holds both staging buffers until the rows resolve
            (rows, counters), table_out = program(self.params, self._put(page),
                                                  self._put(table))
            # running totals stay on the device: one tiny add a page, read
            # once when the run's counters are (_extra_pack_stats)
            self._moe_counters = (counters if self._moe_counters is None
                                  else self._moe_counters + counters)
            return rows, table_out

        def finalize(path, rows, info):
            t: Transcript = info["transcript"]
            return {self.feature_type: np.ascontiguousarray(rows[0]),
                    "timestamps_ms": np.stack([t.start_ms, t.end_ms], axis=1),
                    "tokens": t.segment_tokens}

        return PackSpec(batch_size=self.page_rows,
                        empty_row_shape=(0, self.model_cfg.hidden_size),
                        open_clips=open_clips, step=None, finalize=finalize,
                        paged_step=paged_step, page_rows=self.page_rows,
                        pages_in_flight=self.cfg.pages_in_flight,
                        page_tokens=self.page_tokens)

    def _extra_pack_stats(self) -> dict:
        """The routing counters of the run's pages: assignments made
        (``routed_total`` = top-k × real tokens), those to experts held here
        (``routed_held``), rows per held expert for each sparse layer
        (``expert_rows``, layers × experts held), and how many chunks the
        routed layers ran (``expert_chunks``) in how many calls
        (``expert_chunk_calls`` = sparse layers × pages): equal when no page
        held more than one chunk's rows (``ops/moe.py``); the (token tile,
        expert) runs the combine read (``combine_runs``: ``routed_held`` over
        it is the mean rows a run). Between them and the rows, what the model's
        own ``PAGE_COUNTERS`` name (``qwen3_next``:
        ``gdn_chunks``, ``gdn_boundary_chunks``)."""
        counters = getattr(self, "_moe_counters", None)
        if counters is None:
            return {}
        c = np.asarray(counters)
        own = getattr(self.model, "PAGE_COUNTERS", ())
        rows = c[5 + len(own):].reshape(-1, max(len(self.share.experts), 1))
        return {"routed_total": int(c[0]), "routed_held": int(c[1]),
                "expert_chunks": int(c[2]), "expert_chunk_calls": int(c[3]),
                **{name: int(v) for name, v in zip(own, c[5:])},
                "combine_runs": int(c[4]), "expert_rows": rows.tolist()}

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        raise NotImplementedError(
            f"{self.model_name} has no per-video loop: its one path is the packed one "
            "(the type sets --pack_corpus)")

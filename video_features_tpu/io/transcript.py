"""Token transcripts: the text stream's input, one file per video.

``<stem>.tokens.npz`` holds a video's tokenised, timed transcript: ``ids``
int32 ``(N,)`` token ids (no tokenizer here: ids in, features out),
``segment_ends`` int32 ``(S,)`` cumulative token counts closing each timed
segment (narration line, ASR segment; the last one is ``N``), and
``start_ms``/``end_ms`` int64 ``(S,)``. A transcript that breaks this is a
:class:`..reliability.errors.DecodeError` of its video: permanent, as a
corrupt container is.
"""

from __future__ import annotations

import zipfile
from typing import NamedTuple

import numpy as np

from ..reliability.errors import DecodeError

SUFFIX = ".tokens.npz"


class Transcript(NamedTuple):
    ids: np.ndarray           # (N,) int32
    segment_ends: np.ndarray  # (S,) int32, cumulative, ends with N
    start_ms: np.ndarray      # (S,) int64
    end_ms: np.ndarray        # (S,) int64

    @property
    def segment_tokens(self) -> np.ndarray:
        return np.diff(self.segment_ends, prepend=0).astype(np.int32)


def read_transcript(path: str, vocab_size: int) -> Transcript:
    try:
        with np.load(path) as z:
            t = Transcript(z["ids"].astype(np.int32, copy=False),
                           z["segment_ends"].astype(np.int32, copy=False),
                           z["start_ms"].astype(np.int64, copy=False),
                           z["end_ms"].astype(np.int64, copy=False))
    except (OSError, KeyError, ValueError, zipfile.BadZipFile) as e:
        raise DecodeError(f"{path}: not a token transcript ({e})") from e
    n, s = len(t.ids), len(t.segment_ends)
    if (t.ids.ndim != 1 or t.segment_ends.ndim != 1 or not n or not s
            or t.start_ms.shape != (s,) or t.end_ms.shape != (s,)
            or t.segment_ends[-1] != n or np.any(t.segment_tokens <= 0)):
        raise DecodeError(
            f"{path}: {n} token(s) in {s} segment(s) whose ends are not "
            "increasing up to the token count, or times of another length")
    if t.ids.min() < 0 or t.ids.max() >= vocab_size:
        raise DecodeError(f"{path}: token ids outside 0..{vocab_size - 1}")
    return t

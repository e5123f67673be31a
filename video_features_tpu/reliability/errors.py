"""Error taxonomy for the extraction pipeline.

Every failure the io layer can produce is one of four classes, each tagged
transient (worth retrying: the same input may succeed on a second attempt) or
permanent (retry is wasted work: the input itself is bad). The per-video fault
barrier (``extractors/base.py``) keys retry and manifest decisions off the tags
instead of guessing from exception types.

Classes:

- :class:`DecodeError` — unopenable/corrupt container, mid-stream decode
  failure. Permanent: the bytes on disk will not improve.
- :class:`FfmpegError` — ffmpeg subprocess failed (nonzero exit, missing
  output, killed). Transient: subprocesses die for environmental reasons
  (OOM killer, tmp-dir pressure) that clear up.
- :class:`DeviceError` — accelerator runtime failure. Transient: device
  restarts and preemptions heal.
- :class:`OutputError` — writing features or manifests failed. Transient:
  disk pressure and NFS hiccups clear up.
- :class:`VideoTimeoutError` — the per-video watchdog cancelled a wedged
  video. Permanent by default: a decode that hangs once usually hangs again,
  and re-running it re-wedges the host for another full timeout.
"""

from __future__ import annotations

import hashlib
import traceback
from typing import Tuple

import jax


class ExtractionError(Exception):
    """Base of the taxonomy; ``transient`` is a class-level retry tag."""

    transient: bool = False

    @property
    def error_class(self) -> str:
        return type(self).__name__


class DecodeError(ExtractionError):
    """Corrupt/unopenable container or a failed decode stream."""

    transient = False


class FfmpegError(ExtractionError):
    """ffmpeg subprocess failure (nonzero exit, missing/empty output)."""

    transient = True


class DeviceError(ExtractionError):
    """Accelerator runtime failure (XLA runtime errors map here)."""

    transient = True


class OutputError(ExtractionError):
    """Feature/manifest write failure."""

    transient = True


class VideoTimeoutError(ExtractionError):
    """Per-video watchdog fired; the video was cancelled, not completed."""

    transient = False


class CacheError(ExtractionError):
    """Feature-cache entry unreadable or corrupt (checksum mismatch, torn
    file, broken cache disk). Transient in the taxonomy sense — the content
    is recomputable — and by contract never escapes :mod:`..cache`: the
    store quarantines the entry, reports a miss, and extraction proceeds."""

    transient = True


class CircuitBreakerTripped(Exception):
    """Run-level abort: more failures than ``--max_failures`` allows.

    Deliberately outside the :class:`ExtractionError` taxonomy — it is not a
    per-video fault and must never be swallowed by the per-video barrier.
    """


def classify(exc: BaseException) -> Tuple[str, bool]:
    """(error_class, transient) for any exception the barrier can see.

    Taxonomy members carry their own tags. ``jax.errors.JaxRuntimeError`` —
    what a failing compile or execution raises (HBM or VMEM exhaustion, a
    refused Mosaic kernel, a lost device) — is a device fault and therefore
    transient. Everything else is an unknown permanent error: retrying an
    exception we cannot classify just repeats the work.
    """
    if isinstance(exc, ExtractionError):
        return exc.error_class, exc.transient
    if isinstance(exc, jax.errors.JaxRuntimeError):
        return DeviceError.__name__, DeviceError.transient
    return type(exc).__name__, False


def traceback_digest(exc: BaseException, length: int = 12) -> str:
    """Short stable digest of an exception's traceback frames.

    Hashes the ``file:line:function`` chain (not the message, which embeds
    per-video paths) so the failure manifest groups identical failure sites
    across thousands of videos.
    """
    frames = traceback.extract_tb(exc.__traceback__)
    sig = "|".join(f"{f.filename}:{f.lineno}:{f.name}" for f in frames)
    if not sig:
        sig = type(exc).__name__
    return hashlib.sha1(sig.encode()).hexdigest()[:length]

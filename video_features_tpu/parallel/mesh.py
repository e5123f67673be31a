"""Device-mesh data parallelism for the clip pipeline.

The reference's only parallel axis is inter-video data parallelism via one Python
thread per GPU (``/root/reference/main.py:37-47``). The TPU-native design replaces
threads with SPMD over a ``jax.sharding.Mesh``: a batch of clips/frames/pairs is
sharded along the leading axis across devices (``data`` axis over ICI), params are
replicated, and a single jitted program runs everywhere. No collectives are
semantically required for inference; XLA inserts only the initial shard/replicate
transfers and the output gather when results return to host.

Every extractor owns a :class:`MeshRunner` (built from ``cfg.num_devices``) and
routes its batched device step through :meth:`MeshRunner.jit`; batch sizes are
rounded up to a multiple of the mesh size with :meth:`MeshRunner.device_batch` so
the leading axis always divides evenly (static shapes — one compile per geometry).

Multi-host (DCN) scaling uses the same code: each host builds a mesh over its local
devices and processes its shard of the *video list*
(:func:`video_features_tpu.parallel.pipeline.shard_video_list`), mirroring the
embarrassingly-parallel split the reference documents via ``gen_file_list.py``.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def local_mesh(num_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over local devices: the clip-batch data-parallel axis."""
    if devices is None:
        devices = jax.local_devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(f"requested {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) axis across the data axis.

    The PartitionSpec names only axis 0, so the same sharding serves every batch
    rank in the framework: (B, F) features, (B, H, W, C) frames, (B, T, H, W, C)
    clip stacks, (B, H, W, 2) flow fields.
    """
    return NamedSharding(mesh, P(DATA_AXIS))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_apply(mesh: Mesh, fn: Callable, n_batch_args: int = 1,
                  matmul_precision: Optional[str] = None,
                  n_replicated_args: int = 0,
                  donate_argnums: Tuple[int, ...] = ()):
    """jit ``fn(params, *batches)`` with params replicated and batches sharded on axis 0.

    Each batch argument's leading axis must be divisible by the mesh size — callers
    round their batch size up via :meth:`MeshRunner.device_batch` and zero-pad the
    tail (:func:`video_features_tpu.parallel.pipeline.pad_batch`). Output shardings
    are left to XLA (batch-preserving steps keep rows sharded; ``np.asarray``
    gathers them to host).

    ``donate_argnums``: XLA input-output aliasing needs an output of
    IDENTICAL shape/dtype/layout to reuse a donated buffer, and with the
    uint8 wire format no frame-path *step* has one: every step consumes a
    uint8 frame buffer (4× smaller than any float activation or output) and
    emits fp32 (or ``--transfer_dtype``) features/flow, so donating those
    would only emit XLA's "donated buffer could not be aliased" warning per
    compile — the non-paged steps therefore donate nothing (default ``()``).
    The one genuinely matching pair is the paged dispatch mode's int32 row
    table (same shape/dtype in and out — :meth:`MeshRunner.jit_paged`), the
    path this seam was documented for; ``tests/test_paged.py`` pins the
    aliasing actually happening (donated table deleted) AND the uint8 steps
    still declining donation.

    ``matmul_precision``: TPU fp32 convs/matmuls default to bf16 MXU passes;
    ``"highest"`` traces the step under true-fp32 accumulation for the
    bit-parity path (≈3× the matmul cost; irrelevant on CPU).

    ``n_replicated_args``: trailing non-param arguments placed replicated
    rather than batch-sharded — the encode-once flow steps pass the window's
    final frame this way (a (1, H, W, 3) array cannot shard over the mesh).
    """
    # one device scope per jitted step, under the step function's own name:
    # every model's operations carry a name in a profiler trace, whether or
    # not the model sets scopes of its own (docs/observability.md)
    inner = fn
    scope = getattr(inner, "__name__", "step").lstrip("_")

    @functools.wraps(inner)  # the program keeps the step's name (jit_<name>)
    def fn(*args):  # noqa: F811 — precision must be active at trace time
        with jax.named_scope(scope):
            if matmul_precision is None:
                return inner(*args)
            with jax.default_matmul_precision(matmul_precision):
                return inner(*args)

    in_shardings = ((replicate(mesh),)
                    + (batch_sharding(mesh),) * n_batch_args
                    + (replicate(mesh),) * n_replicated_args)
    return jax.jit(fn, in_shardings=in_shardings,
                   donate_argnums=donate_argnums)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Every entry point calls this first, so the cache is always on. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and nothing
    is touched — whoever runs the program places the cache. Otherwise the
    cache lives at ``<checkout>/.jax_cache``, resolved from this package's
    own path: the directory is part of every cache key, so it must not move
    with the cwd, a pid or a temporary name. JAX's default threshold (compiles
    over 1 s are cached) is kept.
    """
    import os

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def describe_devices(mesh: Mesh) -> str:
    """One line naming what the process runs on — every entry point prints it
    once, so no run can quietly be on another device than the one intended.
    (The cache directory is read through :func:`enable_compilation_cache`,
    which is idempotent.)"""
    dev = jax.devices()[0]
    return (f"platform={dev.platform} device_kind={dev.device_kind!r} "
            f"local_devices={jax.local_device_count()} "
            f"mesh={int(mesh.devices.size)} "
            f"compile_cache={enable_compilation_cache()}")


class MeshRunner:
    """Per-extractor data-parallel execution context.

    Replaces the reference's thread-per-GPU ``replicate``/``scatter``/
    ``parallel_apply`` (``/root/reference/main.py:43-47``): instead of replicating a
    Python module across devices and scattering video indices, the model params are
    replicated onto a mesh once and every device step is a single SPMD program over
    a sharded batch.
    """

    def __init__(self, num_devices: Optional[int] = None,
                 matmul_precision: Optional[str] = None):
        self.mesh = local_mesh(num_devices)
        self.num_devices = int(self.mesh.devices.size)
        self.batch_sharding = batch_sharding(self.mesh)
        self.replicated = replicate(self.mesh)
        self.matmul_precision = matmul_precision

    def device_batch(self, requested: int) -> int:
        """Smallest multiple of the mesh size ≥ ``requested``."""
        return -(-requested // self.num_devices) * self.num_devices

    def jit(self, fn: Callable, n_batch_args: int = 1, n_replicated_args: int = 0):
        return sharded_apply(self.mesh, fn, n_batch_args, self.matmul_precision,
                             n_replicated_args)

    def jit_paged(self, paged_fn: Callable):
        """jit a paged step ``paged_fn(params, page, table) -> (out, table)``
        with the int32 row table DONATED (``parallel/pages.py``).

        The table is the one buffer on the dispatch path whose output is
        identical in shape/dtype/layout to its input (int32 ``(page_rows, 3)``
        in, passed through unchanged), so XLA aliases it in place — the
        legal-donation seam :func:`sharded_apply` documents. Pages themselves
        stay undonated: uint8 in, fp32 features out never alias.

        This wiring is statically checked: vftlint's ``use-after-donate``
        rule discovers the ``jit_paged → sharded_apply(donate_argnums=…)``
        forwarding chain (not hardcoded — docs/static-analysis.md), so a
        caller that reads its table after dispatch, loops without
        re-staging, or a paged fn that stops returning the table, fails
        lint with this chain named in the finding."""
        return sharded_apply(self.mesh, paged_fn, n_batch_args=2,
                             matmul_precision=self.matmul_precision,
                             donate_argnums=(2,))

    def put(self, arr):
        """Transfer a host batch onto the mesh, sharded along axis 0."""
        return jax.device_put(arr, self.batch_sharding)

    def put_replicated(self, tree):
        """Place a param pytree on the mesh, replicated, ONCE.

        Host-numpy params passed into a jitted call are re-transferred every
        call (a full weight-tree H2D copy per batch); extractors must pin their
        params here at construction.
        """
        return jax.device_put(tree, self.replicated)

"""Cost-volume correlation kernels: XLA formulation + hand-tiled Pallas kernels.

The reference implements PWC's 81-tap correlation as four raw CUDA kernels
JIT-compiled through CuPy (``/root/reference/models/pwc/pwc_src/correlation.py:17-242``).
Semantics: pad fmap2 by 4 px, mean-over-channels dot product between each pixel
of fmap1 and its 9×9 neighborhood in fmap2 → ``(B, H, W, 81)`` with channel
``k = (dy+4)·9 + (dx+4)`` (``:79-81``; forward-only — inference framework).

Lowerings, selected per call (``--pwc_corr``):

- ``xla``: 81 shifted elementwise products + channel mean. XLA fuses the shifts
  into a few HBM passes; this is the parity-proven formulation.
- ``pallas``: the VMEM-resident kernels, or an error. Spatial sizes ≤16² run
  the single-block kernel (whole image per grid step); larger sizes run the
  spatially TILED kernel (``corr81_pallas_tiled``: 16×16 output blocks, the
  haloed f2 held VMEM-resident per image). On a backend other than TPU, for a
  dtype other than fp32/bf16, or for a shape a VMEM gate excludes, ``pallas``
  RAISES — it never substitutes the XLA formulation.
- ``auto`` (the default): the Pallas kernels on TPU wherever ``pallas`` would
  run, ``xla`` everywhere else. Dispatch is static per call site, so one PWC
  forward may mix kernel and XLA levels; :func:`corr81_lowering` names the
  choice, and ``chip_smoke.py`` prints it per level and asserts that the
  compiled default PWC step contains a Mosaic custom call.

Every gate below was re-established in PR 21 on jax 0.9.0 / libtpu 0.0.34 for
a TPU v5e (Mosaic compile of each kernel at the PWC level shapes of a 256×384
input — 4×6×196, 8×12×128, 16×24×96, 32×48×64, 64×96×32 — in fp32 and bf16);
``chip_smoke.py`` runs every admitted (kernel, shape, dtype) on the chip
against ``corr81_xla``. tests/test_pallas_corr.py exercises the kernels in
interpreter mode on CPU; tests/test_chip_bringup.py cross-lowers them for TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .warp import warp_backward

CORR_RADIUS = 4
CORR_CHANNELS = (2 * CORR_RADIUS + 1) ** 2  # 81

# Scoped-VMEM limit every kernel is compiled with (``vmem_limit_bytes``). A v5e
# core has 128 MiB; the compiler's default scoped limit is 16 MiB, which the
# tiled kernel overruns at the largest PWC level of the sample geometry
# (fp32 64×96×32, any batch over 16: "Scoped allocation with size 16.94M and
# limit 16.00M exceeded scoped vmem limit by 960.0K").
_VMEM_LIMIT = 64 * 1024 * 1024
# What Mosaic takes on top of the pipelined blocks for its internal scratch and
# the spill area of the 81 unrolled taps: 9.6 MiB (fp32) / 9.4 MiB (bf16) on
# the installed compiler at every level shape, rounded up.
_VMEM_INTERNAL = 12 * 1024 * 1024


def _vmem_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one buffer: Mosaic pads the two minor dims to its
    (8 · 4/itemsize, 128) tile, so a 32-channel fp32 map occupies 4× its
    nominal bytes — the gates must count that or they admit what the compiler
    refuses (fp32 192×320×32 tiled: "Scoped allocation with size 64.56M")."""
    *lead, sub, lane = shape
    tile_sub = 8 * 4 // itemsize
    n = itemsize * (-(-sub // tile_sub) * tile_sub) * (-(-lane // 128) * 128)
    for d in lead:
        n *= d
    return n


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _out_struct(shape, *operands) -> jax.ShapeDtypeStruct:
    """Kernel output type. Inside ``shard_map`` — the only way a Mosaic kernel
    runs on a mesh: "Mosaic kernels cannot be automatically partitioned" — the
    output varies over the mesh axes its operands vary over, and pallas_call
    wants that said; outside, the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, operands[0].dtype, vma=vma)


def corr81_xla(f1: jnp.ndarray, f2: jnp.ndarray) -> jnp.ndarray:
    """Channel-mean cost volume over the 9×9 displacement window (pure XLA).

    Accumulates in fp32 whatever the feature dtype; the result is cast back to
    the input dtype so a bf16 forward stays bf16 downstream (a fp32 volume
    would silently promote every decoder conv through ``concatenate``).
    """
    b, h, w, c = f1.shape
    r = CORR_RADIUS
    dtype = f1.dtype
    f2p = jnp.pad(f2, ((0, 0), (r, r), (r, r), (0, 0)))
    f1 = f1.astype(jnp.float32)
    taps = []
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            shifted = f2p[:, r + dy : r + dy + h, r + dx : r + dx + w, :].astype(jnp.float32)
            taps.append(jnp.mean(f1 * shifted, axis=-1))
    # stack taps on axis 1 then move to the channel position: stacking 81
    # single-channel (…, 1) arrays directly on the minor axis makes XLA pad
    # each temp to the 128-lane tile — a 128× memory blowup that OOM'd the
    # 64-pair I3D sandwich at 256×341 (15.8 GiB of f32[64,64,96,1] copies).
    # With W as the minor dim the temps pad ≤1.34× and one cheap relayout
    # produces the (B, H, W, 81) the decoders consume.
    return jnp.moveaxis(jnp.stack(taps, axis=1), 1, -1).astype(dtype)


def _corr81_kernel(f1_ref, f2p_ref, out_ref):
    """One batch element per grid step; everything VMEM-resident.

    f1 (1, H, W, C), f2p (1, H+8, W+8, C) → out (1, H, W, 81). The 81 window
    taps are unrolled statically; each is a VPU multiply + lane reduction.
    Accumulation is fp32 regardless of the feature dtype; the store casts to
    the output dtype (bf16 forwards keep a bf16 volume downstream).
    """
    f1 = f1_ref[0].astype(jnp.float32)
    h, w, c = f1.shape
    taps = []
    for dy in range(2 * CORR_RADIUS + 1):
        for dx in range(2 * CORR_RADIUS + 1):
            shifted = f2p_ref[0, dy : dy + h, dx : dx + w, :].astype(jnp.float32)
            taps.append(jnp.sum(f1 * shifted, axis=-1) * (1.0 / c))
    out_ref[0] = jnp.stack(taps, axis=-1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def corr81_pallas(f1: jnp.ndarray, f2: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Pallas tile kernel; grid over the batch axis.

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU tests).
    """
    from jax.experimental import pallas as pl

    b, h, w, c = f1.shape
    r = CORR_RADIUS
    f2p = jnp.pad(f2, ((0, 0), (r, r), (r, r), (0, 0)))
    return pl.pallas_call(
        _corr81_kernel,
        out_shape=_out_struct((b, h, w, CORR_CHANNELS), f1, f2),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h + 2 * r, w + 2 * r, c), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, w, CORR_CHANNELS), lambda i: (i, 0, 0, 0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="pwc_corr81_single",
    )(f1, f2p)


# Output tile of the tiled kernels. The working set of the 81 unrolled taps
# grows with the tile: the single-block kernel (whole image = one tile)
# compiles up to 16×24 and is refused at 32×48×64 ("Scoped allocation with
# size 51.97M and limit 16.00M"; at 64×96×32 the compiler spends five minutes
# before reporting 202 MiB of "register allocator spill slots").
_TILE = 16


def _corr81_kernel_tiled(f1_ref, f2p_ref, out_ref):
    """Spatially tiled kernel: one 16×16 output block per grid step.

    Grid (b, nh, nw). ``f1`` arrives as a (1, 16, 16, C) block; the padded
    ``f2`` arrives as the FULL (1, Hp+8, Wp+8, C) image — its block index is
    constant across (j, k), so Mosaic keeps it VMEM-resident instead of
    re-fetching per step. The 24×24 haloed window for this block is a dynamic
    slice; the 81 taps are static shifts within it.
    """
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    k = pl.program_id(2)
    halo = 2 * CORR_RADIUS
    tile = f2p_ref[0, pl.dslice(j * _TILE, _TILE + halo),
                   pl.dslice(k * _TILE, _TILE + halo), :]
    f1 = f1_ref[0].astype(jnp.float32)
    c = f1.shape[-1]
    taps = []
    for dy in range(2 * CORR_RADIUS + 1):
        for dx in range(2 * CORR_RADIUS + 1):
            shifted = tile[dy : dy + _TILE, dx : dx + _TILE, :].astype(jnp.float32)
            taps.append(jnp.sum(f1 * shifted, axis=-1) * (1.0 / c))
    out_ref[0] = jnp.stack(taps, axis=-1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def corr81_pallas_tiled(f1: jnp.ndarray, f2: jnp.ndarray,
                        interpret: bool = False) -> jnp.ndarray:
    """Tiled Pallas cost volume for spatial sizes beyond one 16² block.

    Pads H/W to multiples of the tile (zero rows/cols — out-of-bounds f2 taps
    contribute zeros, exactly the reference's zero-padding; the padded f1 rows
    produce extra output rows sliced off afterwards).
    """
    from jax.experimental import pallas as pl

    b, h, w, c = f1.shape
    r = CORR_RADIUS
    ph = (-h) % _TILE
    pw = (-w) % _TILE
    f1p = jnp.pad(f1, ((0, 0), (0, ph), (0, pw), (0, 0)))
    f2p = jnp.pad(f2, ((0, 0), (r, r + ph), (r, r + pw), (0, 0)))
    hp, wp = h + ph, w + pw
    out = pl.pallas_call(
        _corr81_kernel_tiled,
        out_shape=_out_struct((b, hp, wp, CORR_CHANNELS), f1, f2),
        grid=(b, hp // _TILE, wp // _TILE),
        in_specs=[
            pl.BlockSpec((1, _TILE, _TILE, c), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, hp + 2 * r, wp + 2 * r, c), lambda i, j, k: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _TILE, _TILE, CORR_CHANNELS),
                               lambda i, j, k: (i, j, k, 0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="pwc_corr81_tiled",
    )(f1p, f2p)
    return out[:, :h, :w, :]


def _pallas_tiled_supported(h: int, w: int, c: int, itemsize: int = 4) -> bool:
    """VMEM gate for the tiled kernel: the resident per-image f2p plus one
    f1/out block pair, double-buffered by the pipeline, plus Mosaic's own
    scratch, must fit the limit the kernel is compiled with.

    Compiled for v5e at every PWC level shape × {fp32, bf16} × batch 1…128,
    and up to fp32 128×160×32 / 128×192×16 and bf16 192×320×32. Excluded:
    fp32 192×320×32 (PWC level 2 of a 720p frame), whose two f2p buffers
    alone are 64 MiB. The batch is a grid axis and does not enter."""
    r = CORR_RADIUS
    hp = h + (-h) % _TILE
    wp = w + (-w) % _TILE
    blocks = (_vmem_bytes((hp + 2 * r, wp + 2 * r, c), itemsize)
              + _vmem_bytes((_TILE, _TILE, c), itemsize)
              + _vmem_bytes((_TILE, _TILE, CORR_CHANNELS), itemsize))
    return 2 * blocks + _VMEM_INTERNAL <= _VMEM_LIMIT


def _pallas_supported(h: int, w: int, c: int, itemsize: int = 4) -> bool:
    """Shape gate for the single-block kernel: one image per grid step, so at
    most one 16×16 tile (see ``_TILE``) and the same block accounting as the
    tiled gate. Compiled for v5e at 4×6×196, 8×12×128 and 16×16×96, fp32 and
    bf16, batch 1…256 — the batch is a grid axis and does not enter."""
    if h > _TILE or w > _TILE:
        return False
    r = CORR_RADIUS
    blocks = (_vmem_bytes((h, w, c), itemsize)
              + _vmem_bytes((h + 2 * r, w + 2 * r, c), itemsize)
              + _vmem_bytes((h, w, CORR_CHANNELS), itemsize))
    return 2 * blocks + _VMEM_INTERNAL <= _VMEM_LIMIT


# feature dtypes the kernels accept (accumulation is fp32 in-kernel either way)
_KERNEL_DTYPES = (jnp.float32, jnp.bfloat16)


def warp_corr81(f1: jnp.ndarray, f2: jnp.ndarray, flow: jnp.ndarray,
                impl: str = "xla", level: str = "") -> jnp.ndarray:
    """Backward-warp ``f2`` by ``flow`` (already level-scaled) and correlate:
    :func:`~video_features_tpu.ops.warp.warp_backward`, then
    :func:`corr81` under ``impl``.

    ``level`` names the device scopes a profiler trace shows the work under:
    ``pwc/warp<level>`` and ``pwc/corr<level>``.
    """
    with jax.named_scope(f"pwc/warp{level}"):
        warped = warp_backward(f2, flow)
    with jax.named_scope(f"pwc/corr{level}"):
        return corr81(f1, warped, impl)


def corr81_lowering(shape, f1_dtype, f2_dtype, impl: str) -> str:
    """The lowering :func:`corr81` takes for ``(B, H, W, C)`` features:
    ``xla`` | ``pallas_single`` | ``pallas_tiled``.

    ``auto`` selects from what the code can observe — the default backend,
    the dtype, the shape against the VMEM gates. ``pallas`` takes the same
    kernels or raises ``ValueError`` naming what stands in the way.
    """
    if impl == "xla":
        return "xla"
    if impl not in ("auto", "pallas"):
        raise ValueError(
            f"unknown corr impl {impl!r}; expected xla|auto|pallas|pallas_interpret")
    _, h, w, c = shape
    if jax.default_backend() != "tpu":
        why = (f"the default backend is {jax.default_backend()!r} and Mosaic "
               "compiles for TPU only")
    elif f1_dtype not in _KERNEL_DTYPES:
        why = f"feature dtype {jnp.dtype(f1_dtype).name} is not float32|bfloat16"
    else:
        # gate on the LARGER operand itemsize: warp_corr81's composition feeds
        # a bf16 f1 with an fp32 warped f2, and the resident buffer is f2's
        isz = max(jnp.dtype(f1_dtype).itemsize, jnp.dtype(f2_dtype).itemsize)
        if h <= _TILE and w <= _TILE:
            if _pallas_supported(h, w, c, isz):
                return "pallas_single"
        elif _pallas_tiled_supported(h, w, c, isz):
            return "pallas_tiled"
        why = (f"{h}×{w}×{c} at {isz} bytes/value needs more than the "
               f"{_VMEM_LIMIT >> 20} MiB of VMEM the kernels are compiled with")
    if impl == "pallas":
        raise ValueError(f"pwc_corr 'pallas' cannot run here: {why}; "
                         "use 'auto' or 'xla'")
    return "xla"


def corr81(f1: jnp.ndarray, f2: jnp.ndarray, impl: str = "xla") -> jnp.ndarray:
    """Dispatch: ``xla`` (default), ``auto``/``pallas`` (see
    :func:`corr81_lowering`), or ``pallas_interpret`` (CPU tests)."""
    if impl == "pallas_interpret":
        _, h, w, _ = f1.shape
        if h > _TILE or w > _TILE:
            return corr81_pallas_tiled(f1, f2, interpret=True)
        return corr81_pallas(f1, f2, interpret=True)
    lowering = corr81_lowering(f1.shape, f1.dtype, f2.dtype, impl)
    if lowering == "pallas_single":
        return corr81_pallas(f1, f2)
    if lowering == "pallas_tiled":
        return corr81_pallas_tiled(f1, f2)
    return corr81_xla(f1, f2)

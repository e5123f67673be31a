"""Checkpoint resolution: find, convert, and cache model weights.

The reference hard-codes checkpoint paths and downloads torchvision weights on first
use (SURVEY.md §2.1 #25). This image has no network egress, so the store resolves
weights from local files and falls back to deterministic random initialization when
explicitly allowed (smoke tests, benchmarks — feature *values* then differ from the
pretrained reference but shapes, dtypes, and compute are identical).

Resolution order for model key ``<name>``:
1. explicit ``checkpoint_path`` argument
2. ``$VFT_CHECKPOINT_DIR/<name>.npz`` (converted Flax params, flat ``a/b/c`` keys)
3. ``./checkpoints/<name>.npz``
4. a torch file at either location (``<name>.pt``/``.pth``) run through the model's
   converter (requires torch), or an orbax checkpoint directory (``<name>.orbax``)
5. random init iff ``$VFT_ALLOW_RANDOM_WEIGHTS=1`` or ``allow_random=True``
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Dict, Optional

import numpy as np

from ..utils.metrics import setup_span

ENV_DIR = "VFT_CHECKPOINT_DIR"
ENV_ALLOW_RANDOM = "VFT_ALLOW_RANDOM_WEIGHTS"


def flatten_params(tree: dict, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten_params(v, key))
        else:
            out[key] = np.asarray(v)
    return out


def unflatten_params(flat: Dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for key, v in flat.items():
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def save_params_npz(path: str, params: dict) -> None:
    np.savez(path, **flatten_params(params))


def load_params_npz(path: str) -> dict:
    with np.load(path) as z:
        return unflatten_params({k: z[k] for k in z.files})


def _candidates(name: str):
    dirs = []
    if os.environ.get(ENV_DIR):
        dirs.append(os.environ[ENV_DIR])
    dirs.append("./checkpoints")
    for d in dirs:
        for ext in (".npz", ".pt", ".pth", ".orbax"):
            yield os.path.join(d, name + ext)


def save_params_orbax(dir_path: str, params: dict) -> str:
    """Write ``params`` as an orbax checkpoint directory (``<name>.orbax``).

    The ``.npz`` flat format stays the store's default (single file, no extra
    deps at load time); orbax is the JAX-ecosystem interchange format (sharded,
    async-capable) for pipelines that already speak it (SURVEY.md §5).
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(dir_path)
    ocp.PyTreeCheckpointer().save(path, params, force=True)
    return path


def load_params_orbax(dir_path: str) -> dict:
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer().restore(os.path.abspath(dir_path))


def random_params_like(init_fn: Callable, *args, seed: int = 0) -> dict:
    """Random params with the tree/shape/dtype structure of ``init_fn(*args)``
    WITHOUT tracing it on a device — ``jax.eval_shape`` only.

    Flax ``model.init`` compiles and runs a full forward pass (minutes of XLA
    compile for the conv3d networks on TPU, all wasted for random weights).
    Leaf semantics follow the param name: BatchNorm ``var``/``scale`` → ones,
    ``mean``/``bias`` → zeros, kernels → He-scaled normals (fan-in from the
    HWIO/(in, out) layout) so deep stacks keep O(1) activations — random-weight
    parity tests then compare numbers of sane magnitude.
    """
    import jax

    shapes = jax.eval_shape(init_fn, *args)
    rng = np.random.default_rng(seed)

    def leaf(path, s):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in ("var", "scale"):
            return np.ones(s.shape, s.dtype)
        if name in ("mean", "bias"):
            return np.zeros(s.shape, s.dtype)
        fan_in = int(np.prod(s.shape[:-1])) or 1
        std = (2.0 / fan_in) ** 0.5
        return (rng.standard_normal(s.shape) * std).astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def looks_like_tf_vars(flat: Dict[str, np.ndarray]) -> bool:
    """TF-slim variable naming (``vggish/conv1/weights``) vs store-format flat
    Flax keys (``conv1/kernel``)."""
    return any(
        k.replace(":0", "").rsplit("/", 1)[-1] in ("weights", "biases") for k in flat
    )


@contextlib.contextmanager
def open_checkpoint(name: str, init_fn: Optional[Callable[[], Dict[str, np.ndarray]]] = None):
    """A flat checkpoint read leaf by leaf: yields ``(names, read)`` where
    ``read(leaf)`` is that array alone. For a tree too large to hold twice
    (laguna's share is 12.6 GB as float32 on disk and 6.3 GB as the bfloat16
    the device keeps): the caller casts and places each leaf as it arrives,
    so the host never holds more than one. Resolution as :func:`resolve_params`
    for ``.npz`` files; ``init_fn`` (flat ``a/b/c`` → array) stands in where
    random weights are allowed."""
    for path in _candidates(name):
        if path.endswith(".npz") and os.path.exists(path):
            with np.load(path) as z:
                yield list(z.files), z.__getitem__
            return
    if os.environ.get(ENV_ALLOW_RANDOM) == "1" and init_fn is not None:
        flat = init_fn()
        yield list(flat), flat.__getitem__
        return
    raise FileNotFoundError(
        f"no checkpoint found for {name!r}: place its leaves at "
        f"${ENV_DIR}/{name}.npz, or set {ENV_ALLOW_RANDOM}=1 for random weights")


class WeightLoad:
    """What :func:`load_weights` yields. Its record's ids divide the span's
    time: ``read_s`` the checkpoint's arrays being read (from disk, or drawn
    where random weights stand in), ``bytes_read`` their bytes as stored; the
    child ``place_wait`` the one wait for the device copies; the rest is the
    host's own work (casts, stacks, dispatching the copies)."""

    def __init__(self, ids: Dict):
        self.ids = ids
        ids.update(read_s=0.0, bytes_read=0)

    def _count(self, t0: float, tree) -> None:
        import jax

        self.ids["read_s"] += time.perf_counter() - t0
        self.ids["bytes_read"] += sum(int(x.nbytes) for x in jax.tree.leaves(tree))

    def read(self, fn: Callable, *args, **kwargs):
        """``fn(*args, **kwargs)``: a whole host tree read at once
        (:func:`resolve_params`)."""
        t0 = time.perf_counter()
        tree = fn(*args, **kwargs)
        self._count(t0, tree)
        return tree

    def leaves(self, read: Callable) -> Callable:
        """``read(name)`` → one leaf, each read counted as it comes
        (:func:`open_checkpoint`'s reader)."""
        def timed(name):
            t0 = time.perf_counter()
            leaf = read(name)
            self._count(t0, leaf)
            return leaf

        return timed

    def place(self, tree):
        """``tree`` once every device copy has landed: ONE wait for the whole
        tree (a wait per leaf would serialise the copies), then its ``leaves``
        and ``bytes_placed`` (one copy, as the device holds it)."""
        import jax

        with setup_span("place_wait"):
            jax.block_until_ready(tree)
        leaves = jax.tree.leaves(tree)
        self.ids.update(leaves=len(leaves), bytes_placed=sum(int(x.nbytes) for x in leaves))
        return tree


@contextlib.contextmanager
def load_weights(checkpoint: str):
    """The ``load_weights`` set-up span of one checkpoint, read, placed and
    waited for inside it: yields its :class:`WeightLoad`."""
    with setup_span("load_weights", checkpoint=checkpoint) as handle:
        yield WeightLoad(handle.ids)


def resolve_params(
    name: str,
    convert_torch_fn: Optional[Callable[[dict], dict]] = None,
    init_fn: Optional[Callable[[], dict]] = None,
    checkpoint_path: Optional[str] = None,
    allow_random: bool = False,
    convert_tf_fn: Optional[Callable[[Dict[str, np.ndarray]], dict]] = None,
) -> dict:
    """Return the Flax param tree for model ``name`` per the resolution order above.

    ``convert_tf_fn``: converter for an ``.npz`` holding RAW TF checkpoint
    variables (the reference VGGish ships as a TF-slim checkpoint,
    ``vggish_slim.py:102-129``); detected by TF-style variable names so a
    TF-vars dump and a store-format params file can share the ``.npz`` slot.
    """
    if checkpoint_path and not os.path.exists(checkpoint_path):
        # an explicit path must not silently degrade to random weights
        raise FileNotFoundError(f"checkpoint_path {checkpoint_path!r} does not exist")
    paths = [checkpoint_path] if checkpoint_path else list(_candidates(name))
    for path in paths:
        if path is None or not os.path.exists(path):
            continue
        if path.endswith(".npz"):
            with np.load(path) as z:
                flat = {k: z[k] for k in z.files}
            if convert_tf_fn is not None and looks_like_tf_vars(flat):
                return convert_tf_fn(flat)
            return unflatten_params(flat)
        if path.endswith(".orbax"):
            return load_params_orbax(path)
        if convert_torch_fn is None:
            raise ValueError(f"{path}: torch checkpoint given but no converter for {name}")
        import torch  # local import: torch is host-side tooling only

        sd = torch.load(path, map_location="cpu", weights_only=True)
        if isinstance(sd, dict) and "state_dict" in sd:
            sd = sd["state_dict"]
        return convert_torch_fn(sd)

    if allow_random or os.environ.get(ENV_ALLOW_RANDOM) == "1":
        if init_fn is None:
            raise ValueError(f"no init_fn provided for random weights of {name}")
        return init_fn()
    raise FileNotFoundError(
        f"no checkpoint found for {name!r} (searched {paths}); place converted "
        f"weights at $VFT_CHECKPOINT_DIR/{name}.npz (or {name}.orbax), a torch "
        f"checkpoint at ./checkpoints/{name}.pt, or set {ENV_ALLOW_RANDOM}=1 "
        f"for random weights"
    )

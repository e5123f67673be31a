"""What the chip bring-up (PR 21) fixed, as far as a CPU can check it: where
the compile cache lives, that the Pallas kernels still lower for TPU on the
installed JAX, that an explicit ``--pwc_corr pallas`` never turns into XLA
behind the user's back, and that ``chip_smoke.py`` refuses to pass without a
chip. What only a chip can check is ``chip_smoke.py`` itself."""
# fast-registry: default tier — subprocesses that import jax

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.ops import pallas_corr as pc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# PWC pyramid levels 6…2 of a 256×384 input, and level 1 (never correlated,
# kept as the widest map the pyramid holds)
LEVELS = [(4, 6, 196), (8, 12, 128), (16, 24, 96), (32, 48, 64), (64, 96, 32),
          (128, 192, 16)]


# ---- the compile cache ------------------------------------------------------

def _cache_dir_in_fresh_process(cwd, env_value):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         "from video_features_tpu.parallel.mesh import enable_compilation_cache;"
         "print('DIR=' + str(enable_compilation_cache()))"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120, check=True)
    return [l for l in out.stdout.splitlines() if l.startswith("DIR=")][-1][4:]


def test_compile_cache_defaults_to_the_checkout_from_any_cwd(tmp_path):
    assert _cache_dir_in_fresh_process(str(tmp_path), None) == \
        os.path.join(REPO, ".jax_cache")


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path, monkeypatch):
    placed = str(tmp_path / "placed")
    # JAX reads the variable itself: the function reports it and sets nothing
    assert _cache_dir_in_fresh_process(str(tmp_path), placed) == placed
    # … and in this process: with the variable set the config is not touched
    from video_features_tpu.parallel.mesh import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    try:
        jax.config.update("jax_compilation_cache_dir", "/sentinel")
        assert enable_compilation_cache() == "/sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("field,flag,value", [
    ("compilation_cache", "--compilation_cache", "/tmp/x"),
    ("pwc_warp", "--pwc_warp", "gather"),
])
def test_removed_flags_are_gone(field, flag, value):
    from video_features_tpu.cli import parse_args
    from video_features_tpu.config import ExtractionConfig

    assert field not in ExtractionConfig.__dataclass_fields__
    with pytest.raises(SystemExit):
        parse_args(["--feature_type", "resnet50", "--video_paths", "a.mp4",
                    flag, value])


# ---- the kernels still lower for TPU ---------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("kernel", ["single", "tiled"])
def test_pallas_kernels_cross_lower_for_tpu(kernel, dtype):
    """jaxpr → Mosaic MLIR at every PWC level shape, with no TPU present. The
    Mosaic compile itself happens on the chip (``chip_smoke.py``)."""
    fn = {"single": pc.corr81_pallas, "tiled": pc.corr81_pallas_tiled}[kernel]
    for (h, w, c) in LEVELS:
        args = [jax.ShapeDtypeStruct((2, h, w, c), dtype)] * 2
        exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exported.mlir_module(), (kernel, h, w, c)


# ---- no quiet fallback ------------------------------------------------------

@pytest.fixture
def fmaps(rng):
    f1 = jnp.asarray(rng.normal(size=(2, 20, 24, 16)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(2, 20, 24, 16)).astype(np.float32))
    return f1, f2


def test_explicit_pallas_raises_off_tpu_where_auto_selects_xla(fmaps):
    f1, f2 = fmaps
    flow = jnp.zeros((2, 20, 24, 2), jnp.float32)
    with pytest.raises(ValueError, match="default backend is 'cpu'"):
        pc.corr81(f1, f2, "pallas")
    with pytest.raises(ValueError, match="default backend is 'cpu'"):
        pc.warp_corr81(f1, f2, flow, "pallas")
    assert pc.corr81_lowering(f1.shape, f1.dtype, f2.dtype, "auto") == "xla"
    np.testing.assert_array_equal(np.asarray(pc.corr81(f1, f2, "auto")),
                                  np.asarray(pc.corr81_xla(f1, f2)))


def test_explicit_pallas_raises_through_the_flow_net(rng):
    from video_features_tpu.models.pwc import pwc_forward, pwc_init_params

    im = jnp.asarray(rng.uniform(0, 255, (1, 64, 64, 3)).astype(np.float32))
    with pytest.raises(ValueError, match="pwc_corr 'pallas' cannot run here"):
        pwc_forward(pwc_init_params(seed=0), im, im, corr_impl="pallas")


def test_lowering_selection_on_a_tpu_backend(monkeypatch):
    """What ``auto`` observes: backend, dtype, shape against the VMEM gates."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32, bf16 = jnp.float32, jnp.bfloat16
    chosen = [pc.corr81_lowering((16, h, w, c), f32, f32, "auto")
              for (h, w, c) in LEVELS[:5]]
    assert chosen == ["pallas_single", "pallas_single", "pallas_tiled",
                      "pallas_tiled", "pallas_tiled"]
    # level 2 of a 720p frame: the resident f2p alone outgrows the limit in
    # fp32 (the compiler's refusal is quoted at the gate), not in bf16
    big = (2, 192, 320, 32)
    assert pc.corr81_lowering(big, f32, f32, "auto") == "xla"
    assert pc.corr81_lowering(big, bf16, bf16, "auto") == "pallas_tiled"
    # a bf16 f1 against an fp32 warped f2 gates on the larger itemsize
    assert pc.corr81_lowering(big, bf16, f32, "auto") == "xla"
    with pytest.raises(ValueError, match="MiB of VMEM"):
        pc.corr81_lowering(big, f32, f32, "pallas")
    with pytest.raises(ValueError, match="not float32|bfloat16"):
        pc.corr81_lowering(big, jnp.float16, jnp.float16, "pallas")
    assert pc.corr81_lowering(big, jnp.float16, jnp.float16, "auto") == "xla"


# ---- warp, then correlate, under the scopes the benchmark reads -------------

def _name_stacks(jaxpr):
    """Every equation's name stack, through the sub-jaxprs (jit, scan, …)."""
    for eqn in jaxpr.eqns:
        yield str(eqn.source_info.name_stack)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _name_stacks(sub)


@pytest.mark.parametrize("k,shape", list(zip((6, 5, 4, 3, 2), LEVELS[:5])))
def test_warp_corr81_is_the_composition_under_both_scopes(rng, k, shape):
    """``warp_corr81`` is ``warp_backward`` then ``corr81``, and a trace shows
    them under ``pwc/warp<k>`` and ``pwc/corr<k>``: the names the benchmark's
    breakdown and ``pwc_corr_roofline`` find the work by."""
    from video_features_tpu.ops.warp import warp_backward

    h, w, c = shape
    f1 = jnp.asarray(rng.normal(size=(1, h, w, c)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(1, h, w, c)).astype(np.float32))
    flow = jnp.asarray(rng.uniform(-3, 3, (1, h, w, 2)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pc.warp_corr81(f1, f2, flow, "xla", level=str(k))),
        np.asarray(pc.corr81_xla(f1, warp_backward(f2, flow))))
    jaxpr = jax.make_jaxpr(
        lambda a, b, f: pc.warp_corr81(a, b, f, "xla", level=str(k)))(f1, f2, flow)
    stacks = set(_name_stacks(jaxpr.jaxpr))
    warp = {s for s in stacks if f"pwc/warp{k}" in s}
    corr = {s for s in stacks if f"pwc/corr{k}" in s}
    assert warp and corr and not warp & corr
    assert warp | corr == stacks - {""}  # nothing of it outside the two


# ---- chip_smoke.py refuses to pass without a chip ---------------------------

def test_chip_smoke_exits_nonzero_on_cpu_naming_it():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode not in (0, None)
    assert "platform='cpu'" in out.stderr
    assert '"ok"' not in out.stdout

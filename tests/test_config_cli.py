"""Config dataclass + CLI shim behavior (reference flag surface)."""

import ast
import os
import re

import pytest

from video_features_tpu.cli import parse_args
from video_features_tpu.config import ExtractionConfig, resolve_model_defaults


def test_i3d_defaults():
    cfg = resolve_model_defaults(ExtractionConfig(feature_type="i3d"))
    assert cfg.stack_size == 64 and cfg.step_size == 64
    assert cfg.streams == ("rgb", "flow")


def test_r21d_defaults():
    cfg = resolve_model_defaults(ExtractionConfig(feature_type="r21d_rgb"))
    assert cfg.stack_size == 16 and cfg.step_size == 16


def test_user_override_kept():
    cfg = resolve_model_defaults(ExtractionConfig(feature_type="i3d", stack_size=24, step_size=8))
    assert cfg.stack_size == 24 and cfg.step_size == 8


def test_same_out_tmp_rejected():
    cfg = ExtractionConfig(feature_type="i3d", output_path="./x", tmp_path="./x")
    with pytest.raises(ValueError, match="same path"):
        cfg.validate()


def test_r21d_fps_rejected():
    cfg = ExtractionConfig(feature_type="r21d_rgb", extraction_fps=5)
    with pytest.raises(ValueError, match="original fps"):
        cfg.validate()


def test_cli_parse_reference_flags():
    cfg = parse_args([
        "--feature_type", "i3d",
        "--video_paths", "a.mp4", "b.mp4",
        "--stack_size", "24",
        "--step_size", "24",
        "--flow_type", "raft",
        "--on_extraction", "save_numpy",
    ])
    assert cfg.feature_type == "i3d"
    assert cfg.video_paths == ("a.mp4", "b.mp4")
    assert cfg.stack_size == 24
    assert cfg.flow_type == "raft"
    assert cfg.on_extraction == "save_numpy"


def test_cli_device_ids_maps_to_num_devices():
    cfg = parse_args(["--feature_type", "resnet50", "--video_paths", "a.mp4",
                      "--device_ids", "0", "1", "2"])
    assert cfg.num_devices == 3


def test_cli_show_pred_forces_one_device():
    cfg = parse_args(["--feature_type", "resnet50", "--video_paths", "a.mp4",
                      "--device_ids", "0", "1", "--show_pred"])
    assert cfg.num_devices == 1


def test_cli_larger_edge_flag():
    cfg = parse_args(["--feature_type", "raft", "--video_paths", "a.mp4",
                      "--resize_to_larger_edge", "--side_size", "256"])
    assert cfg.resize_to_smaller_edge is False
    assert cfg.side_size == 256


def test_cli_tpu_knobs_round2():
    cfg = parse_args([
        "--feature_type", "raft", "--video_paths", "a.mp4",
        "--raft_corr", "on_demand", "--pwc_corr", "pallas",
        "--matmul_precision", "highest", "--profile_dir", "/tmp/trace",
        "--clips_per_batch", "8", "--dtype", "bfloat16",
    ])
    assert cfg.raft_corr == "on_demand"
    assert cfg.pwc_corr == "pallas"
    assert cfg.matmul_precision == "highest"
    assert cfg.profile_dir == "/tmp/trace"
    assert cfg.clips_per_batch == 8
    assert cfg.dtype == "bfloat16"


def test_config_rejects_bad_round2_values():
    import pytest

    from video_features_tpu.config import ExtractionConfig

    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="raft", raft_corr="cuda").validate()
    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="pwc", pwc_corr="cupy").validate()
    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="i3d", matmul_precision="bf16").validate()


def test_cli_decode_and_bucket_knobs():
    cfg = parse_args([
        "--feature_type", "raft", "--video_paths", "a.mp4",
        "--decode_workers", "3", "--shape_bucket", "64",
        "--raft_corr", "volume_gather",
    ])
    assert cfg.decode_workers == 3
    assert cfg.shape_bucket == 64
    assert cfg.raft_corr == "volume_gather"


def test_cli_vggish_postprocess_flag():
    cfg = parse_args(["--feature_type", "vggish", "--video_paths", "a.wav",
                      "--vggish_postprocess"])
    assert cfg.vggish_postprocess is True
    assert parse_args(["--feature_type", "vggish", "--video_paths", "a.wav"]
                      ).vggish_postprocess is False


def test_cli_flow_dtype_and_use_ffmpeg():
    cfg = parse_args(["--feature_type", "pwc", "--video_paths", "a.mp4",
                      "--flow_dtype", "bfloat16", "--use_ffmpeg", "never"])
    assert cfg.flow_dtype == "bfloat16"
    assert cfg.use_ffmpeg == "never"
    d = parse_args(["--feature_type", "pwc", "--video_paths", "a.mp4"])
    assert d.flow_dtype == "float32" and d.use_ffmpeg == "auto"


def test_cli_transfer_dtype():
    cfg = parse_args(["--feature_type", "raft", "--video_paths", "a.mp4",
                      "--transfer_dtype", "float16"])
    assert cfg.transfer_dtype == "float16"
    assert parse_args(["--feature_type", "raft", "--video_paths", "a.mp4"]
                      ).transfer_dtype == "float32"
    import pytest

    from video_features_tpu.config import ExtractionConfig

    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="raft", transfer_dtype="int8").validate()


def test_cli_i3d_geometry_knobs():
    cfg = parse_args(["--feature_type", "i3d", "--video_paths", "a.mp4",
                      "--i3d_pre_crop_size", "96", "--i3d_crop_size", "64"])
    assert cfg.i3d_pre_crop_size == 96
    assert cfg.i3d_crop_size == 64
    d = parse_args(["--feature_type", "i3d", "--video_paths", "a.mp4"])
    assert d.i3d_pre_crop_size == 256 and d.i3d_crop_size == 224


def test_config_rejects_bad_i3d_geometry():
    import pytest

    from video_features_tpu.config import ExtractionConfig

    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="i3d", i3d_crop_size=16).validate()
    with pytest.raises(ValueError):
        ExtractionConfig(
            feature_type="i3d", i3d_pre_crop_size=64, i3d_crop_size=96
        ).validate()


def test_config_warns_on_non_multiple_of_32_crop(capsys):
    """112 is a common I3D crop: non-multiple-of-32 values >= 32 validate
    with a warning instead of raising (ADVICE r5 — the multiple-of-32
    tightening rejected previously-working configs)."""
    from video_features_tpu.config import ExtractionConfig

    ExtractionConfig(feature_type="i3d", i3d_crop_size=112).validate()
    err = capsys.readouterr().err
    assert "i3d_crop_size 112" in err and "multiple of 32" in err
    # multiples of 32 stay silent
    ExtractionConfig(feature_type="i3d", i3d_crop_size=224).validate()
    assert "i3d_crop_size" not in capsys.readouterr().err


def test_config_rejects_bad_flow_dtype_and_ffmpeg():
    import pytest

    from video_features_tpu.config import ExtractionConfig

    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="pwc", flow_dtype="fp16").validate()
    with pytest.raises(ValueError):
        ExtractionConfig(feature_type="pwc", use_ffmpeg="maybe").validate()


def test_environment_reads_are_deployment_and_debugging_settings_only():
    """The package names seven ``VFT_*`` variables that are deployment or
    debugging settings: where the weights are and what stands in for them,
    the cache's pin, the multi-host switch, and the metrics and fault hooks.
    A lowering is chosen from what the code can observe (backend, dtype,
    shape) or by a flag — never from the environment, where no configuration,
    cache key or benchmark cell can see it."""
    package = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "video_features_tpu")
    named = {}
    for dirpath, _dirs, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                        and re.fullmatch(r"VFT_[A-Z0-9_]+", node.value)):
                    named.setdefault(node.value, set()).add(
                        os.path.relpath(path, package))
    assert named == {
        "VFT_CHECKPOINT_DIR": {"weights/store.py"},
        "VFT_ALLOW_RANDOM_WEIGHTS": {"weights/store.py"},
        "VFT_WEIGHTS_VERSION": {"cache/key.py"},
        "VFT_VGGISH_PCA_PARAMS": {"extractors/vggish.py"},
        "VFT_MULTIHOST": {"parallel/pipeline.py"},
        "VFT_METRICS": {"utils/metrics.py"},
        "VFT_FAULTS": {"reliability/faults.py"},
    }

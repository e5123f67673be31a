"""Sharded-vs-single-device consistency on the virtual 8-device CPU mesh.

The property under test: every extractor's device step is a pure SPMD program, so
running it over an N-device mesh (batch axis sharded) must produce the same numbers
as a 1-device mesh. conftest.py forces ``xla_force_host_platform_device_count=8``,
the TPU answer to testing multi-chip topologies without hardware (SURVEY.md §4).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute on CPU: whole-model parity / full-video extract


from video_features_tpu.config import ExtractionConfig


@pytest.fixture(autouse=True)
def _random_weights():
    mp = pytest.MonkeyPatch()
    mp.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    yield
    mp.undo()


def _cfg(tmp_path, feature_type, num_devices, **kw):
    return ExtractionConfig(
        feature_type=feature_type,
        num_devices=num_devices,
        output_path=str(tmp_path / f"out{num_devices}"),
        tmp_path=str(tmp_path / f"tmp{num_devices}"),
        **kw,
    )


def test_mesh_runner_rounding():
    from video_features_tpu.parallel import MeshRunner

    r = MeshRunner(num_devices=8)
    assert r.num_devices == 8
    assert [r.device_batch(b) for b in (1, 7, 8, 9, 16)] == [8, 8, 8, 16, 16]
    assert MeshRunner(num_devices=1).device_batch(3) == 3


def test_num_devices_changes_placement():
    """--num_devices must actually change how batches land on devices."""
    from video_features_tpu.parallel import MeshRunner

    batch = np.zeros((8, 4, 4, 3), np.float32)
    on1 = MeshRunner(num_devices=1).put(batch)
    on8 = MeshRunner(num_devices=8).put(batch)
    assert len(on1.sharding.device_set) == 1
    assert len(on8.sharding.device_set) == 8
    # 8-way sharded: each device holds one row of the batch
    assert on8.addressable_shards[0].data.shape == (1, 4, 4, 3)


def test_resnet_sharded_matches_single(tmp_path, rng):
    from video_features_tpu.extractors.resnet import ExtractResNet50

    frames = rng.integers(0, 256, (16, 64, 64, 3), dtype=np.uint8)
    ex1 = ExtractResNet50(_cfg(tmp_path, "resnet50", 1, batch_size=16))
    ex8 = ExtractResNet50(_cfg(tmp_path, "resnet50", 8, batch_size=16))
    f1 = np.asarray(ex1._step(ex1.params, ex1.runner.put(frames)))
    f8 = np.asarray(ex8._step(ex8.params, ex8.runner.put(frames)))
    assert f8.shape == (16, 2048)
    # random He weights with identity BN let residual sums grow to O(1e3);
    # tolerance scales with the feature magnitude (fp32 noise × reorder)
    np.testing.assert_allclose(f8, f1, rtol=1e-4, atol=1e-5 * np.abs(f1).max())


def test_r21d_sharded_matches_single(tmp_path, rng):
    from video_features_tpu.extractors.r21d import ExtractR21D

    clips = rng.integers(0, 256, (8, 2, 48, 48, 3), dtype=np.uint8)
    ex1 = ExtractR21D(_cfg(tmp_path, "r21d_rgb", 1, stack_size=2, step_size=2))
    ex8 = ExtractR21D(_cfg(tmp_path, "r21d_rgb", 8, stack_size=2, step_size=2))
    f1 = np.asarray(ex1._step(ex1.params, ex1.runner.put(clips)))
    f8 = np.asarray(ex8._step(ex8.params, ex8.runner.put(clips)))
    assert f8.shape == (8, 512)
    np.testing.assert_allclose(f8, f1, rtol=1e-5, atol=1e-5)


def test_pwc_flow_sharded_matches_single(tmp_path, rng):
    from video_features_tpu.extractors.flow import ExtractFlow

    frames = rng.uniform(0, 255, (9, 64, 64, 3)).astype(np.float32)
    ex1 = ExtractFlow(_cfg(tmp_path, "pwc", 1, batch_size=8))
    ex8 = ExtractFlow(_cfg(tmp_path, "pwc", 8, batch_size=8))
    f1 = np.asarray(ex1._step(ex1.params, ex1.runner.put(frames[:-1]), ex1.runner.put(frames[1:])))
    f8 = np.asarray(ex8._step(ex8.params, ex8.runner.put(frames[:-1]), ex8.runner.put(frames[1:])))
    assert f8.shape == (8, 64, 64, 2)
    np.testing.assert_allclose(f8, f1, rtol=1e-5, atol=1e-4)


def test_vggish_sharded_matches_single(tmp_path, rng):
    from video_features_tpu.extractors.vggish import ExtractVGGish

    examples = rng.normal(size=(8, 96, 64)).astype(np.float32)
    ex1 = ExtractVGGish(_cfg(tmp_path, "vggish", 1))
    ex8 = ExtractVGGish(_cfg(tmp_path, "vggish", 8))
    f1 = np.asarray(ex1._step(ex1.params, ex1.runner.put(examples)))
    f8 = np.asarray(ex8._step(ex8.params, ex8.runner.put(examples)))
    assert f8.shape == (8, 128)
    np.testing.assert_allclose(f8, f1, rtol=1e-5, atol=1e-5)


def test_i3d_rgb_sharded_matches_single(tmp_path, rng):
    """I3D stack step over a 4-device mesh (224² is CPU-heavy; 4 clips keep it sane)."""
    from video_features_tpu.extractors.i3d import ExtractI3D

    stacks = rng.integers(0, 256, (4, 17, 224, 224, 3), dtype=np.uint8)
    kw = dict(streams=("rgb",), stack_size=16, step_size=16, clips_per_batch=4)
    ex1 = ExtractI3D(_cfg(tmp_path, "i3d", 1, **kw))
    ex4 = ExtractI3D(_cfg(tmp_path, "i3d", 4, **kw))
    f1, _ = ex1._rgb_step(ex1.i3d_params["rgb"], ex1.runner.put(stacks))
    f4, _ = ex4._rgb_step(ex4.i3d_params["rgb"], ex4.runner.put(stacks))
    f1, f4 = np.asarray(f1), np.asarray(f4)
    assert f4.shape == (4, 1024)
    np.testing.assert_allclose(f4, f1, rtol=1e-4, atol=1e-4)


def test_matmul_precision_plumbs(tmp_path, rng):
    """--matmul_precision traces and matches default numerics on CPU (where
    fp32 is already exact; on TPU 'highest' switches off the bf16 MXU passes)."""
    from video_features_tpu.extractors.resnet import ExtractResNet50

    frames = rng.integers(0, 256, (8, 64, 64, 3), dtype=np.uint8)
    ex_d = ExtractResNet50(_cfg(tmp_path, "resnet50", 1, batch_size=8))
    ex_h = ExtractResNet50(
        _cfg(tmp_path / "h", "resnet50", 1, batch_size=8, matmul_precision="highest")
    )
    f_d = np.asarray(ex_d._step(ex_d.params, ex_d.runner.put(frames)))
    f_h = np.asarray(ex_h._step(ex_h.params, ex_h.runner.put(frames)))
    np.testing.assert_allclose(f_h, f_d, rtol=1e-5, atol=1e-5 * np.abs(f_d).max())


def test_raft_extract_end_to_end_sharded(tmp_path, sample_video):
    """Full extract() pipeline (decode → pairs → sharded RAFT → unpad → collect)
    gives identical flow on 1- and 8-device meshes."""
    from video_features_tpu.extractors.flow import ExtractFlow

    kw = dict(batch_size=8, side_size=64, extraction_fps=2)
    ex1 = ExtractFlow(_cfg(tmp_path, "raft", 1, **kw))
    ex8 = ExtractFlow(_cfg(tmp_path, "raft", 8, **kw))
    f1 = ex1.extract(sample_video)
    f8 = ex8.extract(sample_video)
    assert f1["raft"].shape == f8["raft"].shape
    assert f1["raft"].shape[0] >= 30
    # Tolerance note: sharding changes XLA fusion/reduction order; with random
    # weights RAFT's 20 recurrent iterations chaotically amplify those last-ulp
    # differences (observed: 0.4% of elements off by ≤4% — single-iteration steps
    # like PWC/ResNet/I3D match at 1e-5 above). Bit-parity across mesh sizes is
    # asserted there; here we bound the amplified drift.
    np.testing.assert_allclose(f8["raft"], f1["raft"], rtol=5e-2, atol=5e-2)


def test_i3d_clip_batching_consistency(tmp_path, rng):
    """clips_per_batch changes throughput, not results: a 4-clip batched step must
    equal four 1-clip steps (padded to the mesh multiple)."""
    from video_features_tpu.extractors.i3d import ExtractI3D

    stacks = rng.integers(0, 256, (4, 17, 224, 224, 3), dtype=np.uint8)
    kw = dict(streams=("rgb",), stack_size=16, step_size=16)
    ex = ExtractI3D(_cfg(tmp_path, "i3d", 1, clips_per_batch=4, **kw))
    batched, _ = ex._rgb_step(ex.i3d_params["rgb"], ex.runner.put(stacks))
    ex1 = ExtractI3D(_cfg(tmp_path / "one", "i3d", 1, clips_per_batch=1, **kw))
    singles = [
        np.asarray(ex1._rgb_step(ex1.i3d_params["rgb"], ex1.runner.put(stacks[i : i + 1]))[0])
        for i in range(4)
    ]
    np.testing.assert_allclose(
        np.asarray(batched), np.concatenate(singles), rtol=1e-4, atol=1e-4
    )


def test_raft_on_demand_matmul_sharded_matches_single(tmp_path, rng):
    """raft_corr=on_demand_matmul under the 8-device mesh: the per-chunk
    volume remat einsums batch over the sharded pair axis.

    Tolerance note: RANDOM weights make the 20-iteration GRU loop chaotic
    (|flow| ≈ 800 px at this geometry), so mesh-size-dependent XLA reduction
    order amplifies to ~5e-3 px — measured IDENTICALLY for volume,
    on_demand, and on_demand_matmul (round-5 sweep), i.e. a property of the
    loop under random weights, not of any lookup lowering. Bound at 4× the
    measured max."""
    from video_features_tpu.extractors.flow import ExtractFlow

    frames = rng.uniform(0, 255, (9, 48, 48, 3)).astype(np.float32)
    ex1 = ExtractFlow(_cfg(tmp_path, "raft", 1, batch_size=8,
                           raft_corr="on_demand_matmul"))
    ex8 = ExtractFlow(_cfg(tmp_path, "raft", 8, batch_size=8,
                           raft_corr="on_demand_matmul"))
    f1 = np.asarray(ex1._step(ex1.params, ex1.runner.put(frames[:-1]),
                              ex1.runner.put(frames[1:])))
    f8 = np.asarray(ex8._step(ex8.params, ex8.runner.put(frames[:-1]),
                              ex8.runner.put(frames[1:])))
    np.testing.assert_allclose(f8, f1, rtol=1e-4, atol=0.02)

"""End-to-end ResNet-50 extraction on a real sample video (random weights, CPU)."""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute on CPU: whole-model parity / full-video extract


from video_features_tpu.config import ExtractionConfig
from video_features_tpu.extractors.resnet import ExtractResNet50


@pytest.fixture(scope="module")
def extractor(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("VFT_ALLOW_RANDOM_WEIGHTS", "1")
    out = tmp_path_factory.mktemp("out")
    cfg = ExtractionConfig(
        feature_type="resnet50",
        on_extraction="save_numpy",
        output_path=str(out),
        batch_size=64,
    )
    yield ExtractResNet50(cfg)
    mp.undo()


def test_extract_sample(extractor, sample_video):
    feats = extractor.extract(sample_video)
    assert feats["resnet50"].shape == (355, 2048)
    assert feats["timestamps_ms"].shape == (355,)
    assert float(feats["fps"]) == pytest.approx(19.62, abs=0.01)
    assert np.isfinite(feats["resnet50"]).all()


def test_tail_padding_does_not_leak(extractor):
    """Rows of a padded tail batch must equal the same frames run as a full batch."""
    rng = np.random.default_rng(0)
    frames = rng.integers(0, 256, (64, 224, 224, 3), dtype=np.uint8)
    full = np.asarray(extractor._step(extractor.params, frames))
    from video_features_tpu.parallel.pipeline import pad_batch

    tail = pad_batch(frames[:5], 64)
    padded = np.asarray(extractor._step(extractor.params, tail))[:5]
    np.testing.assert_allclose(padded, full[:5], rtol=1e-5, atol=1e-5)


def test_run_fault_barrier(extractor, sample_video, capsys):
    ok = extractor.run([sample_video, "/tmp/missing_video.mp4"])
    out = capsys.readouterr().out
    assert ok == 1
    assert "Extraction failed at: /tmp/missing_video.mp4" in out

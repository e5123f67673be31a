"""The rest of a run with the look for a chip skipped, at a size a test can
hold, on the CPU, once for each configuration under ``configs/``:

- ``resnet50_fp32`` at full width and depth: two 12-16-frame clips, pages of
  16 rows (its cell is not in ``BENCHMARK.json`` yet, PERF.md section 7);
- ``i3d_pwc_fp32`` at full width and depth: two clips of one and two
  16-frame stacks at a 64-pixel edge, pages of one stack, through the
  composite page program (PWC-Net flow and both I3D towers); the reference's
  geometry constants are set to the same.

For each: the ``corpus_run`` generator completes a window and returns the
counts the result line is built from, and ``correct`` comes out true; with the
timed path broken underneath -- an answer altered where it is produced
(``faults.py``: row 0 of every page scaled by 1.05 in the page program's
epilogue) -- ``correct`` comes out false. The control (the program's own
``--matmul_precision high``) comes out not correct on the chip only: the CPU
has one float32 product, so there the test is skipped
(``chiprun -- python3 -m pytest benchmark/tests -k control``).

The traced run goes the same way with the profiler faked (a CPU has no TPU
plane to trace): the slice is placed by the window's own first written video,
``run.py`` reduces the trace under the tracer's own directory (the recorded
``data/pwc_page.xplane.pb`` put there by the fake's stop) and calls every
reader; a window that closes before its first video is seen ends the run with
one line and no traceback.
"""

import json

import pytest

from conftest import BENCH, ROOT

import run as bench_run
from faults import altered_answer

SIZES = {
    "resnet50_fp32": {
        "traffic": dict(clips=2, min_frames=12, max_frames=16, min_window_videos=3,
                        warmup_clips=2),
        "extraction": dict(batch_size=32, decode_workers=2),
        "reference": {},
        "altered": "gap.resnet50",
    },
    "i3d_pwc_fp32": {
        "traffic": dict(clips=2, min_frames=20, max_frames=36, min_window_videos=3,
                        warmup_clips=2),
        "extraction": dict(clips_per_batch=2, decode_workers=2, stack_size=16,
                           step_size=16, i3d_pre_crop_size=64, i3d_crop_size=64),
        "reference": dict(STACK=16, STEP=16, EDGE=64, CROP=64),
        "altered": "gap.flow",
    },
}
CONFIGS = sorted(SIZES)


class _Device:
    """What ``run_cell`` asks of a device, for the traced run's peaks table."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {}


def _run(tmp_path, monkeypatch, config, variant="", trace=False):
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = {"name": config + ".corpus_clips", "config": config,
            "traffic": "corpus_clips", "chips": 1}
    size = SIZES[config]
    conf = bench_run.load_json(BENCH, "configs", config + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    traffic.update(size["traffic"])
    conf["extraction"].update(size["extraction"])
    conf["window_videos"] = 3
    conf["check_videos"] = 2
    if size["reference"]:
        import importlib

        ref = importlib.import_module("reference." + conf["reference"])
        for name, value in size["reference"].items():
            monkeypatch.setattr(ref, name, value)
    return bench_run.run_cell(
        bench, cell, seed=2147483659, seconds=bench["run_seconds"], trace=trace,
        variant=variant, scratch=str(tmp_path / "cell"), conf=conf, traffic=traffic,
        devices=[_Device()] if trace else None)


def _checks(result):
    return {c["name"]: c for c in result["checks"]}


@pytest.mark.parametrize("config", CONFIGS)
def test_window_completes_and_is_correct(tmp_path, monkeypatch, config):
    r = _run(tmp_path, monkeypatch, config)
    assert r["attempted"] == 3 and r["failed"] == 0
    assert set(r["metrics"]) == {"videos_per_s", "setup_s"}
    assert r["metrics"]["videos_per_s"]["value"] > 0
    for c in r["checks"]:  # float32 against float32, at the cell's own limits
        assert c["limit"] is None or c["value"] <= c["limit"], c
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    json.dumps(r)


@pytest.mark.parametrize("config", CONFIGS)
def test_control_is_not_correct(tmp_path, monkeypatch, config):
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("--matmul_precision high differs from highest only on the chip")
    r = _run(tmp_path, monkeypatch, config, variant="control")
    assert r["failed"] == 0
    assert any(c["limit"] is not None and c["value"] > c["limit"] for c in r["checks"])
    assert r["correct"] is False


@pytest.mark.parametrize("config", CONFIGS)
def test_an_altered_answer_is_not_correct(tmp_path, monkeypatch, config):
    conf = bench_run.load_json(BENCH, "configs", config + ".json")
    with altered_answer(conf["feature_type"]):
        r = _run(tmp_path, monkeypatch, config)
    assert r["failed"] == 0
    gap = _checks(r)[SIZES[config]["altered"]]
    assert 0.5 * 0.05 < gap["value"] < 2 * 0.05  # reads what was planted
    assert gap["value"] > gap["limit"]
    assert r["correct"] is False


def test_a_video_that_never_comes_is_not_correct(tmp_path, monkeypatch):
    from generators import corpus_run

    real = corpus_run.read_outputs
    monkeypatch.setattr(corpus_run, "read_outputs",
                        lambda window, path: {k: v for k, v in real(window, path).items()
                                              if k != "resnet50"})
    r = _run(tmp_path, monkeypatch, "resnet50_fp32")
    assert r["correct"] is False


def _fake_profiler(monkeypatch):
    import os
    import shutil

    import tracing

    calls = []

    def start(directory):
        calls.append(directory)

    def stop():
        run_dir = os.path.join(calls[-1], "plugins", "profile", "recorded")
        os.makedirs(run_dir)
        shutil.copy(os.path.join(BENCH, "tests", "data", "pwc_page.xplane.pb"),
                    os.path.join(run_dir, "host.xplane.pb"))

    monkeypatch.setattr(tracing, "start_device_trace", start)
    monkeypatch.setattr(tracing, "stop_device_trace", stop)
    return calls


def test_traced_run_goes_through_the_tracer_and_every_reader(tmp_path, monkeypatch, capfd):
    calls = _fake_profiler(monkeypatch)
    r = _run(tmp_path, monkeypatch, "i3d_pwc_fp32", trace=True)
    assert calls == [str(tmp_path / "cell" / "trace")]
    assert r["correct"] is True and r["failed"] == 0
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    wanted = {m["name"] for m in bench["per_layer"]}
    # the recorded trace is of another day, so the clock rule fits an offset
    # of hours and the idle shares mean nothing here; but every reader is
    # called on the trace under the tracer's directory and finds its input
    assert set(r["metrics"]) == wanted
    assert r["metrics"]["flow_resize_pct"]["value"] == pytest.approx(82.1, abs=0.1)
    assert r["metrics"]["pwc_corr_roofline"]["value"] == pytest.approx(3.93, abs=0.01)
    assert r["device"]["slice_pages"] == 0  # the recording holds one execution
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] >= r["device"]["busy_s"]
    assert len(r["breakdown"]["idle_gaps"]) == 10 and len(r["breakdown"]["device_ops"]) == 10
    err = capfd.readouterr().err
    assert "slice: first video written" in err
    json.dumps(r)


def test_a_window_that_closes_before_its_first_video_is_seen_says_so(tmp_path, monkeypatch):
    from generators import corpus_run

    calls = _fake_profiler(monkeypatch)
    monkeypatch.setattr(corpus_run, "first_written", lambda output_dir: None)
    with pytest.raises(SystemExit) as stopped:
        _run(tmp_path, monkeypatch, "resnet50_fp32", trace=True)
    assert stopped.value.code == ("no slice was traced: the window closed before its "
                                  "first video was written")
    assert calls == []

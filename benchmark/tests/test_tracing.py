"""The traced slice is placed by the window's progress (``tracing.py``): a
fake profiler, a temporary output directory and the generator's own
``first_written``. No start before the first output file, a start within two
polls after it, a stop at ``seconds``, and ``finish()`` before or inside the
slice."""

import os
import time

import pytest

import tracing
from generators import corpus_run


class FakeProfiler:
    def __init__(self):
        self.calls = []

    def start(self, directory):
        self.calls.append(("start", directory, time.perf_counter()))

    def stop(self):
        self.calls.append(("stop", None, time.perf_counter()))

    def names(self):
        return [c[0] for c in self.calls]


def wait_for(condition, timeout=5.0):
    deadline = time.perf_counter() + timeout
    while not condition() and time.perf_counter() < deadline:
        time.sleep(0.005)
    return condition()


@pytest.fixture
def armed(tmp_path, monkeypatch):
    """A tracer armed on an empty output directory, as the generator arms it."""
    def make(seconds):
        fake = FakeProfiler()
        monkeypatch.setattr(tracing, "start_device_trace", fake.start)
        monkeypatch.setattr(tracing, "stop_device_trace", fake.stop)
        out = tmp_path / "out"
        out.mkdir(exist_ok=True)
        tracer = tracing.SliceTracer(str(tmp_path / "trace"), {"seconds": seconds})
        tracer.arm(lambda: corpus_run.first_written(str(out)))
        return tracer, fake, out
    return make


def test_no_start_before_the_first_output_file_and_one_within_two_polls_after(armed):
    tracer, fake, out = armed(seconds=30.0)
    # the warm-up's outputs and a file still being written are not the window's
    (out / "clip0_rgb.npy").write_bytes(b"x")
    (out / "w00000_clip0_rgb.npy.tmp").write_bytes(b"x")
    time.sleep(4 * tracing.POLL_S)
    assert fake.calls == [] and not tracer.started
    (out / "w00000_clip0_rgb.npy").write_bytes(b"x")
    written = time.perf_counter()
    assert wait_for(lambda: tracer.started)
    assert fake.calls[0][:2] == ("start", tracer.directory)
    assert fake.calls[0][2] - written <= 2 * tracing.POLL_S + 0.05
    assert 0.0 <= tracer.seen_lag_s <= tracer.start_lag_s <= 2 * tracing.POLL_S + 0.05
    assert tracer.progress_at == pytest.approx(os.stat(out / "w00000_clip0_rgb.npy").st_mtime)
    tracer.finish()
    assert fake.names() == ["start", "stop"]


def test_the_slice_stops_at_its_seconds(armed):
    tracer, fake, out = armed(seconds=0.3)
    (out / "w00003_clip3_flow.npy").write_bytes(b"x")
    assert wait_for(lambda: fake.names() == ["start", "stop"])
    assert fake.calls[1][2] - fake.calls[0][2] == pytest.approx(0.3, abs=0.1)
    tracer.finish()  # the window closes later: nothing more happens
    assert fake.names() == ["start", "stop"]
    assert tracer.slice_seconds == pytest.approx(0.3, abs=0.1)


def test_finish_before_any_file_traces_nothing(armed):
    tracer, fake, _out = armed(seconds=0.3)
    time.sleep(2 * tracing.POLL_S)
    tracer.finish()
    assert fake.calls == [] and not tracer.started and tracer.slice_seconds == 0.0
    time.sleep(2 * tracing.POLL_S)  # the thread has ended: a late file starts nothing
    assert fake.calls == []


def test_finish_inside_the_slice_keeps_the_time_it_had(armed):
    tracer, fake, out = armed(seconds=30.0)
    (out / "w00000_clip0_rgb.npy").write_bytes(b"x")
    assert wait_for(lambda: tracer.started)
    time.sleep(0.2)
    tracer.finish()
    assert fake.names() == ["start", "stop"]
    assert tracer.slice_seconds == pytest.approx(0.2, abs=0.1)


def test_a_profiler_that_fails_fails_the_run(armed, monkeypatch):
    tracer, _fake, out = armed(seconds=0.1)

    def broken(_directory):
        raise RuntimeError("no profiler")

    monkeypatch.setattr(tracing, "start_device_trace", broken)
    (out / "w00000_clip0_rgb.npy").write_bytes(b"x")
    assert wait_for(lambda: tracer.progress_at is not None)
    with pytest.raises(RuntimeError, match="no profiler"):
        tracer.finish()
    assert not tracer.started

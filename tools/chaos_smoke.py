#!/usr/bin/env python
"""Serving chaos smoke: SIGKILL the daemon at each durability seam, restart,
and assert exactly-once recovery (docs/reliability.md "Serving chaos seams",
docs/serving.md "Crash recovery").

The unit layer (tests/test_wal.py, tests/test_service.py) proves the WAL and
replay mechanics in-process; this script proves them across REAL process
death, driving the CLI surface as an operator would:

1. a batch CLI run produces the reference outputs;
2. for each chaos seam, a daemon subprocess runs with ``VFT_FAULTS`` set to
   ``kill`` (``os._exit(137)``) at that seam:

   - ``wal_sync:kill``  — post-accept, pre-WAL-fsync (the torn-ack crash);
   - ``pool_worker:kill`` — a decode worker dies mid-video;
   - ``device:kill``    — mid-batch, just before the device step dispatches;
   - ``publish:kill``   — post-extract, pre-result-record (outputs + the
     done-manifest exist, the acknowledgement does not);

   a request is dropped into the spool, the daemon dies with exit 137, and a
   restart of the SAME spool (no fault) must recover via the admission WAL:
   the ``done`` result record appears, outputs are byte-identical to the
   batch run, the done-manifest holds each video EXACTLY once (no double
   extraction), and the WAL compacts back to empty after the drain;
3. an ENOSPC drill (``wal_append:raise``) proves degrade-never-crash on a
   live daemon: submits keep completing, ``healthz`` flags ``durable: false``.

A CPU tool, and it stays one: every child is pinned to ``JAX_PLATFORMS=cpu``
and this parent never imports jax — a chip belongs to one process at a time,
so a parent that held it would starve its children, and a child killed
mid-step could leave it locked. The chip's own check is ``chip_smoke.py``
(one process).

Runs on CPU with deterministic random weights::

    JAX_PLATFORMS=cpu VFT_ALLOW_RANDOM_WEIGHTS=1 python tools/chaos_smoke.py

Exit code 0 = pass; any assertion or timeout raises.
"""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMEOUT = float(os.environ.get("VFT_SMOKE_TIMEOUT", "600"))

# (name, VFT_FAULTS spec, what the kill simulates, extra daemon flags).
# The pool_worker seam needs a real decode pool: with the default
# --decode_workers 1 the daemon decodes inline and the seam never runs.
KILL_SEAMS = [
    ("wal_sync", "wal_sync:kill", "post-accept, pre-WAL-fsync", ()),
    ("pool_worker", "pool_worker:kill", "decode worker dies mid-video",
     ("--decode_workers", "2")),
    ("device", "device:kill", "mid-batch, pre-device-step", ()),
    ("publish", "publish:kill", "post-extract, pre-result-publish", ()),
]


def write_video(path, frames, size=(32, 24)):
    import cv2

    w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), 10.0, size)
    rng = np.random.default_rng(frames)
    for _ in range(frames):
        w.write(rng.integers(0, 256, (size[1], size[0], 3), dtype=np.uint8))
    w.release()
    return path


def cli(out_dir, *extra):
    return [sys.executable, os.path.join(REPO, "main.py"),
            "--feature_type", "resnet50", "--on_extraction", "save_numpy",
            "--batch_size", "4", "--output_path", out_dir, *extra]


def daemon_cmd(out_dir, spool, *extra):
    return cli(out_dir, "--serve", "--spool_dir", spool,
               "--idle_flush_sec", "0.05", "--spool_poll_sec", "0.05",
               *extra)


def outputs(out_dir):
    return {os.path.basename(p): np.load(p)
            for p in glob.glob(os.path.join(out_dir, "resnet50", "*.npy"))}


def sock_op(sock_path, op):
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(10.0)
        s.connect(sock_path)
        s.sendall(json.dumps(op).encode() + b"\n")
        buf = b""
        while b"\n" not in buf:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf.split(b"\n", 1)[0].decode())


def drop_request(spool, request_id, payload):
    tmp = os.path.join(spool, f".{request_id}.tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(spool, f"{request_id}.json"))


def await_results(daemon, paths, deadline):
    while time.time() < deadline:
        if daemon.poll() is not None:
            raise AssertionError(
                f"daemon exited early with {daemon.returncode}")
        if all(os.path.exists(p) for p in paths):
            return
        time.sleep(0.2)
    raise AssertionError("timed out waiting for result records")


def wal_records(spool):
    path = os.path.join(spool, "admission.wal")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        recs = []
        for line in f:
            try:
                recs.append(json.loads(line))
            except ValueError:
                recs.append({"rec": "torn"})  # a torn tail is expected here
        return recs


def kill_seam_drill(name, fault, desc, extra, env, root, videos, want):
    spool = os.path.join(root, f"spool_{name}")
    os.makedirs(spool)
    serve_out = os.path.join(root, f"serve_{name}")
    result = os.path.join(spool, "results", "req_chaos.result.json")

    print(f"[chaos] seam {name}: {desc} (VFT_FAULTS={fault})")
    daemon = subprocess.Popen(daemon_cmd(serve_out, spool, *extra),
                              env={**env, "VFT_FAULTS": fault})
    try:
        drop_request(spool, "req_chaos", {"tenant": "alice",
                                          "videos": videos})
        rc = daemon.wait(timeout=TIMEOUT)
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()
    assert rc == 137, f"seam {name}: expected kill exit 137, got {rc}"
    # the crash window: the request was claimed from the spool and admitted
    # to the WAL, but never acknowledged
    assert not os.path.exists(result), \
        f"seam {name}: result record published before the kill"
    admitted = [r for r in wal_records(spool)
                if r.get("rec") == "admitted" and r.get("request") == "req_chaos"]
    assert admitted, f"seam {name}: no admitted WAL record survived the kill"

    print(f"[chaos] seam {name}: restarting over the same spool (recovery)")
    daemon = subprocess.Popen(daemon_cmd(serve_out, spool, *extra), env=env)
    try:
        await_results(daemon, [result], time.time() + TIMEOUT)
        daemon.send_signal(signal.SIGTERM)
        assert daemon.wait(timeout=TIMEOUT) == 0, daemon.returncode
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()

    with open(result) as f:
        record = json.load(f)
    assert record["state"] == "done", (name, record)
    assert sorted(record["done"]) == sorted(
        os.path.abspath(v) for v in videos), (name, record)

    got = outputs(serve_out)
    assert set(got) == set(want), (name, sorted(got), sorted(want))
    for fname in sorted(want):
        assert got[fname].tobytes() == want[fname].tobytes(), \
            f"seam {name}: {fname} differs from the batch run after recovery"

    # exactly-once: every video appears ONCE in the done-manifest — a seam
    # that re-extracted already-published work would append a second record
    with open(os.path.join(serve_out, "resnet50",
                           ".done_manifest.jsonl")) as f:
        done = [json.loads(line)["video"] for line in f]
    assert sorted(set(done)) == sorted(
        os.path.abspath(v) for v in videos), (name, done)
    assert len(done) == len(set(done)), \
        f"seam {name}: duplicate done-manifest records — not exactly-once"

    # the acknowledged+published request resolved its WAL entry; the drain
    # compacted the log back to empty
    assert wal_records(spool) == [], (name, wal_records(spool))
    print(f"[chaos] seam {name}: recovered exactly-once, byte parity ok")


def enospc_drill(env, root, videos):
    """wal_append:raise = the ENOSPC drill: the daemon must keep serving
    (non-durable, loudly flagged), never crash."""
    spool = os.path.join(root, "spool_enospc")
    os.makedirs(spool)
    serve_out = os.path.join(root, "serve_enospc")
    result = os.path.join(spool, "results", "req_degraded.result.json")
    print("[chaos] ENOSPC drill: VFT_FAULTS=wal_append:raise "
          "(degrade, keep serving)")
    daemon = subprocess.Popen(daemon_cmd(serve_out, spool),
                              env={**env, "VFT_FAULTS": "wal_append:raise"})
    try:
        drop_request(spool, "req_degraded", {"tenant": "alice",
                                             "videos": videos})
        await_results(daemon, [result], time.time() + TIMEOUT)
        health = sock_op(os.path.join(spool, "control.sock"),
                         {"op": "healthz"})
        assert health["ok"], health
        assert health["wal"]["enabled"] is True, health["wal"]
        assert health["wal"]["durable"] is False, health["wal"]
        daemon.send_signal(signal.SIGTERM)
        assert daemon.wait(timeout=TIMEOUT) == 0, daemon.returncode
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()
    with open(result) as f:
        record = json.load(f)
    assert record["state"] == "done", record
    print("[chaos] ENOSPC drill: served while degraded, healthz flagged it")


def main() -> int:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "VFT_ALLOW_RANDOM_WEIGHTS": "1"}
    env.pop("VFT_FAULTS", None)
    root = tempfile.mkdtemp(prefix="vft_chaos_smoke_")
    videos = [write_video(os.path.join(root, f"v{i}.mp4"), n)
              for i, n in enumerate((3, 6))]

    print("[chaos] batch reference run")
    subprocess.run(cli(os.path.join(root, "batch"), "--video_paths", *videos),
                   env=env, check=True, timeout=TIMEOUT)
    want = outputs(os.path.join(root, "batch"))
    assert want, "batch reference run produced no outputs"

    for name, fault, desc, extra in KILL_SEAMS:
        kill_seam_drill(name, fault, desc, extra, env, root, videos, want)
    enospc_drill(env, root, videos)

    print(f"[chaos] PASS: {len(KILL_SEAMS)} kill seams recovered "
          "exactly-once with byte parity; ENOSPC degraded without a crash")
    return 0


if __name__ == "__main__":
    sys.exit(main())

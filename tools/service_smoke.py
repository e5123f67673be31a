#!/usr/bin/env python
"""Service smoke: spool-directory ingest → drain → manifest/output parity.

The CI-runnable end-to-end check for the always-on daemon (docs/serving.md),
driving the REAL CLI surface as an operator would — no test harness imports:

1. two per-tenant batch CLI runs produce the reference outputs;
2. a daemon subprocess (``--serve``, spool ingest, real signals, a
   ``--cache_dir`` feature cache) serves the same videos as two tenant
   requests dropped into the spool;
3. a RESUBMIT of alice's videos must be served entirely from the feature
   cache (``cache_hits`` in its result record, hits in the socket ``stats``
   op — docs/caching.md);
3b. telemetry (docs/observability.md): the daemon runs with
   ``--telemetry_dir``; the script asserts the versioned ``stats`` payload
   (``"schema": 1`` + per-tenant latency summaries), hits ``healthz`` and
   ``metrics`` (Prometheus text), runs one ``profile start/stop`` cycle
   against the live daemon, and — after the drain — exports the journal
   with ``python -m video_features_tpu.obs.export`` and validates the
   Chrome trace parses as JSON with a complete span chain per request;
4. the daemon co-loads a second model (``--serve_models r21d_rgb``,
   docs/serving.md): a mixed-traffic step submits carol's request with
   ``"feature_type": "r21d_rgb"`` to the SAME daemon — carol's two videos
   carry DIFFERENT native geometries, so the daemon serves mixed-geometry
   traffic through the default ragged paged dispatch (docs/performance.md)
   — and asserts byte-parity against a single-model r21d batch run,
   per-model sections plus the paged counters (``pages_dispatched``,
   ``max_in_flight`` ≥ 2, ``page_occupancy``) in the socket ``stats`` op,
   the ``vft_page_occupancy`` gauge in the ``metrics`` op, and a clean
   ``rejected`` record for a request naming an unloaded model;
5. SIGTERM drains it, and the script asserts exit code 0, ``done`` result
   records for every request, complete per-model done-manifests, and
   byte-identical ``.npy`` outputs against the batch runs;
6. a second, dedicated daemon runs with ``--device_preproc`` (the raw-pixels
   wire — docs/performance.md) and serves one mixed-geometry request: the
   outputs must track a ``--device_preproc`` batch run to float32 ulp level
   (the daemon's paged dispatch runs the fused resize at page shape, so
   byte-parity is not the contract there), and the ``stats`` op must report
   the decode/transfer stage split — the operator meter showing the decode
   pool shed the per-frame PIL work.

A CPU tool, and it stays one: every child is pinned to ``JAX_PLATFORMS=cpu``
and this parent never imports jax — a chip belongs to one process at a time,
so a parent that held it would starve its children, and a child killed
mid-step could leave it locked. The chip's own check is ``chip_smoke.py``
(one process).

Runs on CPU with deterministic random weights::

    JAX_PLATFORMS=cpu VFT_ALLOW_RANDOM_WEIGHTS=1 python tools/service_smoke.py

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


def write_video(path, frames, size=(32, 24)):
    import cv2

    w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"), 10.0, size)
    rng = np.random.default_rng(frames)
    for _ in range(frames):
        w.write(rng.integers(0, 256, (size[1], size[0], 3), dtype=np.uint8))
    w.release()
    return path


def cli(out_dir, *extra, feature="resnet50"):
    return [sys.executable, os.path.join(REPO, "main.py"),
            "--feature_type", feature, "--on_extraction", "save_numpy",
            "--batch_size", "4", "--output_path", out_dir, *extra]


def outputs(out_dir, feature="resnet50"):
    return {os.path.basename(p): np.load(p)
            for p in glob.glob(os.path.join(out_dir, feature, "*.npy"))}


def sock_op(sock_path, op):
    """One line-JSON round-trip on the daemon's control socket (stdlib only,
    like the rest of this operator-shaped script)."""
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


def main() -> int:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "VFT_ALLOW_RANDOM_WEIGHTS": "1"}
    root = tempfile.mkdtemp(prefix="vft_service_smoke_")
    videos = {"alice": [write_video(os.path.join(root, f"a{i}.mp4"), n)
                        for i, n in enumerate((3, 6))],
              "bob": [write_video(os.path.join(root, f"b{i}.mp4"), n)
                      for i, n in enumerate((5, 2))]}
    # carol's videos go to the co-loaded r21d_rgb model (>=16 frames: one
    # full reference stack each) with DIFFERENT native geometries — r21d
    # keys paged bucket families per decoded shape, so this is the
    # mixed-geometry paged-serving traffic the stats assertions below pin
    r21d_videos = [write_video(os.path.join(root, "c0.mp4"), 16),
                   write_video(os.path.join(root, "c1.mp4"), 18,
                               size=(48, 32))]

    print("[smoke] per-tenant batch reference runs")
    for tenant, vids in videos.items():
        subprocess.run(cli(os.path.join(root, f"batch_{tenant}"),
                           "--video_paths", *vids),
                       env=env, check=True, timeout=TIMEOUT)
    print("[smoke] single-model r21d_rgb batch reference run")
    subprocess.run(cli(os.path.join(root, "batch_r21d"),
                       "--video_paths", *r21d_videos, feature="r21d_rgb"),
                   env=env, check=True, timeout=TIMEOUT)

    spool = os.path.join(root, "spool")
    os.makedirs(spool)
    serve_out = os.path.join(root, "serve")
    print("[smoke] starting the daemon (co-resident models: resnet50 + "
          "r21d_rgb)")
    telemetry_dir = os.path.join(root, "telemetry")
    daemon = subprocess.Popen(
        cli(serve_out, "--serve", "--spool_dir", spool,
            "--idle_flush_sec", "0.05", "--spool_poll_sec", "0.05",
            "--serve_models", "r21d_rgb",
            "--cache_dir", os.path.join(root, "cache"),
            "--telemetry_dir", telemetry_dir),
        env=env)
    try:
        for tenant, vids in videos.items():
            drop_request(spool, f"req_{tenant}",
                         {"tenant": tenant, "videos": vids})

        results = {t: os.path.join(spool, "results", f"req_{t}.result.json")
                   for t in videos}
        await_results(daemon, results.values(), time.time() + TIMEOUT)

        for tenant, path in results.items():
            with open(path) as f:
                record = json.load(f)
            assert record["state"] == "done", (tenant, record)
            assert sorted(record["done"]) == sorted(
                os.path.abspath(v) for v in videos[tenant]), record
            print(f"[smoke] request {tenant}: done "
                  f"({len(record['done'])} videos)")

        # resubmit alice's videos: the feature cache must serve every one
        # (zero device steps) and say so in the result record + stats op
        print("[smoke] resubmitting alice's videos (expect cache hits)")
        drop_request(spool, "req_alice2",
                     {"tenant": "alice", "videos": videos["alice"]})
        resubmit = os.path.join(spool, "results", "req_alice2.result.json")
        await_results(daemon, [resubmit], time.time() + TIMEOUT)
        with open(resubmit) as f:
            record = json.load(f)
        assert record["state"] == "done", record
        assert record["cache_hits"] == len(videos["alice"]), record
        stats = sock_op(os.path.join(spool, "control.sock"), {"op": "stats"})
        # versioned payload: external scrapers pin the schema key and treat
        # a bump as a breaking change (docs/serving.md documents the tree)
        assert stats["schema"] == 1, stats.get("schema")
        assert stats["cache"]["hits"] >= len(videos["alice"]), stats["cache"]
        assert stats["cache"]["hit_rate"] > 0, stats["cache"]
        print(f"[smoke] resubmit served from cache "
              f"({record['cache_hits']} hits; cumulative hit rate "
              f"{stats['cache']['hit_rate']:.0%})")

        # telemetry ops: healthz liveness, Prometheus metrics, and one
        # profile start/stop cycle against the LIVE daemon
        sock = os.path.join(spool, "control.sock")
        health = sock_op(sock, {"op": "healthz"})
        assert health["ok"] and health["stale"] is False, health
        assert health["uptime_sec"] > 0, health
        metrics = sock_op(sock, {"op": "metrics"})
        assert metrics["ok"] and metrics["schema"] == 1, metrics.get("ok")
        assert "vft_e2e_latency_seconds_bucket" in metrics["prometheus"], \
            metrics["prometheus"][:400]
        latency = {s["labels"]["tenant"]: s
                   for s in stats["latency"]["e2e"]}
        assert {"alice", "bob"} <= set(latency), stats["latency"]
        assert all(s["p50"] <= s["p99"] for s in latency.values()), latency
        print(f"[smoke] healthz ok (last step {health['last_step_age_sec']}s"
              f" ago); e2e p99: "
              + ", ".join(f"{t}={s['p99']}s" for t, s in latency.items()))
        prof = sock_op(sock, {"op": "profile", "action": "start"})
        assert prof["ok"], prof
        prof2 = sock_op(sock, {"op": "profile", "action": "stop"})
        assert prof2["ok"], prof2
        print(f"[smoke] profile cycle ok → {prof2['trace_dir']}")

        # two-model mixed traffic: carol's r21d_rgb request rides the SAME
        # daemon/mesh as the resnet50 tenants; byte parity vs the
        # single-model batch run is asserted after the drain below
        print("[smoke] submitting carol's r21d_rgb request (co-resident "
              "model)")
        drop_request(spool, "req_carol",
                     {"tenant": "carol", "videos": r21d_videos,
                      "feature_type": "r21d_rgb"})
        carol = os.path.join(spool, "results", "req_carol.result.json")
        await_results(daemon, [carol], time.time() + TIMEOUT)
        with open(carol) as f:
            record = json.load(f)
        assert record["state"] == "done", record
        assert record["feature_type"] == "r21d_rgb", record

        # a request naming an UNLOADED model must produce a clean rejection
        # record, not a daemon crash or a silent terminal failure
        print("[smoke] submitting a request for an unloaded model "
              "(expect rejection record)")
        drop_request(spool, "req_unknown",
                     {"tenant": "carol", "videos": videos["alice"],
                      "feature_type": "vggish"})
        unknown = os.path.join(spool, "results", "req_unknown.result.json")
        await_results(daemon, [unknown], time.time() + TIMEOUT)
        with open(unknown) as f:
            record = json.load(f)
        assert record["state"] == "rejected", record
        assert "not loaded" in record["reason"], record
        assert os.path.exists(os.path.join(spool,
                                           "req_unknown.json.rejected"))

        stats = sock_op(os.path.join(spool, "control.sock"), {"op": "stats"})
        assert stats["serving_models"] == ["resnet50", "r21d_rgb"], stats
        assert set(stats["models"]) == {"resnet50", "r21d_rgb"}, \
            stats["models"]
        for model, m in stats["models"].items():
            assert m["videos_ok"] > 0 and m["dispatched_slots"] > 0, \
                (model, m)
        print(f"[smoke] per-model stats: "
              + ", ".join(f"{m}: occupancy {s['occupancy']}"
                          for m, s in stats["models"].items()))

        # ragged paged dispatch (docs/performance.md): the default-on paged
        # mode must have carried the mixed-geometry traffic above — pages
        # dispatched, the double-buffered ring observed at depth >= 2, and
        # page_occupancy reported in the stats op + the metrics gauge
        packing = stats["packing"]
        assert packing["pages_dispatched"] > 0, packing
        assert packing["max_in_flight"] >= 2, packing
        assert packing["page_occupancy"] > 0, packing
        metrics = sock_op(os.path.join(spool, "control.sock"),
                          {"op": "metrics"})
        assert "vft_page_occupancy" in metrics["prometheus"], \
            metrics["prometheus"][:400]
        print(f"[smoke] paged dispatch: {packing['pages_dispatched']} pages, "
              f"max {packing['max_in_flight']} in flight, page occupancy "
              f"{packing['page_occupancy']}")

        print("[smoke] SIGTERM → graceful drain")
        daemon.send_signal(signal.SIGTERM)
        assert daemon.wait(timeout=TIMEOUT) == 0, daemon.returncode
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()

    got = outputs(serve_out)
    want = {**outputs(os.path.join(root, "batch_alice")),
            **outputs(os.path.join(root, "batch_bob"))}
    assert set(got) == set(want), (sorted(got), sorted(want))
    for name in sorted(want):
        assert got[name].tobytes() == want[name].tobytes(), \
            f"{name}: daemon output differs from the batch run"
    # the co-resident model's outputs: byte-identical to the single-model
    # r21d batch run, in r21d's own output subtree
    got_r = outputs(serve_out, feature="r21d_rgb")
    want_r = outputs(os.path.join(root, "batch_r21d"), feature="r21d_rgb")
    assert set(got_r) == set(want_r) and got_r, (sorted(got_r),
                                                 sorted(want_r))
    for name in sorted(want_r):
        assert got_r[name].tobytes() == want_r[name].tobytes(), \
            f"{name}: two-model daemon r21d output differs from batch run"
    manifest = os.path.join(serve_out, "resnet50", ".done_manifest.jsonl")
    # cache-hit replays append their own records (resume-vs-cache layering
    # is deterministic), so count DISTINCT videos, not lines
    with open(manifest) as f:
        done = {json.loads(line)["video"] for line in f}
    assert len(done) == 4, f"done-manifest incomplete: {sorted(done)}"
    with open(os.path.join(serve_out, "r21d_rgb",
                           ".done_manifest.jsonl")) as f:
        done_r = {json.loads(line)["video"] for line in f}
    assert len(done_r) == len(r21d_videos), sorted(done_r)

    # telemetry journal → Chrome trace: the exported file must parse as
    # JSON and hold a COMPLETE request span (admitted→done, ph "X") for
    # every accepted request, plus ≥1 per-video span each
    print("[smoke] exporting the telemetry journal to a Chrome trace")
    journal = os.path.join(telemetry_dir, "events.jsonl")
    trace_path = os.path.join(root, "trace.json")
    subprocess.run([sys.executable, "-m", "video_features_tpu.obs.export",
                    journal, "-o", trace_path],
                   env=env, check=True, timeout=60, cwd=REPO)
    with open(trace_path) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    req_spans = {e["args"].get("request") for e in xs
                 if e["name"] == "request"}
    accepted = {"req_alice", "req_bob", "req_alice2", "req_carol"}
    assert accepted <= req_spans, (sorted(req_spans), sorted(accepted))
    per_video = [e for e in xs if e["name"] in ("queue_wait", "process")]
    assert len(per_video) >= len(videos["alice"]) + len(videos["bob"]), \
        len(per_video)
    # the rejected request journaled its rejection, not a span
    instants = {(e.get("name"), e["args"].get("request"))
                for e in trace["traceEvents"] if e.get("ph") == "i"}
    assert ("request_rejected", "req_unknown") in instants
    print(f"[smoke] trace ok: {len(req_spans)} request spans, "
          f"{len(per_video)} per-video spans")

    # --device_preproc serving: a dedicated daemon with the raw-pixels wire
    # on, one mixed-geometry request, parity vs a --device_preproc batch run
    print("[smoke] --device_preproc daemon: one mixed-geometry request")
    dave_videos = [write_video(os.path.join(root, "d0.mp4"), 4),
                   write_video(os.path.join(root, "d1.mp4"), 5,
                               size=(48, 36))]
    subprocess.run(cli(os.path.join(root, "batch_dave"), "--device_preproc",
                       "--video_paths", *dave_videos),
                   env=env, check=True, timeout=TIMEOUT)
    spool2 = os.path.join(root, "spool_dp")
    os.makedirs(spool2)
    dp_out = os.path.join(root, "serve_dp")
    daemon2 = subprocess.Popen(
        cli(dp_out, "--serve", "--spool_dir", spool2, "--device_preproc",
            "--idle_flush_sec", "0.05", "--spool_poll_sec", "0.05"),
        env=env)
    try:
        drop_request(spool2, "req_dave",
                     {"tenant": "dave", "videos": dave_videos})
        dave = os.path.join(spool2, "results", "req_dave.result.json")
        await_results(daemon2, [dave], time.time() + TIMEOUT)
        with open(dave) as f:
            record = json.load(f)
        assert record["state"] == "done", record
        # the stats op's per-stage split: decode ran (and, with the raw
        # wire, did NO PIL work — the resize is fused into the step), and
        # the host→device transfer stage is accounted separately
        stats_dp = sock_op(os.path.join(spool2, "control.sock"),
                           {"op": "stats"})
        stages = stats_dp["stages"]
        assert stages.get("decode", 0) > 0, stages
        assert "transfer" in stages, stages
        assert stats_dp["transfer"]["bytes"] > 0, stats_dp["transfer"]
        print(f"[smoke] device_preproc stage split: decode "
              f"{stages['decode']}s, transfer {stages['transfer']}s "
              f"({stats_dp['transfer']['bytes']} B staged)")
        daemon2.send_signal(signal.SIGTERM)
        assert daemon2.wait(timeout=TIMEOUT) == 0, daemon2.returncode
    finally:
        if daemon2.poll() is None:
            daemon2.kill()
            daemon2.wait()
    got_dp = outputs(dp_out)
    want_dp = outputs(os.path.join(root, "batch_dave"))
    assert set(got_dp) == set(want_dp) and got_dp, (sorted(got_dp),
                                                    sorted(want_dp))
    for name in sorted(want_dp):
        w, g = want_dp[name], got_dp[name]
        assert w.shape == g.shape, name
        scale = max(1.0, float(np.abs(w).max()))
        assert np.abs(w - g).max() <= 1e-5 * scale, \
            f"{name}: device_preproc daemon output drifts past ulp level"
    print(f"[smoke] device_preproc outputs track the batch run "
          f"({len(want_dp)} files, ulp-level)")

    print(f"[smoke] PASS: {len(want)} + {len(want_r)} outputs "
          "byte-identical across two co-resident models, manifests intact, "
          "telemetry trace complete, device_preproc serving verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())

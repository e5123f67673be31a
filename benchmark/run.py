#!/usr/bin/env python3
"""One process, one cell, once: set up, warm, measure, check, print.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: the configuration (``configs/<config>.json``), the traffic
mix (``traffic/<mix>.json``) and the generator it names
(``generators/<name>.py``), the plain reference (``reference/<name>.py``), the
operation counter (``flops/<name>.py``) and one reader per per-layer metric
(``layer_metrics/<metric>.py``). Nothing here names any of them.

No accelerator, or fewer chips than the cell asks for: exit 3, no result line.
The last line of standard output is the result object; the numbers compared
for ``correct`` are its last key and the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# The compile cache is where JAX_COMPILATION_CACHE_DIR says, and inside the
# checkout at a fixed path where it says nothing; the program takes the
# directory JAX is given and sets no other. Only a size cap is lifted: under
# the chip machine's 192 MiB the I3D cell's executables (380 MB) evict each
# other, so that every run compiles (PERF.md §6).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(entries, cell: str):
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform!r}", file=sys.stderr)
        raise SystemExit(3)
    return devs


def make_context(cell: dict, seed: int, seconds: float, trace: bool, variant: str,
                 scratch: str = None, conf: dict = None, traffic: dict = None):
    conf = conf or load_json(HERE, "configs", cell["config"] + ".json")
    traffic = traffic or load_json(HERE, "traffic", cell["traffic"] + ".json")
    scratch = scratch or os.path.join(ROOT, "output", "benchmark", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    ctx = types.SimpleNamespace(
        cell=cell, conf=conf, traffic=traffic, seed=seed, seconds=seconds,
        trace=trace, variant=variant, chips=int(cell["chips"]), scratch=scratch,
        root=ROOT, t_start=T_START, compiles=0, in_window=False,
        before_window=lambda progressed: None, after_window=lambda: None, tracer=None)
    return ctx


def write_weights(ctx) -> dict:
    """Weights from the seed, by the reference's shape table; the program
    finds them through its checkpoint directory."""
    from weights import make_weights, write_npz

    ref = importlib.import_module("reference." + ctx.conf["reference"])
    wdir = os.path.join(ctx.scratch, "weights")
    flat = {}
    fixed = ctx.conf.get("fixed_weights", {})
    for name, spec in ref.weight_specs().items():
        # a file named under `fixed_weights` is the one thing not drawn from
        # --seed: the program compiles it into its executable (PERF.md §7)
        flat[name] = make_weights(spec, fixed.get(name, ctx.seed), name)
        write_npz(wdir, name, flat[name])
    os.environ["VFT_CHECKPOINT_DIR"] = wdir
    os.environ.pop("VFT_ALLOW_RANDOM_WEIGHTS", None)
    return flat


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             variant: str = "", scratch: str = None, devices=None,
             conf: dict = None, traffic: dict = None) -> dict:
    """Everything after the look for a chip. ``devices`` is what JAX found;
    ``conf``/``traffic`` stand in for the cell's files in the tests."""
    import jax
    from jax import monitoring

    ctx = make_context(cell, seed, seconds, trace, variant, scratch, conf, traffic)
    ctx.run_seconds = float(bench["run_seconds"])
    gen = importlib.import_module("generators." + ctx.traffic["generator"])
    devices = devices or jax.devices()

    def on_event(name, _secs, **_kw):
        if ctx.in_window and name == COMPILE_EVENT:
            ctx.compiles += 1

    monitoring.register_event_duration_secs_listener(on_event)

    if trace:
        from tracing import SliceTracer

        ctx.tracer = SliceTracer(os.path.join(ctx.scratch, "trace"), ctx.conf["trace_slice"])

    def before_window(progressed):
        # `progressed()`: None until the window has finished its first unit
        # of work, then the Unix time at which it did (the generator's word)
        ctx.in_window = True
        if ctx.tracer:
            ctx.tracer.arm(progressed)

    def after_window():
        ctx.in_window = False
        if ctx.tracer:
            ctx.tracer.finish()

    ctx.before_window, ctx.after_window = before_window, after_window

    flat = write_weights(ctx)
    log("weights written")
    window = gen.run_window(ctx)
    log(f"window closed: {window['attempted']} attempted, {window['failed']} failed, "
        f"{window['wall_s']:.2f} s, {ctx.compiles} compile(s) inside")
    if trace:
        t = ctx.tracer
        if not t.started:
            raise SystemExit("no slice was traced: the window closed before its first "
                             "video was written")
        log(f"slice: first video written {t.progress_at - t.armed_at:.3f} s into the window, "
            f"seen {t.seen_lag_s:.3f} s and traced from {t.start_lag_s:.3f} s after that, "
            f"for {t.slice_seconds:.3f} s")
    # The runtime keeps a running program's temporaries in a reservation of
    # its own, which peak_bytes_in_use (the buffers) leaves out (PERF.md §4
    # has the probe). The two peaks need not fall at the same moment, so the
    # peak reported is the larger of them, which the true peak cannot be
    # under, and both stand beside it.
    mem = [d.memory_stats() or {} for d in devices[:ctx.chips]]
    log(f"memory_stats: {json.dumps({k: int(v) for k, v in mem[0].items()})}")
    fullest = max(mem, key=lambda m: max(int(m.get("peak_bytes_in_use", 0)),
                                         int(m.get("peak_bytes_reserved", 0))))
    peak_in_use = int(fullest.get("peak_bytes_in_use", 0))
    peak_reserved = int(fullest.get("peak_bytes_reserved", 0))
    gen.release(window)

    from check import compare

    t_check = time.perf_counter()
    correct, numbers = compare(ctx, gen, window, flat)
    log(f"reference and comparison took {time.perf_counter() - t_check:.1f} s")
    if window["failed"]:
        correct = False

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peak_in_use, peak_reserved),
              "peak_bytes_in_use": peak_in_use, "peak_bytes_reserved": peak_reserved,
              "memory_limit_bytes": int(fullest.get("bytes_limit", 0))}
    window["compiles"] = ctx.compiles
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"]}
    if not trace:
        wanted = metrics_for(bench["end_to_end"], cell["name"])
        result["metrics"] = {
            m["name"]: {"value": window["end_to_end"][m["name"]], "unit": m["unit"]}
            for m in wanted if m["name"] in window["end_to_end"]}
    else:
        from layer_metrics._spans import name_idle_gaps
        from trace_reduce import reduce_trace_dir

        reduction = reduce_trace_dir(ctx.tracer.directory, ctx.chips)
        facts = {"wall_s": window["wall_s"], "rows": window["rows"],
                 "compiles": ctx.compiles, "chips": ctx.chips,
                 "device_kind": devices[0].device_kind,
                 "conf": ctx.conf,
                 "peaks": load_json(HERE, "peaks.json")}
        result["metrics"] = {}
        for m in metrics_for(bench["per_layer"], cell["name"]):
            reader = importlib.import_module("layer_metrics." + m["name"])
            value = reader.read(reduction, window["stats"], facts)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        # both from the trace's own clock: the span from the first traced
        # operation's start to the last one's end, and the union of the
        # operations' intervals inside it. The host's clock around
        # start_trace/stop_trace (logged for comparison) is another clock
        log(f"traced: busy {reduction['busy_s']:.4f} s of span {reduction['span_s']:.4f} s, "
            f"{reduction['slice_pages']} whole page(s); "
            f"the host counted {ctx.tracer.slice_seconds:.4f} s between start and stop")
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["span_s"]
        # whole executions of the page program in the slice: what the
        # per-layer numbers of this line rest on
        device["slice_pages"] = reduction["slice_pages"]
        result["breakdown"] = {"device_ops": reduction["top_ops"][:10],
                               "idle_gaps": name_idle_gaps(reduction, window["stats"])[:10]}
    result["device"] = device
    result["window_s"] = window["wall_s"]
    result["checks"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    # "control": the control of `correct`, the program's own next precision
    # down (`variants.control` of the configuration's file). Never passed by
    # the driver.
    ap.add_argument("--variant", default="")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    devices = require_chips(int(cell["chips"]))
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      args.variant, devices=devices)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The fault a cell of this kind can have, planted in the program: an answer
altered where it is produced. One entry per ``feature_type``, each a patch of
the epilogue of that configuration's page program, so that everything after
it (the fetch, the writer, the ``.npy`` files the comparison reads) is the
program's own.

Used by ``test_window_cpu.py`` at a size a test can hold, and on the chip at
the cell's own size:

    chiprun -- python3 benchmark/tests/faults.py <cell> <seed>
"""

from __future__ import annotations

import contextlib
import json
import sys

FACTOR = 1.05


def _patch_i3d():
    """Row 0 of every page: the flow stream's 1024 numbers scaled, in the
    composite forward that stacks the two streams."""
    from video_features_tpu.extractors.i3d import ExtractI3D

    real = ExtractI3D._composite_forward

    def altered(self, params, stacks_u8):
        out = real(self, params, stacks_u8)  # (rows, streams, 1024)
        return out.at[0, self.streams.index("flow")].multiply(FACTOR)

    return ExtractI3D, "_composite_forward", altered


def _patch_resnet50():
    """Row 0 of every page scaled, in the paged program's masking epilogue."""
    from video_features_tpu.parallel import pages

    real = pages.mask_rows

    def altered(rows, valid):
        return real(rows, valid).at[0].multiply(FACTOR)

    return pages, "mask_rows", altered


PATCHES = {"i3d": _patch_i3d, "resnet50": _patch_resnet50}


@contextlib.contextmanager
def altered_answer(feature_type: str):
    owner, name, altered = PATCHES[feature_type]()
    real = getattr(owner, name)
    setattr(owner, name, altered)
    try:
        yield
    finally:
        setattr(owner, name, real)


def main(argv) -> int:
    from conftest import ROOT  # puts the checkout and benchmark/ on the path

    import run as bench_run

    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, argv[0])
    conf = bench_run.load_json(ROOT, "benchmark", "configs", cell["config"] + ".json")
    devices = bench_run.require_chips(int(cell["chips"]))
    with altered_answer(conf["feature_type"]):
        result = bench_run.run_cell(bench, cell, int(argv[1]), float(bench["run_seconds"]),
                                    False, devices=devices)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

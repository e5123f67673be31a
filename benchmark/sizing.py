#!/usr/bin/env python3
"""Memory reckoning for a configuration's batch, without the chip.

    JAX_PLATFORMS=cpu python benchmark/sizing.py <config> [<rows> ...]

Builds the configuration's extractor on the CPU with the fields its file
sets (page budget and precision included), takes the paged program the packed
loop compiles (``paged_program(forward)`` with the row table), and compiles it
for a *described* ``v5e:2x2`` device at each page size given, or at the
configuration's own. Which forward, which params and the shape of one row are
the configuration's to say (``sizing`` in its file: ``forward`` and ``params``
name attributes of the extractor, ``row_shape`` one uint8 row of a page,
``page_rows`` the page the cell commits), so a new configuration is reckoned
without an edit here.
Prints ``compiled.memory_analysis()`` and, for programs with the PWC net, the
number of Mosaic custom calls in the compiled text: ``--pwc_corr auto`` asks
``jax.default_backend()``, which is the CPU here, so the lowering is steered
to the kernels by patching that one function while the program is traced.

This is a compile, never a chip run: it says what one program needs, not what
the process holds (params, two pages in flight, the staging of the next).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["VFT_ALLOW_RANDOM_WEIGHTS"] = "1"

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv):
    config_name = argv[0]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "configs", config_name + ".json")) as f:
        conf = json.load(f)

    from video_features_tpu.config import ExtractionConfig
    from video_features_tpu.extractors import get_extractor
    from video_features_tpu.parallel.pages import paged_program

    sizing = conf["sizing"]
    rows_list = [int(r) for r in argv[1:]] or [int(sizing["page_rows"])]
    scratch = os.path.join(os.path.dirname(HERE), "output", "benchmark", "sizing")
    fields = dict(conf["extraction"])
    fields.update(feature_type=conf["feature_type"], num_devices=1,
                  output_path=os.path.join(scratch, "out"),
                  tmp_path=os.path.join(scratch, "tmp"))
    ex = get_extractor(ExtractionConfig(**fields))
    forward, params = getattr(ex, sizing["forward"]), getattr(ex, sizing["params"])
    row = tuple(int(d) for d in sizing["row_shape"])

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    pshape = jax.tree_util.tree_map(shape, params)
    param_bytes = sum(int(a.size) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(params))
    real_backend = jax.default_backend
    for rows in rows_list:
        page = jax.ShapeDtypeStruct((rows,) + row, jnp.uint8, sharding=one)
        table = jax.ShapeDtypeStruct((rows, 3), jnp.int32, sharding=one)
        t0 = time.time()
        jax.default_backend = lambda: "tpu"
        try:
            lowered = jax.jit(paged_program(forward)).lower(pshape, page, table)
        finally:
            jax.default_backend = real_backend
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "config": config_name, "page_rows": rows,
            "extraction": conf["extraction"],
            "temp_bytes": m.temp_size_in_bytes,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "generated_code_bytes": m.generated_code_size_in_bytes,
            "param_bytes": param_bytes,
            "mosaic_calls": text.count("tpu_custom_call"),
            "compile_s": round(time.time() - t0, 1)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])

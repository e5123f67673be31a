#!/usr/bin/env python3
"""The memory reckoning of a token page for ANY text-stream configuration
(``sizing_tokens.py`` names ``models.laguna`` in its body and cannot take
another type; this one finds the model module by the configuration's
``feature_type``): compiles the page program for a described ``v5e:2x2``
device, at the configuration's ``page_tokens``, with the weights' shapes as
arguments. A compile, never a chip run; nothing here is a speed.

    JAX_PLATFORMS=cpu python benchmark/sizing_token_pages.py sarvam_105b_bf16 [page_tokens ...]

The model's ``forward`` takes its two Pallas kernels compiled unless told
``interpret``, so the described device gets what the chip gets.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def reckon(config: str, page_tokens: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from video_features_tpu.extractors.token_pages import ATTENTION_BLOCK, SEGMENT_TOKENS_MIN
    from video_features_tpu.parallel.pages import token_paged_program

    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        conf = json.load(f)
    model = importlib.import_module("video_features_tpu.models." + conf["feature_type"])
    ref = importlib.import_module("reference." + conf["reference"])
    spec = ref.weight_specs()[conf["feature_type"]]
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg = model.PUBLISHED
    share = model.share_of(list(spec))
    shapes = jax.eval_shape(lambda: model.stack_checkpoint(
        cfg, list(spec), lambda name: np.zeros(spec[name], np.float32))[0])
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
    page_rows = page_tokens // SEGMENT_TOKENS_MIN
    page = jax.ShapeDtypeStruct((4, page_tokens), jnp.int32, sharding=one)
    table = jax.ShapeDtypeStruct((page_rows, 3), jnp.int32, sharding=one)

    def forward(p, pg):
        return model.forward(cfg, share, page_rows, min(ATTENTION_BLOCK, page_tokens), p, pg)

    t0 = time.perf_counter()
    compiled = jax.jit(token_paged_program(forward), donate_argnums=(2,)).lower(
        params, page, table).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    return {"config": config, "page_tokens": page_tokens, "page_rows": page_rows,
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": int(m.argument_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "code_bytes": int(m.generated_code_size_in_bytes),
            "mosaic_calls": text.count("tpu_custom_call")}


if __name__ == "__main__":
    name = sys.argv[1]
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        default = json.load(f)["extraction"]["page_tokens"]
    for tokens in [int(a) for a in sys.argv[2:]] or [default]:
        print(json.dumps(reckon(name, tokens)), flush=True)

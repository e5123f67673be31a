"""Real two-process jax.distributed bootstrap over the loopback "DCN".

The reference's only multi-machine mechanism is manually split file lists
(``/root/reference/gen_file_list.py:6-21``); here the equivalent is
``maybe_initialize_distributed`` + ``shard_video_list``. This test launches TWO
actual Python processes that join one JAX distributed job via a localhost
coordinator (the same code path a TPU pod uses over DCN), then asserts the
processes agree on the world size and take disjoint, exhaustive, round-robin
video shards.
"""
# fast-registry: default tier — loopback two-process jax.distributed init

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
# one local device per process (the parent pytest env forces 8 for the
# single-process mesh tests; here the two processes ARE the mesh)
os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", "")).strip()
sys.path.insert(0, os.environ["VFT_REPO"])
import jax
# multiprocess CPU needs gloo collectives
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from video_features_tpu.parallel.pipeline import (
    maybe_initialize_distributed, shard_video_list)

multi = maybe_initialize_distributed()

# one cross-process collective over the federated 2-device mesh: the actual
# DCN communication path (psum of rank+1 over both processes -> 3.0 on each)
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(jax.devices(), ("hosts",))  # 2 global devices, 1 per process
local = jnp.full((1,), float(jax.process_index() + 1), jnp.float32)
summed = jax.jit(
    jax.shard_map(lambda x: jax.lax.psum(x, "hosts"), mesh=mesh,
              in_specs=P("hosts"), out_specs=P("hosts")),
)(jax.make_array_from_single_device_arrays(
    (2,), jax.NamedSharding(mesh, P("hosts")), [local]))
psum_val = float(summed.addressable_data(0)[0])

paths = [f"v{i:02d}.mp4" for i in range(7)]
print("RESULT " + json.dumps({
    "multi": bool(multi),
    "process_index": jax.process_index(),
    "process_count": jax.process_count(),
    "global_devices": len(jax.devices()),
    "psum": psum_val,
    "shard": shard_video_list(paths),
}), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_bootstrap_and_disjoint_shards():
    port = _free_port()
    env_base = {
        **os.environ,
        "VFT_REPO": REPO,
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
    }
    procs = []
    for rank in (0, 1):
        env = {**env_base, "JAX_PROCESS_ID": str(rank)}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = {}
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=220)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-2000:]}"
        line = [l for l in out.splitlines() if l.startswith("RESULT ")][-1]
        results[rank] = json.loads(line[len("RESULT "):])

    for rank, r in results.items():
        assert r["multi"] is True
        assert r["process_count"] == 2
        assert r["process_index"] == rank
        assert r["global_devices"] == 2
        assert r["psum"] == 3.0  # 1 + 2 across processes: the collective ran
    paths = [f"v{i:02d}.mp4" for i in range(7)]
    s0, s1 = results[0]["shard"], results[1]["shard"]
    assert s0 == paths[0::2] and s1 == paths[1::2]  # round-robin, gen_file_list semantics
    assert not (set(s0) & set(s1))
    assert sorted(s0 + s1) == sorted(paths)

"""Collective aggregation across REAL process boundaries (VERDICT r3 #6).

Spawns two ``jax.distributed`` CPU processes (2 local devices each → a
4-client global mesh) and runs :func:`collective_weighted_average` as a true
multi-controller SPMD program — the launch topology a multi-host TPU pod
uses, with the psum riding the distributed backend instead of
intra-process shared memory. Process 0 checks parity against the host
streaming-average oracle (``aggregate_inplace``)."""

import json
import subprocess
import sys

import numpy as np
import pytest

CHILD = r"""
import json, sys
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; out_path = sys.argv[3]
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2, process_id=pid)

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_tpu.parallel.collective_agg import (
    CLIENT_AXIS, collective_weighted_average, make_client_mesh,
)

N_CLIENTS = 4
assert len(jax.devices()) == N_CLIENTS, jax.devices()
mesh = make_client_mesh(N_CLIENTS)


def client_params(cid):
    rng = np.random.default_rng(cid)
    return {
        "w": rng.normal(size=(6, 4)).astype(np.float32),
        "b": rng.normal(size=(4,)).astype(np.float32),
    }

n_samples = np.asarray([10, 20, 5, 65], np.int32)
sharding = NamedSharding(mesh, P(CLIENT_AXIS))


def make_global(stacked_np):
    return jax.make_array_from_callback(
        stacked_np.shape, sharding, lambda idx: stacked_np[idx]
    )

stacked = {
    k: make_global(np.stack([client_params(c)[k] for c in range(N_CLIENTS)]))
    for k in ("w", "b")
}
ns = jax.make_array_from_callback(
    n_samples.shape, sharding, lambda idx: n_samples[idx]
)

avg = collective_weighted_average(stacked, ns, mesh)
# outputs are replicated -> fully addressable on every process
result = {k: np.asarray(v).tolist() for k, v in avg.items()}
with open(out_path, "w") as f:
    json.dump(result, f)
print(f"proc {pid} done", flush=True)
"""


@pytest.mark.slow
def test_collective_average_across_two_processes(tmp_path):
    from tests._helpers import free_port, subprocess_env

    port = free_port()
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    outs = [tmp_path / f"out_{pid}.json" for pid in range(2)]
    logs = [tmp_path / f"child_{pid}.log" for pid in range(2)]

    # child output goes to files, not PIPEs: proc 1's pipe is undrained
    # while proc 0 is being waited on — distributed-logging chatter past the
    # pipe buffer would deadlock the collective mid-psum
    procs = []
    for pid in range(2):
        with logs[pid].open("w") as logf:
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script), str(pid), str(port), str(outs[pid])],
                    env=subprocess_env(), stdout=logf, stderr=subprocess.STDOUT,
                    text=True,
                )
            )
    for pid, p in enumerate(procs):
        try:
            p.wait(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multiprocess collective aggregation timed out")
        assert p.returncode == 0, logs[pid].read_text()[-2000:]

    from photon_tpu.strategy.aggregation import aggregate_inplace

    def client_params(cid):
        rng = np.random.default_rng(cid)
        return [rng.normal(size=(6, 4)).astype(np.float32),
                rng.normal(size=(4,)).astype(np.float32)]

    n = [10, 20, 5, 65]
    oracle, total = aggregate_inplace(
        (client_params(c), n[c]) for c in range(4)
    )
    assert total == 100

    for out in outs:  # both controllers must hold identical averages
        got = json.loads(out.read_text())
        np.testing.assert_allclose(np.asarray(got["w"]), oracle[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got["b"]), oracle[1], rtol=1e-5, atol=1e-6)
